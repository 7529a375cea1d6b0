"""The two proofs that `correct` can come out false.

1. The control — the plain reference computed as an fp8 recipe computes, the step in
   precision below the bfloat16 that the configurations state — put in the program's place
   at a size a test run can hold, must fail the cells' own limits (on the chip, at the
   cells' sizes, it was read on three seeds a cell: PERF.md section 2).
2. The harness without its look for a chip (`--tiny`) drives the rest of a run with the
   timed path broken underneath — a train step that returns its state unchanged, a served
   token altered where it is produced — and the numbers compared leave their limits, while
   the sound path stays inside them.
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.drivers.train_packed import compare_with_reference
from benchmark.reference import gpt_dense
from benchmark.spec import ROOT, Spec

TRAIN_CELLS = ("train-3b-packed4k", "train-8b-packed4k")
TRAIN_CFG = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4, num_key_value_heads=2, n_inner=256,
    eos_token_id=0, rope_theta=10000, z_loss_coef=1.0e-4,
)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
# tied embeddings at toy depth make one logit tower over the rest, and no rounding moves it;
# a wider initialisation lets the blocks' outputs dominate, as 32 layers do at full size
SERVE_CFG = dict(
    vocab_size=4096, n_positions=256, n_embd=128, n_layer=4, n_head=4, num_key_value_heads=2, n_inner=512,
    eos_token_id=0, rope_theta=10000, initializer_range=0.1,
)


# No serving cell is in BENCHMARK.json yet. These are the limits the serve driver's chip
# rehearsals were held to (PR 23: sound runs' widest gap under 0.4 and mean under 0.0054 in 26
# runs on 16 seeds; the fp8 control's smallest 1.37 and 0.346 on 3 seeds; at answers of median
# 24 tokens): a serving cell reads its own at its own lengths.
SERVE_LIMITS = {"served_token_gap_widest": 0.4, "served_token_gap_mean": 0.02}
SERVE_METRICS = ("ttft_p95_ms", "itl_p95_ms", "serve_out_tokens_per_s")


def limits(cell: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "limits", cell + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [2**31 + 1, 17, 99991])
def test_fp8_control_fails_the_training_limits(seed):
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        row = rng.integers(1, TRAIN_CFG["vocab_size"], size=(1, 129)).astype(np.int32)
        row[0, rng.integers(5, 120, size=3)] = 0  # document boundaries
        batches.append(row)
    reference = gpt_dense.train_steps(TRAIN_CFG, seed, batches, OPTIMIZER)
    control = gpt_dense.train_steps(TRAIN_CFG, seed, batches, OPTIMIZER, quant="fp8")
    for cell in TRAIN_CELLS:
        checks = compare_with_reference(
            control["losses"], control["grad_norms"], control["delta_norms"], reference, limits(cell)
        )
        by_name = {c.name: c for c in checks}
        # the lower precision has to fail one of a cell's numbers: it fails the first gradient
        assert not by_name["first_grad_norm_worst_block_leaf_gap"].ok, (cell, by_name["first_grad_norm_worst_block_leaf_gap"])
        assert not all(c.ok for c in checks)
        # and the reference held against itself is inside every limit
        same = compare_with_reference(
            reference["losses"], reference["grad_norms"], reference["delta_norms"], reference, limits(cell)
        )
        assert all(c.ok and c.value == 0 for c in same)


@pytest.mark.parametrize("seed", [2**31 + 1, 17, 99991])
def test_fp8_control_fails_the_serving_limits(seed):
    rng = np.random.default_rng(seed)
    sequences = []
    for length in (128, 100, 60):
        tokens = rng.integers(1, SERVE_CFG["vocab_size"], size=length).tolist()
        sequences.append((tokens[:1], tokens[1:]))  # read the control at every position
    gaps = gpt_dense.served_token_gaps(SERVE_CFG, seed, sequences, bucket=64, control=True)
    control = np.concatenate([g["control_gap"] for g in gaps])
    limit = SERVE_LIMITS
    assert control.max() > limit["served_token_gap_widest"] or control.mean() > limit["served_token_gap_mean"]
    assert control.mean() > limit["served_token_gap_mean"]


def checks_of(workload: str, seed: int, seconds: float, spec=None) -> dict:
    line, checks = bench_run.execute(workload, seed, seconds, False, tiny=True, spec=spec)
    assert line["correct"] is False  # a rehearsal never says correct
    if spec is not None:
        assert set(line["metrics"]) == {"setup_s", *SERVE_METRICS}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    return {c.name: c for c in checks}


@pytest.fixture()
def serve_spec(tmp_path):
    """The benchmark with a serving cell ADDED as data files and entries alone: the chat mix of
    ``benchmark/testdata`` (at a rate that puts some requests into a rehearsal's window) on
    ``granite-3b-code``'s ``serve`` section, judged on the tails that ISSUE 23 names."""
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "drivers", "layer_metrics", "limits"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), bench / sub)
    with open(os.path.join(ROOT, "benchmark", "testdata", "chat_rehearsal.json")) as f:
        (bench / "traffic" / "chat_open_loop.json").write_text(json.dumps(dict(json.load(f), rate_per_s=2.0)))
    (bench / "limits" / "serve-3b-chat.json").write_text(json.dumps(SERVE_LIMITS))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["workloads"].append({"name": "serve-3b-chat", "config": "granite-3b-code", "traffic": "chat_open_loop", "chips": 1, "why": "test"})
    for name in SERVE_METRICS:
        unit, better = ("tokens/s", "higher") if name.endswith("per_s") else ("ms", "lower")
        data["end_to_end"].append({"name": name, "unit": unit, "better": better, "bound": 0.05, "source": "host_clock", "workloads": ["serve-3b-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    return Spec.load(str(tmp_path))


def test_train_sound_passes_and_a_step_that_keeps_its_state_fails(monkeypatch):
    sound = checks_of("train-3b-packed4k", 2**31 + 5, 1.0)
    assert all(c.ok for c in sound.values()), [c for c in sound.values() if not c.ok]

    from dolomite_engine_tpu import pretrain

    real = pretrain.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def keeps_its_state(state, batch, rng):
            new_state, metrics = step(state, batch, rng)
            return state.replace(step=new_state.step, opt_state=new_state.opt_state), metrics

        return keeps_its_state

    monkeypatch.setattr(pretrain, "make_train_step", broken)
    faulty = checks_of("train-3b-packed4k", 2**31 + 5, 1.0)
    assert not faulty["param_change_norm_worst_leaf_gap"].ok
    assert faulty["param_change_norm_worst_leaf_gap"].value == pytest.approx(1.0)  # no change at all
    assert not faulty["loss_after_window_minus_first"].ok or not faulty["loss_gap_step3"].ok
    assert faulty["first_grad_norm_worst_block_leaf_gap"].ok and faulty["first_grad_norm_wte_gap"].ok  # the gradient was sound


def test_serve_sound_passes_and_an_altered_token_fails(monkeypatch, serve_spec):
    sound = checks_of("serve-3b-chat", 2**31 + 6, 4.0, serve_spec)
    assert all(c.ok for c in sound.values()), [c for c in sound.values() if not c.ok]

    from dolomite_engine_tpu.serving.engine import ServingEngine

    deliver = ServingEngine._deliver
    monkeypatch.setattr(
        ServingEngine, "_deliver", lambda self, state, token: deliver(self, state, token + 1 if token < 500 else token - 1)
    )
    faulty = checks_of("serve-3b-chat", 2**31 + 6, 4.0, serve_spec)
    assert not faulty["served_token_gap_widest"].ok and not faulty["served_token_gap_mean"].ok
    assert faulty["chunk_programs_after_window"].ok and faulty["compilations_in_window"].ok
