"""`ouro`'s part of the benchmark: the configuration's file against the catalog's keys, itself and
the contract; the cell's files by name and its entries appended (pinned from the front);
parameter and operation counts against hand sums; each new reader on a hand-built result (and
finding nothing on a program without the scopes); the loop's comparisons and the three controls
(fp8, three passes, an unweighted loss) failing the cell's limits at a small size; and the
driver's ``--tiny`` rehearsal end to end."""

import json
import math
import os
import re

import numpy as np
import pytest

from benchmark import flops_ouro as flops
from benchmark import ouro_trace
from benchmark import reduce_trace as rt
from benchmark import run as bench_run
from benchmark import weights_ouro as W
from benchmark.drivers import train_packed_loop as loop_driver
from benchmark.drivers import train_packed_tower as tower_driver
from benchmark.harness import RunResult
from benchmark.kernels import splash_attention, splash_attention_visited
from benchmark.spec import ROOT, Spec
from benchmark.xplane import Event

CELL = "train-ouro-loop4-packed8k"
PROGRAM = "77"
BODY = "transformer/blocks/while/body/closed_call/stack/pass/stack/pass/checkpoint"
FWD = f"jit(train_step)/jvp(OuroForCausalLM)/{BODY}"
BWD = f"jit(train_step)/transpose(jvp(OuroForCausalLM))/{BODY}"
# this configuration's own readers (files that no entry of BENCHMARK.json names: the pin of
# `test_bench_phases.py`, as for the three configurations before it), then the accepted phase readers printed beside them
NEW_READERS = ["mfu.ouro_train", "loop_blocks_share.train", "loop_head_loss_share.train", "pass_time_spread.train", "splash_roofline.ouro"]
PRINTED_ACCEPTED_READERS = [
    "blocks_fwd_ms.train", "blocks_bwd_ms.train", "head_loss_ms.train", "optimizer_ms.train", "unattributed_device_share.train",
    "host_between_steps_ms.train", "device_programs_per_step.train",
]
ACCEPTED_READERS_OF_THE_CELL = {"data_wait_share.train", "hbm_peak_gib.train", "device_idle_share.train"}
ACCEPTED_CELLS = ["train-3b-packed4k", "train-8b-packed4k", "train-nemotron-tower-packed8k", "train-joyai-flash-mtp-packed8k", "train-lfm2-moe-packed8k"]
ACCEPTED_CONFIGS = ["granite-3b-code", "granite-8b-code", "nemotron-twotower-30b-a3b", "joyai-llm-flash", "lfm2-24b-a2b"]
REDUCED = ["n_layer", "n_positions", "micro_batch_size", "gradient_accumulation_steps", "lr", "tensor_parallel_size"]
# the catalog's `config` of Ouro-2.6B (model-configs guide, architectures.jsonl), every key
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152,
}
LIMITS = {
    "loss_gap", "pass_loss_gap", "exit_mass_gap", "exit_mass_gap_last_step", "first_grad_norm_worst_block_leaf_gap", "first_grad_norm_wte_gap",
    "first_grad_norm_head_gap", "first_grad_norm_gate_gap", "param_change_norm_worst_leaf_gap",
}


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config["pretrained_config"]


# ---- the configuration's file and the cell's entry

def test_the_cell_resolves_to_its_files(cell):
    assert cell.config_name == "ouro-2.6b" and cell.traffic_name == "pretrain_packed_8k_loop" and cell.chips == 1
    assert cell.traffic["driver"] == "train_packed_loop"
    assert set(cell.limits) == LIMITS | {"reasons"}
    assert set(cell.limits["reasons"]) >= LIMITS | {"readings", "controls"}  # each limit with its reason, and which limit each control fails
    assert {m["name"] for m in cell.per_layer} == ACCEPTED_READERS_OF_THE_CELL
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s_per_chip", "setup_s"}
    spec = Spec.load()
    for name in NEW_READERS + PRINTED_ACCEPTED_READERS:
        assert hasattr(spec.layer_metric(name), "read")
    assert spec.driver(cell.traffic).run.__module__ == "benchmark_driver_train_packed_loop"
    weights_module, reference_module = tower_driver.modules_of(cell.config)
    assert weights_module is W and hasattr(reference_module, "train_steps")
    assert len(cell.why) <= 200 and cell.why.startswith("2 packed 8192-token rows") and "8/48" in cell.why and "1 part in 8" in cell.why


def test_the_traffic_is_the_accepted_8k_file_s_numbers_and_the_trainer_is_the_other_cells(cell):
    spec = Spec.load()
    tower = spec.cell("train-nemotron-tower-packed8k")
    differs = {k for k in tower.traffic if tower.traffic[k] != cell.traffic[k]}
    assert differs == {"driver", "what"} and set(cell.traffic) == set(tower.traffic)  # letter for letter, under another driver
    assert (cell.traffic["warmup_steps"], cell.traffic["check_steps"], cell.traffic["trace"]) == (6, 3, {"skip_steps": 4, "steps": 12})
    assert cell.traffic["document_tokens"] == {"distribution": "lognormal", "median": 600, "sigma": 1.0, "min": 16, "max": 16384}
    train = cell.config["train"]["training_args"]
    assert train["training_parameters"]["micro_batch_size"] == 2 and train["training_parameters"]["gradient_accumulation_steps"] == 1
    assert "2 packed rows" in cell.config["reduced"]["micro_batch_size"] and "2 packed rows of 8192" in cell.config["deployment"]
    assert train["model_args"]["reset_attention_mask"] and train["model_args"]["reset_position_ids"] and not train["model_args"]["scan_layers"]
    assert train["distributed_args"]["gradient_checkpointing_args"] == {"checkpoint_every": 1, "policy": "full"}
    tower_train = tower.config["train"]["training_args"]
    for group in ("optimizer_args", "lr_scheduler_args", "mixed_precision_args", "kernel_args", "distributed_args", "fault_tolerance_args", "training_parameters"):
        assert train[group] == tower_train[group], group  # the tower's trainer (its 2 rows, its 3e-5), another model
    assert train["optimizer_args"]["class_args"]["lr"] == 3e-5


def test_published_widths_and_the_cut(cell, cfg):
    """The catalog's keys at the top level, every one, unchanged (the vocabulary whole, the depth the
    published 48); ``pretrained_config`` saying the same in the program's names with the depth cut
    to the stage's 8 and the loop's 4 passes kept; no width among the reduced keys."""
    public = cell.config
    for key, value in CATALOG.items():
        assert public[key] == value, key
    assert public["published"] == {"num_hidden_layers": 48, "total_ut_steps": 4, "vocab_size": 49152, "max_position_embeddings": 65536}
    assert "six pipeline stages" in public["deployment"] and "round all six stages four times" in public["deployment"] and public["pipeline_stages"] == 6
    assert set(public["assumed"]) >= {"four-norm order", "norm inside the loop", "exit gate", "loss", "matrices", "z_loss_coef", "remat"}
    assert all("ISSUE 38" in public["assumed"][key] for key in ("four-norm order", "norm inside the loop", "exit gate", "loss"))
    assert "second training stage" in public["not_built"]
    same = {
        "hidden_size": "n_embd", "num_attention_heads": "n_head", "num_key_value_heads": "num_key_value_heads", "intermediate_size": "n_inner",
        "rms_norm_eps": "layer_norm_epsilon", "vocab_size": "vocab_size", "total_ut_steps": "total_ut_steps", "rope_theta": "rope_theta",
        "rope_scaling": "rope_scaling", "tie_word_embeddings": "tie_word_embeddings", "early_exit_threshold": "early_exit_threshold", "model_type": "model_type",
    }
    for theirs, ours in same.items():
        assert public[theirs] == cfg[ours], (theirs, ours)
    assert cfg["n_layer"] == 8 == public["num_hidden_layers"] // public["pipeline_stages"] and cfg["n_positions"] == 8192
    assert cfg["n_embd"] // cfg["n_head"] == public["head_dim"] == 128 and cfg["activation_function"] == "swiglu" and public["hidden_act"] == "silu"
    assert cfg["exit_entropy_coef"] == 0.05 and cfg["z_loss_coef"] == 1e-4 and not cfg["add_bias"]
    from dolomite_engine_tpu.models import config_from_dict

    built = config_from_dict(cfg)
    assert (built.block_applications, built.head_readings, built.head_dim, built.num_key_value_heads) == (32, 4, 128, 16)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (entry,) = [c for c in data["configs"] if c["name"] == "ouro-2.6b"]
    assert entry["reduced"] == REDUCED == list(public["reduced"])
    assert entry["source"] == public["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    width = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|expansion|experts_per_tok")
    assert not [key for key in entry["reduced"] if width.search(key)] and "total_ut_steps" not in entry["reduced"]
    assert cell.config["layer_metrics_without_an_entry"] == NEW_READERS + PRINTED_ACCEPTED_READERS


def test_the_cell_is_appended_after_the_accepted_ones_and_its_own_readers_wait_for_a_benchmark_pr():
    """The entries this PR appends stand directly after the accepted ones, which are where they
    were and as they were: pinned from the FRONT (a later cell appends after this one)."""
    data = Spec.load().data
    assert [w["name"] for w in data["workloads"]][:6] == ACCEPTED_CELLS + [CELL]
    assert [c["name"] for c in data["configs"]][:6] == ACCEPTED_CONFIGS + ["ouro-2.6b"]
    names = [m["name"] for m in data["per_layer"]]
    assert not set(NEW_READERS) & set(names)
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)
        if CELL in metric["workloads"]:
            assert metric["workloads"][:6] == ACCEPTED_CELLS + [CELL]  # appended, after lfm2's
    (rate,) = [m for m in data["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][:6] == ACCEPTED_CELLS + [CELL] and rate["bound"] == 0.02
    (entry,) = [w for w in data["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": "ouro-2.6b", "traffic": "pretrain_packed_8k_loop", "chips": 1, "why": entry["why"]}
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 0 and data["run_seconds"] == 45


# ---- counts against hand sums

def test_parameter_counts_by_hand(cfg, cell):
    counts = W.count_parameters(cfg)
    assert counts["attention_matmul"] == 4 * 2048 * 2048 and counts["mlp_matmul"] == 3 * 2048 * 5632
    assert counts["block"] == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416  # the issue's 51.4M
    assert counts["table"] == 49152 * 2048 == 100_663_296 and counts["gate"] == 2049
    assert (counts["passes"], counts["block_applications"]) == (4, 32)
    total = 8 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    assert counts["total"] == total == 612_438_017  # the issue's 612.5M; x 14 B = 8.6 GB of train state
    import jax

    shapes = jax.eval_shape(lambda: W.make_all(cfg, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    tiny = dict(cfg, **cell.config["tiny"])
    shapes = jax.eval_shape(lambda: W.make_all(tiny, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == W.count_parameters(tiny)["total"]


def test_required_operations_by_hand(cfg, cell):
    by_kind = flops.forward_flops_per_token_by_kind(cfg, attended_keys=1000.0)
    assert set(by_kind) == set(flops.KINDS)
    assert by_kind["attention_projections"] == 32 * 2 * 4 * 2048 * 2048
    assert by_kind["scores_values"] == 32 * 2 * 16 * 256 * 1000.0
    assert by_kind["mlp"] == 32 * 2 * 3 * 2048 * 5632
    assert by_kind["head"] == 4 * 2 * 49152 * 2048 and by_kind["exit_gate"] == 4 * 2 * 2048
    matmuls = 3 * (by_kind["attention_projections"] + by_kind["mlp"] + by_kind["head"])
    assert matmuls == 6 * (32 * (51_388_416 - 8192) + 4 * 100_663_296)  # the issue's 6 x (4 x L x block + 4 x head): 12.3 GFLOP a token
    assert 12.2e9 < matmuls < 12.4e9
    assert flops.train_flops_per_token(cfg, 1000.0) == 3 * sum(by_kind.values())
    # the head's four readings are 1 part in 8 of the block applications at this depth
    assert by_kind["head"] / (by_kind["attention_projections"] + by_kind["mlp"]) == pytest.approx(4 * 100_663_296 / (32 * 51_380_224))
    documents = flops.corpus_documents(cell.traffic, 45.0, 2, 8192)
    assert documents == int((6 + 45 * 6 + 2 + 2) * 2 * 8193 / (600 * np.exp(0.5)))
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192, documents)
    assert 900 < keys < 1300
    assert flops.train_flops_per_token(cfg, keys) > flops.train_flops_per_token(cfg, 0.0) > 0.9 * flops.train_flops_per_token(cfg, keys)


# ---- the readers on a hand-built result

def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def built_result(cfg, named=True, slow_pass=None, hoisted=False) -> RunResult:
    """Two traced steps of 1000 us busy each. Forward, an iteration of the loop a pass: a projection
    30, the splash forward 20, a block norm 10 (60 a pass; `slow_pass`'s norm takes 12 more; with
    `hoisted` a cast of 7 that the compiler moved out of the loop runs once, before the step's end);
    backward, from the last pass to the first: a replayed projection 60, the splash backward 50, a
    norm 10 (120 a pass); the gate 4, the chunked loss 120 and the passes' weighting 6 under the
    head; the optimizer 70, and 80 no scope names."""
    scope = (lambda s: "/" + s) if named else (lambda s: "")
    fwd = FWD if named else FWD.replace("/pass", "")
    bwd = BWD if named else BWD.replace("/pass", "")
    head = "jit(train_step)/jvp(OuroForCausalLM)/OuroForCausalLM.gated_loss"
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        for iteration in range(4):
            start = t + 60 * iteration + (12 if slow_pass is not None and iteration > slow_pass else 0)
            ops += [
                op("%fusion.1", start, 30, f"{fwd}/h_0/attn/c_attn/dot_general:"),
                op("%splash.1", start + 30, 20, f"{fwd}/h_0/attn/jit(_splash_attention)/{'splash_mha_fwd' if named else 'x'}/pallas_call:"),
                op("%fusion.2", start + 50, 22 if slow_pass == iteration else 10, f"{fwd}/h_0{scope('block_norms')}/ln_2/mul:"),
            ]
        extra = 12 if slow_pass is not None else 0
        for iteration in range(4):
            start = t + 240 + extra + 120 * iteration
            ops += [
                op("%fusion.3", start, 60, f"{bwd}/rematted_computation/h_0/mlp/c_fc/dot_general:"),
                op("%splash.2", start + 60, 50, f"{bwd}/h_0/attn/jit(_splash_attention)/{'splash_mha_dkv' if named else 'x'}/pallas_call:"),
                op("%fusion.4", start + 110, 10, f"{bwd}/h_0{scope('block_norms')}/ln_1/mul:"),
            ]
        start = t + 720 + extra
        ops += [
            op("%fusion.5", start, 4, f"{head}{scope('exit_gate')}/exit_gate/dot_general:"),
            op("%fusion.6", start + 4, 120, f"{head}/head_loss/loss_chunks/while/body/closed_call/ce_chunk/dot_general:"),
            op("%fusion.7", start + 124, 6, f"{head}/head_loss{scope('pass_weighting')}/mul:"),
            op("%fusion.8", start + 130, 70, "jit(train_step)/optimizer/add:"),
            op("%copy.1", start + 200, 80),
        ]
        if hoisted:
            ops.append(op("%convert.1", start + 280, 7, f"{fwd}/h_0/attn/c_attn/convert_element_type:"))
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, (1000 + extra) * 1e3, {}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    if named:
        telemetry.append({"kind": "event", "event": "splash_block_plan", "block_q": 512, "block_kv": 512, "rows": 2, "tables": "segment_ids"})
        for step in (11, 12):
            telemetry.append({"kind": "event", "event": "step_counters", "step": step, "splash_blocks_visited": 110, "splash_blocks_causal": 272, "pass_loss_1": 10.9})
    facts = dict(
        cfg=cfg, traced_steps=2, traced_first_step=11, tokens_per_step=16384, sequence_length=8192, rows=2, chips=1,
        rate_steps=2, rate_wall_s=4.0, first_measured_step=7, last_measured_step=30,
    )
    return RunResult(attempted=2, failed=0, end_to_end={}, checks=[], trace=trace, telemetry=telemetry, facts=facts)


def context(cell):
    class Context:
        peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
        seconds = 45.0

    Context.cell = cell
    return Context


def read(name, result, cell):
    return Spec.load().layer_metric(name).read(result, context(cell))


def test_new_readers_on_a_built_result(cfg, cell, capsys):
    result = built_result(cfg)
    peaks = context(cell).peaks
    assert read("loop_blocks_share.train", result, cell) == pytest.approx(100 * (4 * 60 + 4 * 120) / 1000)
    out = capsys.readouterr().out
    assert "ms a pass (forward + backward): [0.18, 0.18, 0.18, 0.18]" in out and "block_norms 0.08" in out
    assert read("loop_head_loss_share.train", result, cell) == pytest.approx(100 * (4 + 120 + 6) / 1000)
    assert "pass_weighting 0.006" in capsys.readouterr().out
    assert read("pass_time_spread.train", result, cell) == pytest.approx(1.0)
    assert ouro_trace.pass_seconds(result, 4) == [pytest.approx([180e-6] * 4)] * 2
    # a pass whose norm takes longer than the others': the third is 12 us of 180 slower
    assert read("pass_time_spread.train", built_result(cfg, slow_pass=2), cell) == pytest.approx(192 / 180)
    # an operation under `pass` that runs once a step lies outside the loop (a hoisted cast): no pass's
    assert ouro_trace.pass_seconds(built_result(cfg, hoisted=True), 4) == [pytest.approx([180e-6] * 4)] * 2
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192, flops.corpus_documents(cell.traffic, 45.0, 2, 8192))
    mfu = read("mfu.ouro_train", result, cell)
    assert mfu == pytest.approx(100 * flops.train_flops_per_token(cfg, keys) * (2 * 16384 / 4.0) / 1.97e14) and 0 < mfu < 100
    least, _ = splash_attention_visited.roofline_seconds(
        splash_attention_visited.train_flops(32, 16, 128, 512, 512, 2 * 110), splash_attention.train_bytes(32, 16, 16, 128, 8192, 4), peaks
    )
    assert read("splash_roofline.ouro", result, cell) == pytest.approx(100 * least / (2 * (4 * 20 + 4 * 50) * 1e-6))
    assert "32 applications a step" in capsys.readouterr().out
    # the accepted phase readers the file has printed beside them
    assert read("unattributed_device_share.train", result, cell) == pytest.approx(100 * (80 + 4) / 1000)  # (the gate's 4 us stand under no phase scope)
    assert read("head_loss_ms.train", result, cell) == pytest.approx((120 + 6) / 1000)
    assert read("blocks_fwd_ms.train", result, cell) + read("blocks_bwd_ms.train", result, cell) == pytest.approx((240 + 480) / 1000)
    assert read("optimizer_ms.train", result, cell) == pytest.approx(70 / 1000)
    assert read("device_programs_per_step.train", result, cell) == 1.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_where_the_program_has_no_such_scope_or_counter(name, cfg, cell):
    """A program without these scopes and counters (the parent's), another model's cell and
    configuration: nothing is read and nothing is raised."""
    other = Spec.load().cell("train-8b-packed4k")
    result = built_result(cfg, named=False)
    result.facts["cfg"] = dict(other.config["pretrained_config"], n_layer=2)
    assert read(name, result, other) is None
    untraced = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], facts={})
    assert read(name, untraced, cell) is None
    # ... and this configuration on a program that names no scope: still nothing, still no raise
    unnamed = built_result(cfg, named=False)
    assert read(name, unnamed, cell) is None or name == "mfu.ouro_train"  # (a utilization on the host's clock needs no scope)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_the_recorded_small_trace(name, cfg, cell):
    """``benchmark/testdata/small.xplane.pb.gz`` (PR 23: a v5e trace of a program from before any
    scope) under this cell's facts: no `jit_train_step`, no scopes, no counters — nothing to read,
    nothing raised (the utilization on the host's clock needs no trace: it reads)."""
    trace = rt.reduce_trace(os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz"))
    facts = dict(
        cfg=cfg, traced_steps=4, traced_first_step=1, tokens_per_step=16384, sequence_length=8192, rows=2, chips=1,
        rate_steps=4, rate_wall_s=10.0, first_measured_step=1, last_measured_step=4,
    )
    recorded = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], trace=trace, facts=facts)
    value = read(name, recorded, cell)
    assert value is None or (name == "mfu.ouro_train" and 0 < value < 100)


# ---- the comparisons

OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
SMALL = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4, num_key_value_heads=4, n_inner=96, total_ut_steps=4, rope_theta=1e6,
    eos_token_id=0, z_loss_coef=1e-4, exit_entropy_coef=0.05,
)


@pytest.fixture(scope="module")
def followed():
    from benchmark.reference import ouro as reference

    seed = 2**31 + 1
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        text = rng.integers(1, SMALL["vocab_size"], size=(2, 129)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 120, size=3)] = 0  # document boundaries
        batches.append(text)
    run = lambda **kwargs: reference.train_steps(SMALL, seed, batches, OPTIMIZER, **kwargs)  # noqa: E731
    return {"sound": run(), "fp8": run(quant="fp8"), "three_passes": run(passes=3), "unweighted": run(weigh=False)}


def failed(control: dict, sound: dict, limits: dict) -> set:
    checks = loop_driver.loop_checks(control["losses"], control["grad_norms"], control["delta_norms"], control, sound, limits)
    return {c.name.split("_step")[0] for c in checks if not c.ok}


def test_the_reference_passes_its_own_limits_and_every_check_has_a_limit(cell, followed):
    sound = followed["sound"]
    checks = loop_driver.loop_checks(sound["losses"], sound["grad_norms"], sound["delta_norms"], sound, sound, cell.limits)
    assert all(c.ok and c.value == 0 and math.isfinite(c.limit) for c in checks)
    assert {c.name.split("_step")[0] for c in checks} == LIMITS - {"exit_mass_gap_last_step"} and len(checks) == 3 * 3 + 5
    # the exit mass is held to its limit at every checked step but the last, which has a limit of its own that fails nothing
    by_name = {c.name: c.limit for c in checks}
    assert by_name["exit_mass_gap_step1"] == by_name["exit_mass_gap_step2"] == cell.limits["exit_mass_gap"] < 0.05
    assert by_name["exit_mass_gap_step3"] == cell.limits["exit_mass_gap_last_step"] == 1.0 and by_name["pass_loss_gap_step3"] == cell.limits["pass_loss_gap"]
    assert len(sound["pass_losses"]) == 3 and len(sound["pass_losses"][0]) == 4 and sum(sound["exit_mass"][0]) == pytest.approx(1.0, abs=1e-5)
    assert {"wte", "lm_head", "ln_f", "gate_w", "gate_b", "layer0.ln_1_out", "layer1.mlp_c_proj"} <= set(sound["grad_norms"])
    # a step that returned no counters, or another count of passes, is no match
    assert loop_driver.worst_pass_gap(None, [1.0, 2.0]) == math.inf == loop_driver.worst_pass_gap([1.0], [1.0, 2.0]) == loop_driver.worst_pass_gap([1.0, None], [1.0, 2.0])
    assert loop_driver.worst_pass_gap([1.0, 2.5], [1.0, 2.0]) == 0.5


def test_the_three_controls_each_fail_the_limits_the_file_names(cell, followed):
    """`reasons.controls` says which limit each control must exceed; here at a small size, on the CPU."""
    named = cell.limits["reasons"]["controls"]
    assert set(named) == {"fp8", "three_passes", "unweighted"}
    sound = followed["sound"]
    three = failed(followed["three_passes"], sound, cell.limits)
    # a pass dropped: another count of passes (no match), and the gate's gradient a third off (clipping to one
    # global norm hides the blocks' lost quarter: their leaves keep their proportions)
    assert {"exit_mass_gap", "pass_loss_gap", "first_grad_norm_gate_gap"} <= three and "loss_gap" not in three
    plain = failed(followed["unweighted"], sound, cell.limits)
    # the gate out of the loss: its gradient is 0 and it never moves; the loss lacks beta x the entropy; the first step's passes are sound
    assert {"first_grad_norm_gate_gap", "loss_gap", "param_change_norm_worst_leaf_gap"} <= plain
    assert followed["unweighted"]["grad_norms"]["gate_w"] == 0.0
    assert failed(followed["fp8"], sound, cell.limits)  # the precision control fails at least one
    for name, fails in (("three_passes", three), ("unweighted", plain)):
        assert all(limit in fails for limit in re.findall(r"\b(?:\w+_gap)\b", named[name].split(":")[0])), (name, fails)


def test_the_driver_stands_at_three_attributes_of_the_tower_s_and_puts_them_back(cell):
    before = (tower_driver.modules_of, tower_driver.compare_with_reference, tower_driver.read_telemetry)

    class Stop(Exception):
        pass

    class Context:
        control = False

    Context.cell = cell
    saved = tower_driver.run
    seen = {}

    def run(ctx):
        seen["attributes"] = (tower_driver.modules_of, tower_driver.compare_with_reference, tower_driver.read_telemetry)
        raise Stop

    tower_driver.run = run
    try:
        with pytest.raises(Stop):
            loop_driver.run(Context)
    finally:
        tower_driver.run = saved
    assert all(mine is not theirs for mine, theirs in zip(seen["attributes"], before))
    assert (tower_driver.modules_of, tower_driver.compare_with_reference, tower_driver.read_telemetry) == before


# ---- the rehearsal

def test_tiny_rehearsal_runs_the_trainer_and_is_never_correct(capsys):
    try:
        line, checks = bench_run.execute(CELL, 2**31 + 5, 6.0, False, tiny=True)
    except RuntimeError as error:
        # a machine so loaded that one toy step outlasts the window (`test_bench_lfm2.py` says when): once more, with room
        if "the window did not close" not in str(error):
            raise
        line, checks = bench_run.execute(CELL, 2**31 + 5, 60.0, False, tiny=True)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    by_name = {c.name: c for c in checks}
    assert {"loss_gap_step1", "loss_gap_step3", "pass_loss_gap_step1", "exit_mass_gap_step3", "first_grad_norm_worst_block_leaf_gap",
            "first_grad_norm_head_gap", "first_grad_norm_gate_gap", "param_change_norm_worst_leaf_gap", "compilations_in_window"} <= set(by_name)
    assert by_name["loss_gap_step1"].value < 0.05 and by_name["pass_loss_gap_step1"].value < 0.05 and by_name["exit_mass_gap_step1"].value < 0.01
    assert by_name["compilations_in_window"].value == 0 and by_name["nonfinite_losses"].ok
    assert "2 row(s) x 1 x 128 tokens a step" in capsys.readouterr().out
