"""The `nemotron_h` tower's part of the benchmark: the configuration's file against itself and
the contract, the cell's files by name, the counts against hand sums for one layer, each new
reader on a hand-built result (and finding nothing on a program without the scopes), the
driver's comparisons, the fp8 control failing the cell's limits at a small size, and the
driver's ``--tiny`` rehearsal end to end."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_nemotron_h as flops
from benchmark import reduce_trace as rt
from benchmark import run as bench_run
from benchmark import tower_trace
from benchmark import weights_nemotron_h as W
from benchmark.drivers import train_packed_tower as driver
from benchmark.harness import RunResult
from benchmark.kernels import mamba2_scan, moe_grouped_matmul, splash_attention
from benchmark.spec import ROOT, Spec
from benchmark.xplane import Event

CELL = "train-nemotron-tower-packed8k"
PROGRAM = "77"
FWD = "jit(train_step)/jvp(NemotronHForCausalLM)/transformer/blocks"
BWD = "jit(train_step)/transpose(jvp(NemotronHForCausalLM))/transformer/blocks/jvp(NemotronHForCausalLM)/transformer/blocks/checkpoint"
# the tower's own readers: files that no entry of BENCHMARK.json names yet (the docstring of
# `test_the_tower_s_readers_wait_for_a_benchmark_pr` says why); the configuration's file lists
# them and the driver prints what they read after a traced run
NEW_READERS = [
    "mamba2_scan_roofline", "moe_grouped_matmul_roofline", "splash_roofline.tower", "mamba_mixer_share.train", "moe_share.train",
    "attention_share.train", "expert_rows_max_over_mean.train", "mfu.tower_train",
]
ACCEPTED_READERS_OF_THE_CELL = {"data_wait_share.train", "hbm_peak_gib.train", "device_idle_share.train"}


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config["pretrained_config"]


# ---- the configuration's file

def test_the_cell_resolves_to_its_files(cell):
    assert cell.config_name == "nemotron-twotower-30b-a3b" and cell.traffic_name == "pretrain_packed_8k"
    assert cell.traffic["driver"] == "train_packed_tower" and cell.chips == 1
    assert set(cell.limits) >= {"loss_gap", "first_grad_norm_worst_block_leaf_gap", "routed_rows_histogram_gap"}
    assert {m["name"] for m in cell.per_layer} == ACCEPTED_READERS_OF_THE_CELL  # no dense count: not mfu.train, not splash_roofline
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s_per_chip", "setup_s"}
    for name in NEW_READERS:
        assert hasattr(Spec.load().layer_metric(name), "read")
    weights_module, reference_module = driver.modules_of(cell.config)
    assert weights_module is W and hasattr(reference_module, "train_steps")


def test_published_widths_and_the_cut(cell, cfg):
    """The public config.json's keys at the top level, unchanged but for the cuts the file
    lists, and ``pretrained_config`` saying the same in the program's names."""
    public = cell.config
    assert (public["hidden_size"], public["mamba_num_heads"], public["mamba_head_dim"], public["n_groups"]) == (2688, 64, 64, 8)
    assert (public["ssm_state_size"], public["conv_kernel"], public["chunk_size"]) == (128, 4, 128)
    assert (public["num_attention_heads"], public["num_key_value_heads"], public["head_dim"]) == (32, 2, 128)
    assert (public["num_experts_per_tok"], public["routed_scaling_factor"], public["moe_intermediate_size"]) == (6, 2.5, 1856)
    assert public["moe_shared_expert_intermediate_size"] == 3712 and public["mlp_hidden_act"] == "relu2"
    assert public["published"]["n_routed_experts"] == 128 and public["n_routed_experts"] == 8
    assert public["num_hidden_layers"] == 52 == len(public["hybrid_override_pattern"])  # as published: the cut is n_layer
    assert cfg["hybrid_override_pattern"] == "MEMEM*EME" == public["hybrid_override_pattern"][:9] and cfg["n_layer"] == 9
    assert public["vocab_size"] == 16384 == public["published"]["vocab_size"] // 8
    assert public["chips_sharing_a_layer"] == 16 and public["not_built"] and public["assumed"] and public["source"].startswith("https://")
    assert set(public["reduced"]) >= {"n_layer", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}
    same = {
        "hidden_size": "n_embd",
        "num_attention_heads": "n_head", "num_key_value_heads": "num_key_value_heads", "head_dim": "attention_head_dim",
        "mamba_num_heads": "mamba_num_heads", "mamba_head_dim": "mamba_head_dim", "n_groups": "mamba_n_groups",
        "ssm_state_size": "ssm_state_size", "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
        "num_experts_per_tok": "num_experts_per_tok", "moe_intermediate_size": "moe_intermediate_size",
        "moe_shared_expert_intermediate_size": "moe_shared_expert_intermediate_size", "routed_scaling_factor": "routed_scaling_factor",
        "norm_topk_prob": "norm_topk_prob", "vocab_size": "vocab_size", "layer_norm_epsilon": "layer_norm_epsilon",
        "mlp_hidden_act": "activation_function", "tie_word_embeddings": "tie_word_embeddings", "use_conv_bias": "use_conv_bias",
        "time_step_min": "time_step_min", "time_step_max": "time_step_max", "time_step_floor": "time_step_floor",
    }
    for theirs, ours in same.items():
        assert public[theirs] == cfg[ours], (theirs, ours)
    assert cfg["num_experts"] == 128 and cfg["experts_held"] == [0, public["n_routed_experts"]]
    from dolomite_engine_tpu.models import config_from_dict

    built = config_from_dict(cfg)
    assert built.mamba_inner == 4096 and built.mamba_conv_dim == 6144 and built.held_experts() == (0, 8)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == "nemotron-twotower-30b-a3b"][0]
    assert set(entry["reduced"]) == set(public["reduced"]) and entry["source"] == public["source"]


def test_the_tower_s_readers_wait_for_a_benchmark_pr(cell):
    """``test_bench_phases.py`` pins ``BENCHMARK.json``'s last seven ``per_layer`` entries to
    PR 24's readers and their ``workloads`` to the two dense cells, and the contract wants new
    entries at the end of a list: no metric can be appended, and the cell cannot join those
    seven, until a ``benchmark`` PR rewrites that test (PERF.md section 7). So the cell reports
    the three accepted readers the pin leaves free, and the tower's own are files without an
    entry, named by the configuration's file so that the driver prints them in a traced run."""
    from tests.benchmark.test_bench_phases import READERS

    data = Spec.load().data
    names = [m["name"] for m in data["per_layer"]]
    assert names[-7:] == READERS and not set(NEW_READERS) & set(names)
    assert cell.config["layer_metrics_without_an_entry"] == NEW_READERS
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)


# ---- counts against hand sums

def test_parameter_counts_by_hand(cfg):
    counts = W.count_parameters(cfg)
    assert counts["mamba_matmul"] == 2688 * 10304 + 4096 * 2688 == 38_707_200
    assert counts["attention_matmul"] == 2688 * 4608 + 4096 * 2688 == 23_396_352
    assert counts["routed_expert"] == 2 * 2688 * 1856 and counts["shared_expert"] == 2 * 2688 * 3712
    assert counts["layers_of_kind"] == {"M": 4, "E": 4, "*": 1}
    per_mamba = 38_707_200 + 6144 * 5 + 3 * 64 + 4096
    per_experts = 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856
    total = 4 * per_mamba + 23_396_352 + 4 * per_experts + 9 * 2688 + 2 * 16384 * 2688 + 2688
    assert counts["total"] == total and 660e6 < total < 675e6  # the issue's 667M


def test_required_operations_of_one_layer_by_hand(cfg):
    one = dict(cfg)
    scan = (8 * 128 + 64 * 64) * 129 + 4 * 64 * 64 * 128
    assert flops.scan_forward_flops_per_token(cfg) == scan == 2_757_632
    by_kind = flops.forward_flops_per_token_by_kind(one, 8192, routed_slots_per_token=0.375)
    assert by_kind["M"] == 4 * (2 * 38_707_200 + 2 * 4 * 6144 + scan)
    assert by_kind["*"] == 2 * 23_396_352 + 4 * 32 * 128 * 8193 / 2
    assert by_kind["E"] == 4 * (2 * (2688 * 128 + 2 * 2688 * 3712) + 2 * (2 * 2688 * 1856) * 0.375)
    assert by_kind["head"] == 2 * 16384 * 2688
    assert flops.even_routed_slots_per_token(cfg) == 6 * 8 / 128 == 0.375
    assert flops.train_flops_per_token(cfg, 8192) == 3 * sum(by_kind.values())
    blocks = by_kind["M"] + by_kind["E"] + by_kind["*"]
    assert 0.50 < by_kind["M"] / blocks < 0.60 and 0.25 < by_kind["E"] / blocks < 0.36  # the cell's `why`
    # the routed experts count by what is routed here, not by top_k
    assert flops.train_flops_per_token(cfg, 8192, 6.0) > 1.5 * flops.train_flops_per_token(cfg, 8192, 0.375)


def test_kernel_counts_by_hand(cfg):
    tokens = 16384
    assert mamba2_scan.train_flops(cfg, tokens) == 3 * 2_757_632 * tokens * 4
    per_token = (4096 + 2048 + 64) + 4096 + (4096 + 2048 + 64) + 4096 + (4096 + 2048 + 64)
    assert mamba2_scan.train_bytes(cfg, tokens) == tokens * 4 * per_token * 2
    rows = 6144.0
    assert moe_grouped_matmul.train_flops(cfg, rows) == 3 * 2 * 2 * 2688 * 1856 * rows
    bank = 8 * 2688 * 1856
    expected = rows * (2688 + 1856 + 2688) * 2 + rows * (2 * 2688 + 2 * 1856 + 2688) * 2 + 4 * (2 * bank * 2 + 2 * bank * 2 + 2 * bank * 4)
    assert moe_grouped_matmul.train_bytes(cfg, rows, layer_steps=4) == expected
    least, bound = moe_grouped_matmul.roofline_seconds(1.97e14, 8.19e11, {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11})
    assert bound == "compute" and least == pytest.approx(1.0)


# ---- the readers on a hand-built result

def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def built_result(cfg, named=True) -> RunResult:
    """Two traced steps of 1000 us busy each: a Mamba layer (in-projection 100, a scan `while`
    of 120 over 100 of its own operations, its backward 200), a layer of experts (router 20,
    dispatch 30, the grouped products 50 under their scope and 40 more as the compiler's own
    unnamed `ragged-dot`, shared expert 150), attention (splash 90 under its scope, 60 more in
    the backward), the head's 100 and the optimizer's 40."""
    scope = (lambda s: "/" + s) if named else (lambda s: "")
    module = "moe" if named else "ffn"  # (the program's module is named as its scope is)
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        ops += [
            op("%fusion.1", t, 100, f"{FWD}/h_0{scope('mamba_mixer')}/mixer{scope('mamba_in_proj')}/in_proj/dot_general:"),
            op("%while.1", t + 100, 120, category="while"),
            op("%fusion.2", t + 110, 100, f"{FWD}/h_0{scope('mamba_mixer')}/mixer{scope('mamba2_scan')}/while/body/mul:"),
            op("%fusion.3", t + 220, 200, f"{BWD}/h_0{scope('mamba_mixer')}/mixer{scope('mamba2_scan')}/bcgrls,bcsgrp->bclgrp/dot_general:"),
            op("%fusion.4", t + 420, 20, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_router')}/dot_general:"),
            op("%fusion.5", t + 440, 30, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_dispatch')}/sort:"),
            op("%fusion.6", t + 470, 50, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_experts')}/square:"),
            # (the benchmark's programs before this PR ran no grouped product)
            op("%ragged-dot-none.3 = bf16[24576,1856] custom-call(...)" if named else "%fusion.r", t + 520, 40, "ragged-dot-none:" if named else "", category="custom-call"),
            op("%fusion.7", t + 560, 150, f"{BWD}/h_1{scope('moe')}/{module}{scope('moe_shared_expert')}/shared_c_fc/dot_general:"),
            op("%splash.1", t + 710, 90, f"{FWD}/h_5{scope('attention')}/attn/vmap(jit(_splash_attention))/splash_mha_fwd/pallas_call:"),
            op("%fusion.8", t + 800, 60, f"{BWD}/h_5{scope('attention')}/attn/c_attn/dot_general:"),
            op("%fusion.9", t + 860, 100, "jit(train_step)/jvp(NemotronHForCausalLM)/head_loss/loss_chunks/while/body/closed_call/ce_chunk/dot_general:"),
            op("%fusion.10", t + 960, 40, "jit(train_step)/optimizer/add:"),
        ]
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    if named:
        for step in (11, 12):
            telemetry.append({
                "kind": "event", "event": "step_counters", "step": step,
                "routed_slots": [6000, 8000, 4000, 6576], "absent_slots": [92304, 90304, 94304, 91728],
                "fullest_expert_rows": [1500, 4000, 600, 822], "held_expert_rows": [[750] * 8] * 4,
            })
    facts = dict(
        cfg=cfg, traced_steps=2, traced_first_step=11, tokens_per_step=16384, sequence_length=8192, rows=2, chips=1,
        rate_steps=2, rate_wall_s=1.0, first_measured_step=7, last_measured_step=40,
    )
    return RunResult(attempted=2, failed=0, end_to_end={}, checks=[], trace=trace, telemetry=telemetry, facts=facts)


class Peaks:
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def read(name, result):
    return Spec.load().layer_metric(name).read(result, Peaks)


def test_new_readers_on_a_built_result(cfg):
    result = built_result(cfg)
    assert tower_trace.scope_table(result)["busy_s"] == pytest.approx(2 * 1000e-6)
    assert read("mamba_mixer_share.train", result) == pytest.approx(100 * 400 / 1000)
    # the compiler's own `ragged-dot`, which carries no scope, is the experts' grouped product
    assert read("moe_share.train", result) == pytest.approx(100 * (20 + 30 + 50 + 40 + 150) / 1000)
    assert read("attention_share.train", result) == pytest.approx(100 * 150 / 1000)
    tokens = 2 * 16384
    least, _ = mamba2_scan.roofline_seconds(mamba2_scan.train_flops(cfg, tokens), mamba2_scan.train_bytes(cfg, tokens), Peaks.peaks)
    assert read("mamba2_scan_roofline", result) == pytest.approx(100 * least / (2 * 300e-6))
    rows = 2 * 24576.0
    least, _ = moe_grouped_matmul.roofline_seconds(
        moe_grouped_matmul.train_flops(cfg, rows), moe_grouped_matmul.train_bytes(cfg, rows, 8), Peaks.peaks
    )
    assert read("moe_grouped_matmul_roofline", result) == pytest.approx(100 * least / (2 * 90e-6))
    # one `*` in MEMEM*EME: one layer's kernel over 2 rows x 2 steps, against the 90 us a step under `splash_mha*`
    least, _ = splash_attention.roofline_seconds(
        splash_attention.train_flops(1, 32, 128, 8192, 4), splash_attention.train_bytes(1, 32, 2, 128, 8192, 4), Peaks.peaks
    )
    assert read("splash_roofline.tower", result) == pytest.approx(100 * least / (2 * 90e-6))
    ratios = [1500 * 8 / 6000, 4000 * 8 / 8000, 600 * 8 / 4000, 822 * 8 / 6576]
    assert read("expert_rows_max_over_mean.train", result) == pytest.approx(sum(ratios) / 4)
    slots = 24576 / 4 / 16384
    assert tower_trace.routed_slots_per_token(result) == pytest.approx(slots)
    assert read("mfu.tower_train", result) == pytest.approx(100 * flops.train_flops_per_token(cfg, 8192, slots) * 2 * 16384 / 1.97e14)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_where_the_program_has_no_such_scope_or_counter(name, cfg):
    """A program from before this PR: no scope names, no ``step_counters``, another model's
    configuration in the facts. Nothing is read and nothing is raised."""
    result = built_result(cfg, named=False)
    result.facts["cfg"] = {"n_embd": 64, "n_layer": 2}
    assert read(name, result) is None
    untraced = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], facts={})
    assert read(name, untraced) is None


# ---- the comparisons

OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
SMALL = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=5, hybrid_override_pattern="MEM*E", n_head=4, num_key_value_heads=2,
    attention_head_dim=16, mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=32,
    num_experts=16, num_experts_per_tok=3, experts_held=[4, 4], moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
    routed_scaling_factor=2.5, eos_token_id=0, z_loss_coef=1e-4,
)


def test_histogram_gap_is_the_least_share_of_slots_that_moved():
    assert driver.histogram_gap([[10, 20]], [[10, 20]]) == 0
    assert driver.histogram_gap([[11, 19], [5, 5]], [[10, 20], [5, 5]]) == pytest.approx(1 / 30)


@pytest.mark.parametrize("seed", [2**31 + 1, 17])
def test_fp8_control_fails_the_cell_s_limits_and_the_reference_passes_them(cell, seed):
    from benchmark.reference import nemotron_h_tower as reference

    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        text = rng.integers(1, SMALL["vocab_size"], size=(2, 129)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 120, size=3)] = 0  # document boundaries
        batches.append(text)
    sound = reference.train_steps(SMALL, seed, batches, OPTIMIZER)
    control = reference.train_steps(SMALL, seed, batches, OPTIMIZER, quant="fp8")
    rows = lambda out: [r["held_expert_rows"] for r in out["routing"]]  # noqa: E731
    checks = driver.compare_with_reference(
        control["losses"], control["grad_norms"], control["delta_norms"], rows(control), sound, cell.limits
    )
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
    same = driver.compare_with_reference(sound["losses"], sound["grad_norms"], sound["delta_norms"], rows(sound), sound, cell.limits)
    names = {c.name for c in same}
    assert {"first_grad_norm_routed_experts_gap", "routed_rows_histogram_gap", "router_choices_moved_share"} <= names
    assert all(c.value == 0 for c in same if c.name != "router_choices_moved_share")
    missing = driver.compare_with_reference(sound["losses"], sound["grad_norms"], sound["delta_norms"], [None] * 3, sound, cell.limits)
    assert not {c.name: c for c in missing}["routed_rows_histogram_gap"].ok  # a step without counters is not correct


# ---- the rehearsal

def test_tiny_rehearsal_runs_the_trainer_and_is_never_correct(capsys):
    line, checks = bench_run.execute(CELL, 2**31 + 5, 8.0, False, tiny=True)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    by_name = {c.name: c for c in checks}
    assert {"loss_gap_step1", "loss_gap_step3", "first_grad_norm_routed_experts_gap", "routed_rows_histogram_gap",
            "param_change_norm_worst_leaf_gap", "compilations_in_window"} <= set(by_name)
    assert by_name["loss_gap_step1"].value < 0.05 and by_name["routed_rows_histogram_gap"].value < 0.2
    assert by_name["compilations_in_window"].value == 0 and by_name["nonfinite_losses"].ok
    out = capsys.readouterr().out
    assert "model_layout" in out and "'experts_held': 4" in out and "pattern MEMEM*EME" in out
