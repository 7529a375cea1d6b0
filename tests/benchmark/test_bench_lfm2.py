"""`lfm2_moe`'s part of the benchmark: the configuration's file against itself, the catalog's
keys and the contract; the cell's files by name; parameter and operation counts against hand
sums; each new reader on a hand-built result (and finding nothing on a program without the
scopes); the fp8 control failing the cell's limits at a small size; and the driver's ``--tiny``
rehearsal end to end."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_lfm2_moe as flops
from benchmark import lfm2_trace
from benchmark import reduce_trace as rt
from benchmark import run as bench_run
from benchmark import weights_lfm2_moe as W
from benchmark.drivers import train_packed_tower as tower_driver
from benchmark.harness import RunResult
from benchmark.kernels import moe_grouped_matmul_gated, moe_grouped_matmul_swiglu, short_conv_gates_taps, splash_attention, splash_attention_visited
from benchmark.spec import ROOT, Spec
from benchmark.xplane import Event

CELL = "train-lfm2-moe-packed8k"
PROGRAM = "93"
FWD = "jit(train_step)/jvp(Lfm2MoeForCausalLM)/transformer/blocks"
BWD = "jit(train_step)/transpose(jvp(Lfm2MoeForCausalLM))/transformer/blocks/jvp(Lfm2MoeForCausalLM)/transformer/blocks/checkpoint"
# this configuration's own readers (files that no entry of BENCHMARK.json names: the pin of
# `test_bench_phases.py`, as for the two configurations before it), then the accepted phase readers printed beside them
OWN_READERS = [
    "short_conv_share.train", "moe_share.train", "attention_share.train", "expert_rows_max_over_mean.train", "mfu.lfm2_train",
    "short_conv_gates_taps_roofline", "moe_grouped_matmul_roofline.lfm2", "splash_roofline.lfm2",
]
PRINTED_ACCEPTED_READERS = [
    "blocks_fwd_ms.train", "blocks_bwd_ms.train", "head_loss_ms.train", "optimizer_ms.train", "unattributed_device_share.train",
    "host_between_steps_ms.train", "device_programs_per_step.train",  # the host loop and the device programs are every cell's
]
NEW_READERS = ["short_conv_share.train", "mfu.lfm2_train", "short_conv_gates_taps_roofline", "moe_grouped_matmul_roofline.lfm2", "splash_roofline.lfm2"]
ACCEPTED_READERS_OF_THE_CELL = {"data_wait_share.train", "hbm_peak_gib.train", "device_idle_share.train"}
PUBLISHED_LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]
# the catalog's `config` of LFM2-24B-A2B (model-configs guide, architectures.jsonl), every key
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776, "layer_types": PUBLISHED_LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config["pretrained_config"]


# ---- the configuration's file and the cell's entry

def test_the_cell_resolves_to_its_files(cell):
    assert cell.config_name == "lfm2-24b-a2b" and cell.traffic_name == "pretrain_packed_8k" and cell.chips == 1
    assert cell.traffic["driver"] == "train_packed_tower"
    assert set(cell.limits) >= {
        "loss_gap", "first_grad_norm_worst_block_leaf_gap", "first_grad_norm_routed_experts_gap",
        "first_grad_norm_wte_gap", "param_change_norm_worst_leaf_gap", "routed_rows_histogram_gap", "router_choices_moved_share",
    }
    assert set(cell.limits["reasons"]) >= set(cell.limits) - {"reasons"}  # each limit with its reason
    assert {m["name"] for m in cell.per_layer} == ACCEPTED_READERS_OF_THE_CELL
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s_per_chip", "setup_s"}
    spec = Spec.load()
    for name in OWN_READERS + PRINTED_ACCEPTED_READERS:
        assert hasattr(spec.layer_metric(name), "read")
    assert hasattr(spec.driver(cell.traffic), "run")
    weights_module, reference_module = tower_driver.modules_of(cell.config)
    assert weights_module is W and hasattr(reference_module, "train_steps")
    assert len(cell.why) <= 200 and "1/4 of a full feed" in cell.why and "5/40" in cell.why and cell.why.startswith("4 packed 8192-token rows")
    assert "lr 1e-6 stop-gap" in cell.why  # the one trainer's argument that is not the other cells' is said where the cell is read


def test_the_traffic_is_the_accepted_file_and_the_trainer_is_the_other_expert_cells(cell):
    spec = Spec.load()
    tower = spec.cell("train-nemotron-tower-packed8k")
    assert cell.traffic == tower.traffic  # one file, unchanged: the cell names it
    assert (cell.traffic["warmup_steps"], cell.traffic["check_steps"], cell.traffic["trace"]) == (6, 3, {"skip_steps": 4, "steps": 12})
    train = cell.config["train"]["training_args"]
    assert train["training_parameters"]["micro_batch_size"] == 4 and train["training_parameters"]["gradient_accumulation_steps"] == 1
    assert "4 packed rows" in cell.config["reduced"]["micro_batch_size"] and "4 packed rows of 8192" in cell.config["deployment"]
    assert train["model_args"]["reset_attention_mask"] and train["model_args"]["reset_position_ids"] and not train["model_args"]["scan_layers"]
    assert train["distributed_args"]["gradient_checkpointing_args"] == {"checkpoint_every": 1, "policy": "full"}
    tower_train = tower.config["train"]["training_args"]
    for group in ("lr_scheduler_args", "mixed_precision_args", "kernel_args", "distributed_args", "fault_tolerance_args"):
        assert train[group] == tower_train[group], group  # the tower's trainer, another model
    # the optimizer too, but for a learning rate that moves the routers little (`reduced` says why)
    ours, theirs = train["optimizer_args"]["class_args"], tower_train["optimizer_args"]["class_args"]
    assert {**ours, "lr": theirs["lr"]} == theirs and ours["lr"] == 1e-6 and "1e-6 constant" in cell.config["reduced"]["lr"]


def test_published_widths_and_the_cut(cell, cfg):
    """The catalog's keys at the top level, every one, unchanged but for the three the file lists
    as the share held and the stage's cut; ``pretrained_config`` saying the same in the program's
    names; no width among the reduced keys."""
    public = cell.config
    cut = {"num_experts": 8, "vocab_size": 8192, "num_dense_layers": 1}
    for key, value in CATALOG.items():
        assert public[key] == cut.get(key, value), key
    assert public["published"] == {"num_experts": 64, "vocab_size": 65536, "num_hidden_layers": 40, "num_dense_layers": 2}
    assert public["vocab_size"] * 8 == 65536 and public["num_experts"] * public["chips_sharing_a_layer"] == 64
    assert "8 chips share a layer" in public["deployment"] and public["not_built"] and public["chips_sharing_a_layer"] == 8
    assert set(public["assumed"]) >= {"tie_word_embeddings", "head dimension", "qk norm", "routing", "expert bias", "matrices", "z_loss_coef"}
    same = {
        "hidden_size": "n_embd", "num_attention_heads": "n_head", "num_key_value_heads": "num_key_value_heads", "intermediate_size": "n_inner",
        "conv_L_cache": "conv_L_cache", "conv_bias": "conv_bias", "num_experts_per_tok": "num_experts_per_tok",
        "moe_intermediate_size": "moe_intermediate_size", "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
        "use_expert_bias": "use_expert_bias", "norm_eps": "layer_norm_epsilon", "vocab_size": "vocab_size", "num_dense_layers": "num_dense_layers",
    }
    for theirs, ours in same.items():
        assert public[theirs] == cfg[ours], (theirs, ours)
    assert public["rope_parameters"]["rope_theta"] == cfg["rope_theta"] == 1000000 and cfg["rope_scaling"] is None
    assert cfg["layer_types"] == PUBLISHED_LAYER_TYPES[1:6] == ["conv", "full_attention", "conv", "conv", "conv"] and cfg["n_layer"] == 5
    assert cfg["num_experts"] == 64 and cfg["experts_held"] == [0, public["num_experts"]] and cfg["n_positions"] == 8192
    assert cfg["activation_function"] == "swiglu" and cfg["tie_word_embeddings"] and cfg["qk_norm"] and cfg["norm_topk_prob_epsilon"] == 1e-6
    from dolomite_engine_tpu.models import config_from_dict

    built = config_from_dict(cfg)
    assert built.held_experts() == (0, 8) and built.head_dim == 64 and built.moe_shared_expert_intermediate_size == 0
    assert built.expert_layers == 4 and built.layout_record()["chips_sharing_a_layer"] == 8 and built.layout_record()["blocks_conv"] == 4
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (entry,) = [c for c in data["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert set(entry["reduced"]) == set(public["reduced"]) and entry["source"] == public["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert entry["reduced"] == ["n_layer", "num_dense_layers", "num_experts", "vocab_size", "n_positions", "micro_batch_size", "gradient_accumulation_steps", "lr", "tensor_parallel_size"]
    import re

    width = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|expansion|experts_per_tok")
    assert not [key for key in entry["reduced"] if width.search(key)]


def test_the_cell_is_appended_after_the_accepted_ones_and_its_own_readers_wait_for_a_benchmark_pr(cell):
    """The entries this PR appends stand directly after the accepted ones, which are where they
    were and as they were (by name and order, not by position from the end: a later cell
    appends after this one)."""
    from tests.benchmark.test_bench_phases import READERS

    data = Spec.load().data
    accepted_cells = ["train-3b-packed4k", "train-8b-packed4k", "train-nemotron-tower-packed8k", "train-joyai-flash-mtp-packed8k"]
    cells = [w["name"] for w in data["workloads"]]
    assert cells[:5] == accepted_cells + [CELL]
    assert [c["name"] for c in data["configs"]][:5] == ["granite-3b-code", "granite-8b-code", "nemotron-twotower-30b-a3b", "joyai-llm-flash", "lfm2-24b-a2b"]
    names = [m["name"] for m in data["per_layer"]]
    assert names[-7:] == READERS and not set(NEW_READERS) & set(names)
    assert cell.config["layer_metrics_without_an_entry"] == OWN_READERS + PRINTED_ACCEPTED_READERS
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)
        if CELL in metric["workloads"]:
            assert metric["workloads"][:5] == accepted_cells + [CELL]  # appended, after joyai's
    (rate,) = [m for m in data["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][:5] == accepted_cells + [CELL] and rate["bound"] == 0.02
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 0 and data["run_seconds"] == 45


# ---- counts against hand sums

def test_parameter_counts_by_hand(cfg):
    counts = W.count_parameters(cfg)
    assert counts["conv_operator"] == 2048 * 6144 + 2048 * 2048 + 3 * 2048 == 16_783_360
    assert counts["attention_operator"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 == 10_485_888
    assert counts["dense_mlp"] == 3 * 2048 * 11776 == 72_351_744
    assert counts["routed_expert"] == 3 * 2048 * 1536 == 9_437_184 and counts["router"] == 2048 * 64
    assert counts["experts_layer"] == 8 * 9_437_184 + 131_072 + 64
    assert counts["layers_of_kind"] == {"conv": 4, "full_attention": 1, "dense": 1, "experts": 4}
    blocks = [16_783_360 + 72_351_744 + 4096, 10_485_888 + 75_628_608 + 4096] + [16_783_360 + 75_628_608 + 4096] * 3
    assert counts["blocks"] == blocks == [89_139_200, 86_118_592, 92_416_064, 92_416_064, 92_416_064]
    total = sum(blocks) + 8192 * 2048 + 2048
    assert counts["total"] == total == 469_285_248  # the issue's 469.3M; x 14 B = 6.57 GB of train state
    import jax

    shapes = jax.eval_shape(lambda: W.make_all(cfg, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    tiny = dict(cfg, **Spec.load().cell(CELL).config["tiny"])
    shapes = jax.eval_shape(lambda: W.make_all(tiny, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == W.count_parameters(tiny)["total"]


def test_required_operations_by_hand(cfg, cell):
    by_kind = flops.forward_flops_per_token_by_kind(cfg, attended_keys=1000.0, routed_slots_per_token=0.5)
    assert set(by_kind) == set(flops.KINDS)
    assert by_kind["conv_projections"] == 4 * 2 * (2048 * 6144 + 2048 * 2048)
    assert by_kind["conv_gates_taps"] == 4 * (2 * 3 + 2) * 2048  # counted and named; four orders under the projections
    assert by_kind["attention_projections"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert by_kind["scores_values"] == 2 * 32 * 128 * 1000.0
    assert by_kind["dense_mlp"] == 2 * 72_351_744 and by_kind["router"] == 4 * 2 * 2048 * 64
    assert by_kind["routed_experts"] == 4 * 2 * 9_437_184 * 0.5 and by_kind["head"] == 2 * 8192 * 2048
    assert flops.even_routed_slots_per_token(cfg) == 4 * 8 / 64 == 0.5
    assert flops.train_flops_per_token(cfg, 1000.0) == 3 * sum(by_kind.values())
    # the issue's arithmetic: 380 MFLOP a token forward at 1.0k keys, the conv operators 35%, the dense MLP 38%, the routed experts 10%
    total = sum(by_kind.values())
    assert 375e6 < total < 385e6
    assert 0.34 < (by_kind["conv_projections"] + by_kind["conv_gates_taps"]) / total < 0.36 and 0.37 < by_kind["dense_mlp"] / total < 0.39
    assert 0.09 < by_kind["routed_experts"] / total < 0.11 and 0.07 < (by_kind["attention_projections"] + by_kind["scores_values"]) / total < 0.09
    # the keys a token attends, over as many documents as the run's corpus has
    documents = flops.corpus_documents(cell.traffic, 45.0, 4, 8192)
    assert documents == int((6 + 45 * 6 + 2 + 2) * 4 * 8193 / (600 * np.exp(0.5)))
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192, documents)
    assert 900 < keys < 1300
    # the routed experts count by what is routed here, not by top_k
    assert flops.train_flops_per_token(cfg, keys, 4.0) > 1.25 * flops.train_flops_per_token(cfg, keys, 0.5)


def test_kernel_counts_by_hand(cfg):
    tokens = 12 * 32768.0
    assert short_conv_gates_taps.train_flops(2048, 3, 4, tokens) == 3 * 8 * 2048 * 4 * tokens
    assert short_conv_gates_taps.train_bytes(2048, 4, tokens) == tokens * 4 * 2048 * 11 * 2
    least, bound = short_conv_gates_taps.roofline_seconds(
        short_conv_gates_taps.train_flops(2048, 3, 4, tokens), short_conv_gates_taps.train_bytes(2048, 4, tokens), {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    )
    assert bound == "memory" and least == pytest.approx(tokens * 4 * 2048 * 22 / 8.19e11)
    rows = 16384.0
    assert moe_grouped_matmul_swiglu.train_flops(2048, 1536, rows) == 3 * 3 * 2 * 2048 * 1536 * rows
    bank = 8 * 3 * 2048 * 1536
    expected = rows * (2048 + 3072 + 2048) * 2 + rows * (2 * 2048 + 2 * 3072 + 2048) * 2 + 4 * (bank * 2 + bank * 2 + bank * 4)
    assert moe_grouped_matmul_swiglu.train_bytes(2048, 1536, 8, rows, layer_steps=4) == expected
    # the same counts as the accepted gated file's, at its configuration's sizes
    joyai = Spec.load().cell("train-joyai-flash-mtp-packed8k").config["pretrained_config"]
    assert moe_grouped_matmul_swiglu.train_flops(2048, 768, rows) == moe_grouped_matmul_gated.train_flops(joyai, rows)
    assert moe_grouped_matmul_swiglu.train_bytes(2048, 768, 16, rows, 5) == moe_grouped_matmul_gated.train_bytes(joyai, rows, 5)
    # splash over the visited blocks: every block of the triangle visited is more than half the square (the diagonal blocks count whole)
    n, rows_ = 8192 // 512, 4
    triangle = rows_ * n * (n + 1) // 2
    visited_all = splash_attention_visited.train_flops(1, 32, 64, 512, 512, triangle)
    assert visited_all == 2 * 7 * 64 * triangle * 512 * 512 * 32
    half_square = splash_attention.train_flops(1, 32, 64, 8192, rows_)
    assert half_square < visited_all < 1.07 * half_square
    assert splash_attention_visited.train_flops(1, 32, 64, 512, 512, 0.4 * triangle) == pytest.approx(0.4 * visited_all)
    assert splash_attention_visited.train_bytes is splash_attention.train_bytes


# ---- the readers on a hand-built result

def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def built_result(cfg, cell, named=True) -> RunResult:
    """Two traced steps of 1000 us busy each: a conv block (in-projection 100, gates and taps 40
    forward and 60 backward, out-projection 50) before the dense MLP 150; the attention block
    (projection 30, qk norm 10, the splash forward 40 and its backward 60) before experts (router
    20, dispatch 50, grouped products 120, combine 30); the loss 100, the optimizer 60, and 80 no
    scope names."""
    scope = (lambda s: "/" + s) if named else (lambda s: "")
    module = "moe" if named else "ffn"  # (the program's module is named as its scope is)
    head = "jit(train_step)/jvp(Lfm2MoeForCausalLM)/head_loss"
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        conv = f"{scope('short_conv')}/conv"
        ops += [
            op("%fusion.1", t, 100, f"{FWD}/h_0{conv}{scope('short_conv_in_proj')}/in_proj/dot_general:"),
            op("%fusion.2", t + 100, 40, f"{FWD}/h_0{conv}{scope('short_conv_gates_taps')}/mul:"),
            op("%fusion.3", t + 140, 60, f"{BWD}/h_0{conv}{scope('short_conv_gates_taps')}/mul:"),
            op("%fusion.4", t + 200, 50, f"{BWD}/h_0{conv}{scope('short_conv_out_proj')}/out_proj/dot_general:"),
            op("%fusion.5", t + 250, 150, f"{FWD}/h_0{scope('dense_mlp')}/mlp/c_fc/dot_general:"),
            op("%fusion.6", t + 400, 30, f"{FWD}/h_1{scope('attention')}/attn/c_attn/dot_general:"),
            op("%fusion.7", t + 430, 10, f"{FWD}/h_1{scope('attention')}/attn{scope('qk_norm')}/mul:"),
            op("%splash.1", t + 440, 40, f"{FWD}/h_1{scope('attention')}/attn/jit(_splash_attention)/{'splash_mha_fwd' if named else 'x'}/pallas_call:"),
            op("%splash.2", t + 480, 60, f"{BWD}/h_1{scope('attention')}/attn/jit(_splash_attention)/{'splash_mha_dkv' if named else 'x'}/pallas_call:"),
            op("%fusion.8", t + 540, 20, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_router')}/dot_general:"),
            op("%fusion.9", t + 560, 50, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_dispatch')}/gather:"),
            op("%gmm.1", t + 610, 120, f"{BWD}/h_1{scope('moe')}/{module}{scope('moe_experts')}/gmm/pallas_call:"),
            op("%fusion.10", t + 730, 30, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_combine')}/scatter-add:"),
            op("%fusion.11", t + 760, 100, f"{head}/loss_chunks/while/body/closed_call/ce_chunk/dot_general:"),
            op("%fusion.12", t + 860, 60, "jit(train_step)/optimizer/add:"),
            op("%copy.1", t + 920, 80),
        ]
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    if named:
        telemetry.append({"kind": "event", "event": "splash_block_plan", "block_q": 512, "block_kv": 512, "rows": 4, "tables": "segment_ids"})
        for step in (11, 12):
            telemetry.append({
                "kind": "event", "event": "step_counters", "step": step,
                "routed_slots": [16000, 16800, 15800, 16600], "absent_slots": [115072, 114272, 115272, 114472],
                "fullest_expert_rows": [4000, 2100, 2500, 8300], "held_expert_rows": [[2048] * 8] * 4,
                "splash_blocks_visited": 220, "splash_blocks_causal": 544,
            })
    facts = dict(
        cfg=cfg, traced_steps=2, traced_first_step=11, tokens_per_step=32768, sequence_length=8192, rows=4, chips=1,
        rate_steps=2, rate_wall_s=2.0, first_measured_step=7, last_measured_step=40,
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
    result = built_result(cfg, cell)
    peaks = context(cell).peaks
    assert read("short_conv_share.train", result, cell) == pytest.approx(100 * (100 + 40 + 60 + 50) / 1000)
    assert read("moe_share.train", result, cell) == pytest.approx(100 * (20 + 50 + 120 + 30) / 1000)
    assert read("attention_share.train", result, cell) == pytest.approx(100 * (30 + 10 + 40 + 60) / 1000)
    # ... and the table the first of them prints counts every operation once and sums to the busy time
    table = lfm2_trace.exclusive_table(result)
    per_step = {part: round(seconds / 2 * 1e6) for part, seconds in table["part_s"].items()}
    assert per_step == {"short_conv": 250, "dense_mlp": 150, "attention": 140, "moe": 220, "head_loss": 100, "optimizer": 60, "unattributed": 80}
    assert table["busy_s"] == pytest.approx(2 * 1000e-6) and sum(table["part_s"].values()) == pytest.approx(table["busy_s"])
    assert round(table["sub_s"][("short_conv", "short_conv_gates_taps")] / 2 * 1e6) == 100
    assert round(table["sub_s"][("attention", "splash_mha")] / 2 * 1e6) == 100 and round(table["sub_s"][("attention", "qk_norm")] / 2 * 1e6) == 10
    assert ("moe", "moe_shared_expert") not in table["sub_s"] and round(table["sub_s"][("moe", "moe_experts")] / 2 * 1e6) == 120
    assert "lfm2_trace:   short_conv" in capsys.readouterr().out
    tokens = 2 * 32768
    least, bound = short_conv_gates_taps.roofline_seconds(
        short_conv_gates_taps.train_flops(2048, 3, 4, tokens), short_conv_gates_taps.train_bytes(2048, 4, tokens), peaks
    )
    assert bound == "memory"
    assert read("short_conv_gates_taps_roofline", result, cell) == pytest.approx(100 * least / (2 * 100e-6))
    rows = 2 * 65200.0
    least, _ = moe_grouped_matmul_swiglu.roofline_seconds(
        moe_grouped_matmul_swiglu.train_flops(2048, 1536, rows), moe_grouped_matmul_swiglu.train_bytes(2048, 1536, 8, rows, 8), peaks
    )
    assert read("moe_grouped_matmul_roofline.lfm2", result, cell) == pytest.approx(100 * least / (2 * 120e-6))
    least, _ = splash_attention_visited.roofline_seconds(
        splash_attention_visited.train_flops(1, 32, 64, 512, 512, 2 * 220), splash_attention.train_bytes(1, 32, 8, 64, 8192, 8), peaks
    )
    assert read("splash_roofline.lfm2", result, cell) == pytest.approx(100 * least / (2 * 100e-6))
    ratios = [4000 * 8 / 16000, 2100 * 8 / 16800, 2500 * 8 / 15800, 8300 * 8 / 16600]
    assert read("expert_rows_max_over_mean.train", result, cell) == pytest.approx(sum(ratios) / 4)
    slots = 65200 / 4 / 32768
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192, flops.corpus_documents(cell.traffic, 45.0, 4, 8192))
    assert read("mfu.lfm2_train", result, cell) == pytest.approx(100 * flops.train_flops_per_token(cfg, keys, slots) * 32768 / 1.97e14)
    # the accepted phase readers the file has printed beside them
    assert read("unattributed_device_share.train", result, cell) == pytest.approx(100 * 80 / 1000)
    assert read("head_loss_ms.train", result, cell) == pytest.approx(100 / 1000)
    assert read("blocks_fwd_ms.train", result, cell) + read("blocks_bwd_ms.train", result, cell) == pytest.approx((250 + 150 + 140 + 220) / 1000)
    # ... and the two of the host loop: one program a step; between two steps, what follows one sync and precedes the next dispatch's return
    assert read("device_programs_per_step.train", result, cell) == 1.0
    assert read("host_between_steps_ms.train", result, cell) is None  # no step record yet
    split = {"loop.data_wait": 1e-3, "loop.rng": 5e-4, "train_step": 2e-3, "loop.sync": 0.6, "loop.account": 3e-4, "loop.log": 2e-4}
    result.telemetry += [{"kind": "step", "step": step, "t": {"wall": sum(split.values()), "split": split}} for step in (10, 11, 12)]
    assert read("host_between_steps_ms.train", result, cell) == pytest.approx(1e3 * (3e-4 + 2e-4 + 1e-3 + 5e-4 + 2e-3))


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_where_the_program_has_no_such_scope_or_counter(name, cfg, cell):
    """A program without these scopes and counters (the parent's), another model's cell and
    configuration: nothing is read and nothing is raised."""
    other = Spec.load().cell("train-joyai-flash-mtp-packed8k")
    result = built_result(cfg, cell, named=False)
    result.facts["cfg"] = other.config["pretrained_config"]
    assert read(name, result, other) is None
    untraced = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], facts={})
    assert read(name, untraced, cell) is None
    # ... and this configuration on a program that names no scope: still nothing, still no raise
    unnamed = built_result(cfg, cell, named=False)
    assert read(name, unnamed, cell) is None or name == "mfu.lfm2_train"  # (a utilization on the host's clock needs no scope)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_the_recorded_small_trace(name, cfg, cell):
    """``benchmark/testdata/small.xplane.pb.gz`` (PR 23: a v5e trace of a program from before any
    scope) under this cell's facts: no `jit_train_step`, no scopes, no counters — nothing to read,
    nothing raised (the utilization on the host's clock needs no trace: it reads)."""
    trace = rt.reduce_trace(os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz"))
    facts = dict(
        cfg=cfg, traced_steps=4, traced_first_step=1, tokens_per_step=32768, sequence_length=8192, rows=4, chips=1,
        rate_steps=4, rate_wall_s=4.0, first_measured_step=1, last_measured_step=4,
    )
    recorded = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], trace=trace, facts=facts)
    value = read(name, recorded, cell)
    assert value is None or (name == "mfu.lfm2_train" and 0 < value < 100)


# ---- the comparisons

OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
SMALL = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=5, n_head=4, num_key_value_heads=2, n_inner=96,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=1, conv_L_cache=3, rope_theta=1e6,
    num_experts=16, num_experts_per_tok=3, experts_held=[4, 4], moe_intermediate_size=32, routed_scaling_factor=1.0,
    eos_token_id=0, z_loss_coef=1e-4,
)


@pytest.mark.parametrize("seed", [2**31 + 1, 17])
def test_fp8_control_fails_the_cell_s_limits_and_the_reference_passes_them(cell, seed):
    from benchmark.reference import lfm2_moe as reference

    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        text = rng.integers(1, SMALL["vocab_size"], size=(2, 129)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 120, size=3)] = 0  # document boundaries
        batches.append(text)
    sound = reference.train_steps(SMALL, seed, batches, OPTIMIZER)
    control = reference.train_steps(SMALL, seed, batches, OPTIMIZER, quant="fp8")
    assert len(sound["routing"][0]["held_expert_rows"]) == 4  # four layers of experts
    rows = lambda out: [r["held_expert_rows"] for r in out["routing"]]  # noqa: E731
    checks = tower_driver.compare_with_reference(control["losses"], control["grad_norms"], control["delta_norms"], rows(control), sound, cell.limits)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
    same = tower_driver.compare_with_reference(sound["losses"], sound["grad_norms"], sound["delta_norms"], rows(sound), sound, cell.limits)
    names = {c.name for c in same}
    assert {"first_grad_norm_routed_experts_gap", "routed_rows_histogram_gap", "router_choices_moved_share"} <= names
    assert all(c.value == 0 for c in same if c.name != "router_choices_moved_share")
    # the routed group is the routers and banks of the four layers of experts; the dense MLP and both operators are block leaves
    routed = [k for k in sound["grad_norms"] if k.split(".")[-1] in tower_driver.ROUTED_LEAVES and k.startswith("layer")]
    assert sorted(routed) == sorted(f"layer{i}.{leaf}" for i in (1, 2, 3, 4) for leaf in ("gate", "c_fc", "c_proj"))
    assert {"layer0.mlp_c_fc", "layer0.mlp_c_proj", "layer0.conv_weight", "layer1.q_norm_weight", "layer1.attn_c_proj", "layer4.out_proj"} <= set(sound["grad_norms"])
    assert "lm_head" not in sound["grad_norms"]  # tied


# ---- the rehearsal

def test_tiny_rehearsal_runs_the_trainer_and_is_never_correct(capsys):
    try:
        line, checks = bench_run.execute(CELL, 2**31 + 5, 6.0, False, tiny=True)
    except RuntimeError as error:
        # a machine so loaded that one toy step (60 ms alone) outlasts the window: the driver's run of PR 33 stepped in 6-7 s
        # beside two whole-step compiles for the chip, and a window needs two steps. Once more, with room for them.
        if "the window did not close" not in str(error):
            raise
        line, checks = bench_run.execute(CELL, 2**31 + 5, 60.0, False, tiny=True)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    by_name = {c.name: c for c in checks}
    assert {"loss_gap_step1", "loss_gap_step3", "first_grad_norm_routed_experts_gap", "first_grad_norm_worst_block_leaf_gap",
            "routed_rows_histogram_gap", "param_change_norm_worst_leaf_gap", "compilations_in_window"} <= set(by_name)
    assert by_name["loss_gap_step1"].value < 0.05 and by_name["routed_rows_histogram_gap"].value < 0.2
    assert by_name["compilations_in_window"].value == 0 and by_name["nonfinite_losses"].ok
    out = capsys.readouterr().out
    assert "model_layout" in out and "'experts_held': 4" in out and "'blocks_conv': 4" in out and "'chips_sharing_a_layer': 8" in out
