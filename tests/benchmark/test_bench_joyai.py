"""`joyai_llm_flash`'s part of the benchmark: the configuration's file against itself, the
catalog's keys and the contract; the cell's files by name; parameter and operation counts
against hand sums; each new reader on a hand-built result (and finding nothing on a program
without the scopes); the second loss's comparison; the fp8 control failing the cell's limits at
a small size; and the driver's ``--tiny`` rehearsal end to end."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_joyai_flash as flops
from benchmark import joyai_trace
from benchmark import reduce_trace as rt
from benchmark import run as bench_run
from benchmark import weights_joyai_flash as W
from benchmark.drivers import train_packed_tower as tower_driver
from benchmark.harness import RunResult
from benchmark.kernels import moe_grouped_matmul_gated, splash_attention_mla
from benchmark.spec import ROOT, Spec
from benchmark.xplane import Event

CELL = "train-joyai-flash-mtp-packed8k"
PROGRAM = "91"
FWD = "jit(train_step)/jvp(JoyAIFlashForCausalLM)/transformer/blocks"
BWD = "jit(train_step)/transpose(jvp(JoyAIFlashForCausalLM))/transformer/blocks/jvp(JoyAIFlashForCausalLM)/transformer/blocks/checkpoint"
# this configuration's own readers (files that no entry of BENCHMARK.json names: the pin of
# `test_bench_phases.py`, as for the tower), then the accepted readers it has printed beside them
OWN_READERS = [
    "latent_attention_share.train", "mtp_share.train", "splash_roofline.mla", "mfu.joyai_train",
    "moe_share.train", "expert_rows_max_over_mean.train", "moe_grouped_matmul_roofline.gated",
]
PRINTED_ACCEPTED_READERS = ["blocks_fwd_ms.train", "blocks_bwd_ms.train", "head_loss_ms.train", "optimizer_ms.train", "unattributed_device_share.train"]
NEW_READERS = ["latent_attention_share.train", "mtp_share.train", "splash_roofline.mla", "mfu.joyai_train", "moe_grouped_matmul_roofline.gated"]
ACCEPTED_READERS_OF_THE_CELL = {"data_wait_share.train", "hbm_peak_gib.train", "device_idle_share.train"}
# the catalog's `config` of JoyAI-LLM-Flash (model-configs guide, architectures.jsonl), every key
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280,
}


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config["pretrained_config"]


# ---- the configuration's file and the cell's entry

def test_the_cell_resolves_to_its_files(cell):
    assert cell.config_name == "joyai-llm-flash" and cell.traffic_name == "pretrain_packed_8k_mtp" and cell.chips == 1
    assert cell.traffic["driver"] == "train_packed_mtp"
    assert set(cell.limits) >= {
        "loss_gap", "mtp_loss_gap", "first_grad_norm_worst_block_leaf_gap", "first_grad_norm_routed_experts_gap",
        "first_grad_norm_wte_gap", "param_change_norm_worst_leaf_gap", "routed_rows_histogram_gap", "router_choices_moved_share",
    }
    assert {m["name"] for m in cell.per_layer} == ACCEPTED_READERS_OF_THE_CELL
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s_per_chip", "setup_s"}
    spec = Spec.load()
    for name in OWN_READERS + PRINTED_ACCEPTED_READERS:
        assert hasattr(spec.layer_metric(name), "read")
    assert hasattr(spec.driver(cell.traffic), "run")
    weights_module, reference_module = tower_driver.modules_of(cell.config)
    assert weights_module is W and hasattr(reference_module, "train_steps")
    assert len(cell.why) <= 200 and "1/16" in cell.why and "6/41" in cell.why


def test_the_traffic_is_the_accepted_file_s_numbers_under_the_driver_that_compares_the_second_loss(cell):
    accepted = Spec.load().cell("train-nemotron-tower-packed8k").traffic
    different = {k for k in set(accepted) | set(cell.traffic) if accepted.get(k) != cell.traffic.get(k)}
    assert different == {"driver", "what"}
    assert (cell.traffic["warmup_steps"], cell.traffic["check_steps"], cell.traffic["trace"]) == (6, 3, {"skip_steps": 4, "steps": 12})
    train = cell.config["train"]["training_args"]
    assert train["training_parameters"]["micro_batch_size"] == 2 and train["training_parameters"]["gradient_accumulation_steps"] == 1
    assert train["model_args"]["reset_attention_mask"] and train["model_args"]["reset_position_ids"] and not train["model_args"]["scan_layers"]
    tower_train = Spec.load().cell("train-nemotron-tower-packed8k").config["train"]["training_args"]
    for group in ("optimizer_args", "lr_scheduler_args", "mixed_precision_args", "kernel_args", "distributed_args", "fault_tolerance_args"):
        assert train[group] == tower_train[group], group  # the tower's trainer, another model


def test_published_widths_and_the_cut(cell, cfg):
    """The catalog's keys at the top level, every one, unchanged but for the two the file lists
    as the share held; ``pretrained_config`` saying the same in the program's names."""
    public = cell.config
    cut = {"n_routed_experts": 16, "vocab_size": 16160}
    for key, value in CATALOG.items():
        assert public[key] == cut.get(key, value), key
    assert public["published"] == {"n_routed_experts": 256, "vocab_size": 129280, "num_hidden_layers": 40}
    assert public["vocab_size"] * 8 == 129280 and public["n_routed_experts"] * public["chips_sharing_a_layer"] == 256
    assert "16 chips share a layer" in public["deployment"] and public["not_built"] and public["source"] == cell.config["source"]
    assert set(public["assumed"]) >= {"mtp_loss_coef", "order inside the MTP projection", "MTP targets at document boundaries", "e_score_correction_bias", "matrices"}
    same = {
        "hidden_size": "n_embd", "num_attention_heads": "n_head", "intermediate_size": "n_inner", "q_lora_rank": "q_lora_rank",
        "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim", "rope_interleave": "rope_interleave", "rope_theta": "rope_theta", "rope_scaling": "rope_scaling",
        "first_k_dense_replace": "first_k_dense_replace", "num_experts_per_tok": "num_experts_per_tok",
        "moe_intermediate_size": "moe_intermediate_size", "n_shared_experts": "n_shared_experts",
        "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
        "num_nextn_predict_layers": "num_nextn_predict_layers", "rms_norm_eps": "layer_norm_epsilon",
        "tie_word_embeddings": "tie_word_embeddings", "vocab_size": "vocab_size", "attention_bias": "add_bias",
    }
    for theirs, ours in same.items():
        assert public[theirs] == cfg[ours], (theirs, ours)
    assert public["qk_head_dim"] == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == 192
    assert cfg["num_experts"] == 256 and cfg["experts_held"] == [0, public["n_routed_experts"]] and cfg["n_layer"] == 5
    assert cfg["activation_function"] == "swiglu" and public["hidden_act"] == "silu" and cfg["mtp_loss_coef"] == 0.3
    from dolomite_engine_tpu.models import config_from_dict

    built = config_from_dict(cfg)
    assert built.held_experts() == (0, 16) and built.head_dim == 192 and built.moe_shared_expert_intermediate_size == 768
    assert built.expert_layers == 5 and built.layout_record()["chips_sharing_a_layer"] == 16
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    entry = [c for c in data["configs"] if c["name"] == "joyai-llm-flash"][0]
    assert set(entry["reduced"]) == set(public["reduced"]) and entry["source"] == public["source"]
    assert entry["reduced"] == ["n_layer", "n_routed_experts", "vocab_size", "n_positions", "micro_batch_size", "gradient_accumulation_steps", "lr", "tensor_parallel_size"]
    assert data["configs"][-1] is entry and data["workloads"][-1]["name"] == CELL and len(data["workloads"]) == 4


def test_the_cell_joins_four_lists_and_its_own_readers_wait_for_a_benchmark_pr(cell):
    from tests.benchmark.test_bench_phases import READERS

    data = Spec.load().data
    names = [m["name"] for m in data["per_layer"]]
    assert names[-7:] == READERS and not set(NEW_READERS) & set(names)
    assert cell.config["layer_metrics_without_an_entry"] == OWN_READERS + PRINTED_ACCEPTED_READERS
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)
        assert metric["workloads"][-1] == CELL or CELL not in metric["workloads"]  # appended
    (rate,) = [m for m in data["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.02


# ---- counts against hand sums

def test_parameter_counts_by_hand(cfg):
    counts = W.count_parameters(cfg)
    assert counts["attention_matmul"] == 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048 == 26_345_472
    assert counts["dense_mlp"] == 3 * 2048 * 7168 == 44_040_192
    assert counts["routed_expert"] == 3 * 2048 * 768 == 4_718_592 == counts["shared_expert"]
    assert counts["router"] == 2048 * 256 and counts["mtp_projection"] == 4096 * 2048
    assert counts["layers_of_kind"] == {"D": 1, "E": 4, "P": 1}
    norms = 2 * 2048 + 1536 + 512
    dense_block = 26_345_472 + norms + 44_040_192
    expert_block = 26_345_472 + norms + 2048 * 256 + 256 + 4_718_592 + 16 * 4_718_592
    mtp_module = expert_block + 4096 * 2048 + 3 * 2048
    assert (counts["dense_block"], counts["expert_block"], counts["mtp_module"]) == (dense_block, expert_block, mtp_module)
    total = dense_block + 4 * expert_block + mtp_module + 2 * 16160 * 2048 + 2048
    assert counts["total"] == total == 680_441_088  # the issue's 680.4M
    import jax

    shapes = jax.eval_shape(lambda: W.make_all(cfg, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    tiny = dict(cfg, **Spec.load().cell(CELL).config["tiny"])
    shapes = jax.eval_shape(lambda: W.make_all(tiny, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == W.count_parameters(tiny)["total"]


def test_required_operations_by_hand(cfg, cell):
    by_kind = flops.forward_flops_per_token_by_kind(cfg, attended_keys=1300.0, routed_slots_per_token=0.5)
    assert set(by_kind) == set(flops.KINDS)
    assert by_kind["mla_projections"] == 6 * 2 * 26_345_472
    assert by_kind["scores_values"] == 6 * 2 * 32 * (192 + 128) * 1300.0
    assert by_kind["dense_mlp"] == 2 * 44_040_192 and by_kind["router"] == 5 * 2 * 2048 * 256
    assert by_kind["shared_expert"] == 5 * 2 * 4_718_592 and by_kind["routed_experts"] == 5 * 2 * 4_718_592 * 0.5
    assert by_kind["mtp_projection"] == 2 * 4096 * 2048 and by_kind["head"] == 2 * 2 * 16160 * 2048
    assert flops.even_routed_slots_per_token(cfg) == 8 * 16 / 256 == 0.5
    assert flops.train_flops_per_token(cfg, 1300.0) == 3 * sum(by_kind.values())
    # the issue's arithmetic: an expert block ~95M, the dense block ~168M, six blocks ~660M of which latent attention ~73%
    attention = (by_kind["mla_projections"] + by_kind["scores_values"]) / 6
    expert_block = attention + (by_kind["router"] + by_kind["shared_expert"] + by_kind["routed_experts"]) / 5
    assert 94e6 < expert_block < 96e6 and 0.82 < attention / expert_block < 0.86
    assert 166e6 < attention + by_kind["dense_mlp"] < 170e6
    blocks = sum(v for k, v in by_kind.items() if k != "head")
    assert 650e6 < blocks < 670e6 and 0.70 < 6 * attention / blocks < 0.75
    # the keys a token attends, from the traffic's law: documents of median 600 (mean ~990) packed in rows of 8192
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192)
    assert 1000 < keys < 1400 and keys == flops.mean_attended_keys(cell.traffic["document_tokens"], 8192)
    assert flops.mean_attended_keys({"distribution": "lognormal", "median": 100, "sigma": 1e-9, "min": 1, "max": 1000}, 101 * 8) == pytest.approx(51.0)
    # the routed experts count by what is routed here, not by top_k
    assert flops.train_flops_per_token(cfg, keys, 8.0) > 1.3 * flops.train_flops_per_token(cfg, keys, 0.5)


def test_kernel_counts_by_hand(cfg):
    shape = (6, 32, 192, 128, 8192, 2)
    pairs = 2 * 6 * 32 * 8192 * 8193 / 2
    by_launch = splash_attention_mla.flops_by_launch(*shape)
    assert by_launch == {"forward": 2 * 320 * pairs, "dkv": 2 * 640 * pairs, "dq": 2 * 512 * pairs}
    required = splash_attention_mla.train_flops(*shape)
    assert required == 2 * (320 + 832) * pairs and required < sum(by_launch.values())  # S and dP once, not twice
    wide, narrow = 2 * 6 * 32 * 192 * 8192 * 2, 2 * 6 * 32 * 128 * 8192 * 2
    assert splash_attention_mla.train_bytes(*shape) == 6 * wide + 6 * narrow
    assert splash_attention_mla.bytes_by_launch(*shape) == {"forward": 2 * wide + 2 * narrow, "dkv": 3 * wide + 3 * narrow, "dq": 3 * wide + 2 * narrow}
    # equal widths give the accepted kernel file's counts
    from benchmark.kernels import splash_attention

    assert splash_attention_mla.train_flops(2, 8, 64, 64, 1024, 3) == pytest.approx(splash_attention.train_flops(2, 8, 64, 1024, 3))
    assert splash_attention_mla.train_bytes(2, 8, 64, 64, 1024, 3) == splash_attention.train_bytes(2, 8, 8, 64, 1024, 3)
    rows = 8192.0
    assert moe_grouped_matmul_gated.train_flops(cfg, rows) == 3 * 3 * 2 * 2048 * 768 * rows
    bank = 16 * 3 * 2048 * 768
    expected = rows * (2048 + 1536 + 2048) * 2 + rows * (2 * 2048 + 2 * 1536 + 2048) * 2 + 5 * (bank * 2 + bank * 2 + bank * 4)
    assert moe_grouped_matmul_gated.train_bytes(cfg, rows, layer_steps=5) == expected


# ---- the readers on a hand-built result

def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def built_result(cfg, cell, named=True) -> RunResult:
    """Two traced steps of 1000 us busy each: a block's latent attention (q projections 60, the
    splash forward 90 and its backward 150, the out-projection's backward 50), the dense MLP 100,
    the experts (router 20, grouped products 60, shared expert 70), the MTP module (its
    projection 30, its block's splash 40 and experts' products 30, its loss pass 80), the main
    loss pass 120, the optimizer 60, and 40 no scope names."""
    scope = (lambda s: "/" + s) if named else (lambda s: "")
    module, mtp_module = ("moe", "mtp") if named else ("ffn", "second")  # (the program's modules are named as their scopes are)
    mtp = f"{FWD}{scope('mtp')}/{mtp_module}"
    head = "jit(train_step)/jvp(JoyAIFlashForCausalLM)/head_loss"
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        ops += [
            op("%fusion.1", t, 60, f"{FWD}/h_0{scope('latent_attention')}/attn{scope('mla_q_up')}/q_b_proj/dot_general:"),
            op("%splash.1", t + 60, 90, f"{FWD}/h_0{scope('latent_attention')}/attn/vmap(jit(_splash_attention))/{'splash_mha_fwd' if named else 'x'}/pallas_call:"),
            op("%splash.2", t + 150, 150, f"{BWD}/h_0{scope('latent_attention')}/attn/vmap(jit(_splash_attention))/{'splash_mha_dkv' if named else 'x'}/pallas_call:"),
            op("%fusion.2", t + 300, 50, f"{BWD}/h_0{scope('latent_attention')}/attn{scope('mla_out_proj')}/o_proj/dot_general:"),
            op("%fusion.3", t + 350, 100, f"{FWD}/h_0{scope('dense_mlp')}/mlp/c_fc/dot_general:"),
            op("%fusion.4", t + 450, 20, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_router')}/dot_general:"),
            op("%gmm.1", t + 470, 60, f"{FWD}/h_1{scope('moe')}/{module}{scope('moe_experts')}/gmm/pallas_call:"),
            op("%fusion.5", t + 530, 70, f"{BWD}/h_1{scope('moe')}/{module}{scope('moe_shared_expert')}/shared_c_fc/dot_general:"),
            op("%fusion.6", t + 600, 30, f"{mtp}{scope('mtp_combine')}/eh_proj/dot_general:"),
            op("%splash.3", t + 630, 40, f"{mtp}/block{scope('latent_attention')}/attn/vmap(jit(_splash_attention))/{'splash_mha_fwd' if named else 'x'}/pallas_call:"),
            op("%gmm.2", t + 670, 30, f"{mtp}/block{scope('moe')}/{module}{scope('moe_experts')}/gmm/pallas_call:"),
            op("%fusion.7", t + 700, 80, f"{head}{scope('mtp')}{scope('mtp_head_loss')}/loss_chunks/while/body/closed_call/ce_chunk/dot_general:"),
            op("%fusion.8", t + 780, 120, f"{head}/loss_chunks/while/body/closed_call/ce_chunk/dot_general:"),
            op("%fusion.9", t + 900, 60, "jit(train_step)/optimizer/add:"),
            op("%copy.1", t + 960, 40),
        ]
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    if named:
        for step in (11, 12):
            telemetry.append({
                "kind": "event", "event": "step_counters", "step": step,
                "routed_slots": [8000, 8400, 7900, 8300, 8360], "absent_slots": [123072, 122672, 123172, 122772, 122712],
                "fullest_expert_rows": [1000, 2100, 700, 830, 1045], "held_expert_rows": [[512] * 16] * 5,
                "main_loss": 9.1, "mtp_loss": 9.4, "mtp_targets": 16000,
            })
    facts = dict(
        cfg=cfg, traced_steps=2, traced_first_step=11, tokens_per_step=16384, sequence_length=8192, rows=2, chips=1,
        rate_steps=2, rate_wall_s=1.0, first_measured_step=7, last_measured_step=40,
    )
    return RunResult(attempted=2, failed=0, end_to_end={}, checks=[], trace=trace, telemetry=telemetry, facts=facts)


def context(cell):
    class Context:
        peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}

    Context.cell = cell
    return Context


def read(name, result, cell):
    return Spec.load().layer_metric(name).read(result, context(cell))


def test_new_readers_on_a_built_result(cfg, cell, capsys):
    result = built_result(cfg, cell)
    peaks = context(cell).peaks
    # overlapping shares: the MTP module's block counts under its own scope AND under its layers'
    assert read("latent_attention_share.train", result, cell) == pytest.approx(100 * (60 + 90 + 150 + 50 + 40) / 1000)
    assert read("moe_share.train", result, cell) == pytest.approx(100 * (20 + 60 + 70 + 30) / 1000)
    assert read("mtp_share.train", result, cell) == pytest.approx(100 * (30 + 40 + 30 + 80) / 1000)
    # ... and the table it prints counts every operation once
    table = joyai_trace.exclusive_table(result)
    per_step = {part: round(seconds / 2 * 1e6) for part, seconds in table["part_s"].items()}
    assert per_step == {
        "latent_attention": 350, "dense_mlp": 100, "moe": 150, "mtp": 100, "mtp_head_loss": 80, "head_loss": 120,
        "optimizer": 60, "unattributed": 40,
    }
    assert table["busy_s"] == pytest.approx(2 * 1000e-6) and sum(table["part_s"].values()) == pytest.approx(table["busy_s"])
    assert round(table["sub_s"][("latent_attention", "splash_mha")] / 2 * 1e6) == 240
    assert round(table["sub_s"][("mtp", "splash_mha")] / 2 * 1e6) == 40 and round(table["sub_s"][("mtp", "mtp_combine")] / 2 * 1e6) == 30
    assert "joyai_trace:   latent_attention" in capsys.readouterr().out
    least, _ = splash_attention_mla.roofline_seconds(
        splash_attention_mla.train_flops(6, 32, 192, 128, 8192, 4), splash_attention_mla.train_bytes(6, 32, 192, 128, 8192, 4), peaks
    )
    assert read("splash_roofline.mla", result, cell) == pytest.approx(100 * least / (2 * 280e-6))
    rows = 2 * 40960.0
    least, _ = moe_grouped_matmul_gated.roofline_seconds(
        moe_grouped_matmul_gated.train_flops(cfg, rows), moe_grouped_matmul_gated.train_bytes(cfg, rows, 10), peaks
    )
    assert read("moe_grouped_matmul_roofline.gated", result, cell) == pytest.approx(100 * least / (2 * 90e-6))
    ratios = [1000 * 16 / 8000, 2100 * 16 / 8400, 700 * 16 / 7900, 830 * 16 / 8300, 1045 * 16 / 8360]
    assert read("expert_rows_max_over_mean.train", result, cell) == pytest.approx(sum(ratios) / 5)
    slots = 40960 / 5 / 16384
    keys = flops.mean_attended_keys(cell.traffic["document_tokens"], 8192)
    assert read("mfu.joyai_train", result, cell) == pytest.approx(100 * flops.train_flops_per_token(cfg, keys, slots) * 2 * 16384 / 1.97e14)
    # the accepted phase readers the file has printed beside them see the module with the blocks and the head
    assert read("unattributed_device_share.train", result, cell) == pytest.approx(100 * 40 / 1000)
    assert read("head_loss_ms.train", result, cell) == pytest.approx((80 + 120) / 1000)
    assert read("blocks_fwd_ms.train", result, cell) + read("blocks_bwd_ms.train", result, cell) == pytest.approx((350 + 100 + 150 + 100) / 1000)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_where_the_program_has_no_such_scope_or_counter(name, cfg, cell):
    """A program without these scopes and counters, another model's configuration in the facts:
    nothing is read and nothing is raised."""
    result = built_result(cfg, cell, named=False)
    result.facts["cfg"] = {"n_embd": 64, "n_layer": 2}
    assert read(name, result, cell) is None
    untraced = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], facts={})
    assert read(name, untraced, cell) is None


# ---- the comparisons

OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
SMALL = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=3, n_head=4, n_inner=96, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=32e6, first_k_dense_replace=1,
    num_experts=16, num_experts_per_tok=3, experts_held=[4, 4], moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=2.5, num_nextn_predict_layers=1, mtp_loss_coef=0.3, eos_token_id=0, z_loss_coef=1e-4,
)


def test_second_loss_checks_beside_their_limit():
    from benchmark.drivers import train_packed_mtp as driver

    checks = driver.second_loss_checks([9.40, 9.31, None], [9.41, 9.20, 9.0], 0.05)
    assert [c.name for c in checks] == ["mtp_loss_gap_step1", "mtp_loss_gap_step2", "mtp_loss_gap_step3"]
    assert [c.ok for c in checks] == [True, False, False] and checks[0].value == pytest.approx(0.01)
    assert not driver.second_loss_checks([], [9.0], 0.05)[0].ok  # a step that returned no second loss is not correct


@pytest.mark.parametrize("seed", [2**31 + 1, 17])
def test_fp8_control_fails_the_cell_s_limits_and_the_reference_passes_them(cell, seed):
    from benchmark.drivers import train_packed_mtp as driver
    from benchmark.reference import joyai_flash as reference

    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        text = rng.integers(1, SMALL["vocab_size"], size=(2, 129)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 120, size=3)] = 0  # document boundaries
        batches.append(text)
    sound = reference.train_steps(SMALL, seed, batches, OPTIMIZER)
    control = reference.train_steps(SMALL, seed, batches, OPTIMIZER, quant="fp8")
    np.testing.assert_allclose(sound["losses"], [a + 0.3 * b for a, b in zip(sound["main_losses"], sound["mtp_losses"])], rtol=1e-6)
    assert len(sound["routing"][0]["held_expert_rows"]) == 3  # two layers of experts and the MTP module's
    rows = lambda out: [r["held_expert_rows"] for r in out["routing"]]  # noqa: E731
    checks = tower_driver.compare_with_reference(
        control["losses"], control["grad_norms"], control["delta_norms"], rows(control), sound, cell.limits
    ) + driver.second_loss_checks(control["mtp_losses"], sound["mtp_losses"], cell.limits["mtp_loss_gap"])
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
    same = tower_driver.compare_with_reference(sound["losses"], sound["grad_norms"], sound["delta_norms"], rows(sound), sound, cell.limits)
    names = {c.name for c in same}
    assert {"first_grad_norm_routed_experts_gap", "routed_rows_histogram_gap", "router_choices_moved_share"} <= names
    assert all(c.value == 0 for c in same if c.name != "router_choices_moved_share")
    # the routed group is the routers and banks of every layer of experts, the MTP module's too; the dense MLP is a block leaf
    routed = [k for k in sound["grad_norms"] if k.split(".")[-1] in tower_driver.ROUTED_LEAVES and k.startswith("layer")]
    assert sorted(routed) == sorted(f"layer{i}.{leaf}" for i in (1, 2, 3) for leaf in ("gate", "c_fc", "c_proj"))
    assert {"layer0.mlp_c_fc", "layer0.mlp_c_proj", "layer3.mtp_eh_proj", "layer2.o_proj"} <= set(sound["grad_norms"])


# ---- the rehearsal

def test_tiny_rehearsal_runs_the_trainer_and_is_never_correct(capsys):
    line, checks = bench_run.execute(CELL, 2**31 + 5, 6.0, False, tiny=True)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    by_name = {c.name: c for c in checks}
    assert {"loss_gap_step1", "loss_gap_step3", "mtp_loss_gap_step1", "mtp_loss_gap_step3", "first_grad_norm_routed_experts_gap",
            "routed_rows_histogram_gap", "param_change_norm_worst_leaf_gap", "compilations_in_window"} <= set(by_name)
    assert by_name["loss_gap_step1"].value < 0.05 and by_name["mtp_loss_gap_step1"].value < 0.05
    assert by_name["routed_rows_histogram_gap"].value < 0.2
    assert by_name["compilations_in_window"].value == 0 and by_name["nonfinite_losses"].ok
    out = capsys.readouterr().out
    assert "model_layout" in out and "'experts_held': 4" in out and "'blocks_mtp': 1" in out
