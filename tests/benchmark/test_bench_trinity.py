"""`afmoe`'s part of the benchmark (Trinity-Mini): the configuration's file against itself, the
catalog's keys and the contract; the cell's files by name; parameter and operation counts against
hand sums (the keys a window layer attends, the block pairs its tables visit); each new reader on a
hand-built result (and finding nothing on a program without the scopes, on another cell, on an
untraced run and on the recorded small trace); the fp8 control and the two named faults failing
the cell's limits at a small size; and the driver's ``--tiny`` rehearsal end to end."""

import json
import os

import numpy as np
import pytest

from benchmark import afmoe_trace
from benchmark import flops_afmoe as flops
from benchmark import reduce_trace as rt
from benchmark import run as bench_run
from benchmark import weights_afmoe as W
from benchmark.drivers import train_packed_tower as tower_driver
from benchmark.harness import RunResult
from benchmark.kernels import moe_grouped_matmul_swiglu, splash_attention, splash_attention_visited
from benchmark.spec import ROOT, Spec
from benchmark.xplane import Event

CELL = "train-trinity-mini-swa-packed16k"
PROGRAM = "93"
FWD = "jit(train_step)/jvp(AfmoeForCausalLM)/transformer/blocks"
BWD = "jit(train_step)/transpose(jvp(AfmoeForCausalLM))/transformer/blocks/jvp(AfmoeForCausalLM)/transformer/blocks/checkpoint"
# this configuration's own readers (files that no entry of BENCHMARK.json names: the pin of
# `test_bench_phases.py`, as for the four configurations before it), then the accepted phase readers printed beside them
OWN_READERS = [
    "window_attention_share.train", "full_attention_share.train", "attention_gate_share.train", "moe_share.train",
    "expert_rows_max_over_mean.train", "mfu.afmoe_train", "splash_roofline.afmoe", "moe_grouped_matmul_roofline.afmoe",
]
PRINTED_ACCEPTED_READERS = [
    "blocks_fwd_ms.train", "blocks_bwd_ms.train", "head_loss_ms.train", "optimizer_ms.train", "unattributed_device_share.train",
    "host_between_steps_ms.train", "device_programs_per_step.train",  # the host loop and the device programs are every cell's
]
NEW_READERS = [
    "window_attention_share.train", "full_attention_share.train", "attention_gate_share.train", "mfu.afmoe_train",
    "splash_roofline.afmoe", "moe_grouped_matmul_roofline.afmoe",
]
ACCEPTED_READERS_OF_THE_CELL = {"data_wait_share.train", "hbm_peak_gib.train", "device_idle_share.train"}
PUBLISHED_LAYER_TYPES = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 8
# the catalog's `config` of Trinity-Mini (model-configs guide, architectures.jsonl), every key
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PUBLISHED_LAYER_TYPES, "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
ACCEPTED_CELLS = [
    "train-3b-packed4k", "train-8b-packed4k", "train-nemotron-tower-packed8k", "train-joyai-flash-mtp-packed8k",
    "train-lfm2-moe-packed8k", "train-ouro-loop4-packed8k",
]


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config["pretrained_config"]


# ---- the configuration's file and the cell's entry

def test_the_cell_resolves_to_its_files(cell):
    assert cell.config_name == "trinity-mini" and cell.traffic_name == "pretrain_packed_16k_longdoc" and cell.chips == 1
    assert cell.traffic["driver"] == "train_packed_tower"
    assert set(cell.limits) >= {
        "loss_gap", "first_grad_norm_worst_block_leaf_gap", "first_grad_norm_routed_experts_gap",
        "first_grad_norm_wte_gap", "param_change_norm_worst_leaf_gap", "routed_rows_histogram_gap", "router_choices_moved_share",
    }
    assert set(cell.limits["reasons"]) >= (set(cell.limits) - {"reasons"}) | {"readings", "faults"}  # each limit with its reason
    assert "window layer run as a full layer" in cell.limits["reasons"]["faults"] and "full layer that rotates" in cell.limits["reasons"]["faults"]
    assert {m["name"] for m in cell.per_layer} == ACCEPTED_READERS_OF_THE_CELL
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s_per_chip", "setup_s"}
    spec = Spec.load()
    for name in OWN_READERS + PRINTED_ACCEPTED_READERS:
        assert hasattr(spec.layer_metric(name), "read")
    assert hasattr(spec.driver(cell.traffic), "run")
    weights_module, reference_module = tower_driver.modules_of(cell.config)
    assert weights_module is W and hasattr(reference_module, "train_steps")
    assert len(cell.why) <= 200 and "1/16 of a full feed" in cell.why and "5/32" in cell.why and cell.why.startswith("2 rows of 16384 tokens")
    assert "lr 1e-7" in cell.why and "biases balanced" in cell.why  # the one trainer's argument that is not the other cells' is said where the cell is read


def test_the_traffic_is_a_file_of_parameters_for_the_accepted_driver_and_the_trainer_is_the_other_expert_cells(cell):
    spec = Spec.load()
    tower = spec.cell("train-nemotron-tower-packed8k")
    # a new file of parameters for the generator that is there: the long-document law, everything else the 8k file's
    assert {k: v for k, v in cell.traffic.items() if k not in ("document_tokens", "what")} == {k: v for k, v in tower.traffic.items() if k not in ("document_tokens", "what")}
    assert cell.traffic["document_tokens"] == {"distribution": "lognormal", "median": 4096, "sigma": 1.0, "min": 64, "max": 16384}
    assert (cell.traffic["warmup_steps"], cell.traffic["check_steps"], cell.traffic["trace"]) == (6, 3, {"skip_steps": 4, "steps": 12})
    train = cell.config["train"]["training_args"]
    assert train["training_parameters"]["micro_batch_size"] == 2 and train["training_parameters"]["gradient_accumulation_steps"] == 1
    assert train["training_parameters"]["prefetch_depth"] == 2
    assert "2 packed rows" in cell.config["reduced"]["micro_batch_size"] and "2 packed rows of 16384" in cell.config["deployment"]
    assert train["model_args"]["reset_attention_mask"] and train["model_args"]["reset_position_ids"] and not train["model_args"]["scan_layers"]
    assert train["distributed_args"]["gradient_checkpointing_args"] == {"checkpoint_every": 1, "policy": "full"}
    tower_train = tower.config["train"]["training_args"]
    for group in ("lr_scheduler_args", "mixed_precision_args", "kernel_args", "distributed_args", "fault_tolerance_args"):
        assert train[group] == tower_train[group], group  # the tower's trainer, another model
    # the optimizer too, but for a learning rate that moves the routers little (`reduced` says why, with the reading at 3e-5)
    ours, theirs = train["optimizer_args"]["class_args"], tower_train["optimizer_args"]["class_args"]
    assert {**ours, "lr": theirs["lr"]} == theirs and ours["lr"] == 1e-7
    assert "1e-7 constant" in cell.config["reduced"]["lr"] and "5.5%" in cell.config["reduced"]["lr"] and "3.3%" in cell.config["reduced"]["lr"]
    assert "balanced_biases" in cell.config["assumed"]["expert bias"]


def test_published_widths_and_the_cut(cell, cfg):
    """The catalog's keys at the top level, every one, unchanged but for the three the file lists
    as the share held and the stage's cut; ``pretrained_config`` saying the same in the program's
    names; no width among the reduced keys."""
    public = cell.config
    cut = {"num_experts": 8, "vocab_size": 25024, "num_dense_layers": 1}
    for key, value in CATALOG.items():
        assert public[key] == cut.get(key, value), key
    assert public["published"] == {"num_experts": 128, "vocab_size": 200192, "num_hidden_layers": 32, "num_dense_layers": 2}
    assert public["vocab_size"] * 8 == 200192 and public["num_experts"] * public["chips_sharing_a_layer"] == 128
    assert "16 chips share a layer" in public["deployment"] and public["not_built"] and public["chips_sharing_a_layer"] == 16
    assert "9.88 GB" in public["reduced"]["num_experts"]  # why 16 chips share a layer and not 8
    assert set(public["assumed"]) >= {"norms", "qk norm", "window", "attention gate", "mup_enabled", "routing", "expert bias", "matrices", "z_loss_coef"}
    for word in ("per-layer page budgets", "paged decode", "ring / ulysses", "tp, ep > 1 and scan_layers", "hf_interop", "update rule"):
        assert word in public["not_built"], word
    same = {
        "hidden_size": "n_embd", "num_attention_heads": "n_head", "num_key_value_heads": "num_key_value_heads", "head_dim": "attention_head_dim",
        "intermediate_size": "n_inner", "sliding_window": "sliding_window", "num_experts_per_tok": "num_experts_per_tok",
        "moe_intermediate_size": "moe_intermediate_size", "num_shared_experts": "num_shared_experts", "route_scale": "route_scale",
        "route_norm": "route_norm", "score_func": "score_func", "mup_enabled": "mup_enabled", "rms_norm_eps": "layer_norm_epsilon",
        "rope_theta": "rope_theta", "rope_scaling": "rope_scaling", "tie_word_embeddings": "tie_word_embeddings",
        "vocab_size": "vocab_size", "num_dense_layers": "num_dense_layers",
    }
    for theirs, ours in same.items():
        assert public[theirs] == cfg[ours], (theirs, ours)
    assert (cfg["n_embd"], cfg["n_head"], cfg["num_key_value_heads"], cfg["attention_head_dim"], cfg["n_inner"]) == (2048, 32, 4, 128, 6144)
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], cfg["route_scale"], cfg["sliding_window"], cfg["rope_theta"], cfg["layer_norm_epsilon"]) == (1024, 8, 2.826, 2048, 10000, 1e-5)
    assert cfg["layer_types"] == PUBLISHED_LAYER_TYPES[1:6] == ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"]
    assert cfg["n_layer"] == 5 and public["num_hidden_layers"] == 32  # (`reduced` lists n_layer: a key with `hidden` in it is a width's)
    assert cfg["num_experts"] == 128 and cfg["experts_held"] == [0, public["num_experts"]] and cfg["n_positions"] == 16384
    assert public["max_position_embeddings"] == 131072
    assert cfg["activation_function"] == "swiglu" and cfg["qk_norm"] and cfg["attention_output_gate"] and cfg["route_norm_epsilon"] == 1e-20
    from dolomite_engine_tpu.models import config_from_dict

    built = config_from_dict(cfg)
    assert built.held_experts() == (0, 8) and built.head_dim == 128 and built.moe_shared_expert_intermediate_size == 1024
    assert built.m_emb == pytest.approx(2048**0.5) and built.norm_topk_prob_epsilon == 1e-20 and built.routed_scaling_factor == 2.826
    assert [built.layer_window(i) for i in range(5)] == [2048, 2048, None, 2048, 2048]
    assert built.expert_layers == 4 and built.layout_record()["chips_sharing_a_layer"] == 16 and built.layout_record()["blocks_window"] == 4
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (entry,) = [c for c in data["configs"] if c["name"] == "trinity-mini"]
    assert set(entry["reduced"]) == set(public["reduced"]) and entry["source"] == public["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert entry["reduced"] == ["n_layer", "num_dense_layers", "num_experts", "vocab_size", "n_positions", "micro_batch_size", "gradient_accumulation_steps", "lr", "tensor_parallel_size"]
    import re

    width = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|expansion|experts_per_tok")
    assert not [key for key in entry["reduced"] if width.search(key)]


def test_the_cell_is_appended_after_the_accepted_ones_and_its_own_readers_wait_for_a_benchmark_pr(cell):
    """The entries this PR appends stand directly after the accepted ones, which are where they
    were and as they were (by name and order, counted from the front: a later cell appends after
    this one). The cell stands in four `workloads` lists."""
    from tests.benchmark.test_bench_phases import READERS

    data = Spec.load().data
    cells = [w["name"] for w in data["workloads"]]
    assert cells[:7] == ACCEPTED_CELLS + [CELL]
    assert [c["name"] for c in data["configs"]][:7] == [
        "granite-3b-code", "granite-8b-code", "nemotron-twotower-30b-a3b", "joyai-llm-flash", "lfm2-24b-a2b", "ouro-2.6b", "trinity-mini",
    ]
    names = [m["name"] for m in data["per_layer"]]
    assert names[-7:] == READERS and not set(NEW_READERS) & set(names)
    assert cell.config["layer_metrics_without_an_entry"] == OWN_READERS + PRINTED_ACCEPTED_READERS
    lists = 0
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)
        if CELL in metric["workloads"]:
            assert metric["workloads"][:7] == ACCEPTED_CELLS + [CELL]  # appended, after ouro's
            lists += 1
    (rate,) = [m for m in data["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][:7] == ACCEPTED_CELLS + [CELL] and rate["bound"] == 0.02
    assert lists + 1 == 4
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 0 and data["run_seconds"] == 45


# ---- counts against hand sums

def test_parameter_counts_by_hand(cfg):
    counts = W.count_parameters(cfg)
    assert counts["qkv"] == 2048 * (4096 + 512 + 512) == 10_485_760 and counts["gate"] == counts["out"] == 2048 * 4096 == 8_388_608
    assert counts["attention"] == 10_485_760 + 2 * 8_388_608 + 2 * 128 == 27_263_232
    assert counts["dense_mlp"] == 3 * 2048 * 6144 == 37_748_736
    assert counts["routed_expert"] == counts["shared_expert"] == 3 * 2048 * 1024 == 6_291_456 and counts["router"] == 2048 * 128
    assert counts["experts_layer"] == 262_144 + 128 + 6_291_456 + 8 * 6_291_456
    assert counts["layers_of_kind"] == {"sliding_attention": 4, "full_attention": 1, "dense": 1, "experts": 4}
    blocks = [27_263_232 + 37_748_736 + 8192] + [27_263_232 + 56_885_376 + 8192] * 4
    assert counts["blocks"] == blocks == [65_020_160, 84_156_800, 84_156_800, 84_156_800, 84_156_800]
    total = sum(blocks) + 2 * 25_024 * 2048 + 2048
    assert counts["total"] == total == 504_147_712  # the issue's 504.1M; x 14 B = 7.06 GB of train state
    import jax

    shapes = jax.eval_shape(lambda: W.make_drawn(cfg, 1))  # make_all's leaves before it balances the biases (which runs the model: minutes here at these widths)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    tiny = dict(cfg, **Spec.load().cell(CELL).config["tiny"])
    shapes = jax.eval_shape(lambda: W.make_all(tiny, 1))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == W.count_parameters(tiny)["total"]
    # sixteen held would be the alternative the file argues against: 8 chips sharing a layer
    assert W.count_parameters(dict(cfg, experts_held=[0, 16]))["total"] == 705_474_304


def test_required_operations_by_hand(cfg, cell):
    by_kind = flops.forward_flops_per_token_by_kind(cfg, full_keys=3600.0, window_keys=1600.0, routed_slots_per_token=0.5)
    assert set(by_kind) == set(flops.KINDS)
    assert by_kind["attention_projections"] == 5 * 2 * (10_485_760 + 2 * 8_388_608)
    assert by_kind["scores_values_window"] == 4 * 2 * 32 * 256 * 1600.0 and by_kind["scores_values_full"] == 2 * 32 * 256 * 3600.0
    assert by_kind["dense_mlp"] == 2 * 37_748_736 and by_kind["router"] == 4 * 2 * 2048 * 128
    assert by_kind["shared_expert"] == 4 * 2 * 6_291_456 and by_kind["routed_experts"] == 4 * 2 * 6_291_456 * 0.5 and by_kind["head"] == 2 * 25_024 * 2048
    assert flops.even_routed_slots_per_token(cfg) == 8 * 8 / 128 == 0.5
    assert flops.train_flops_per_token(cfg, 3600.0, 1600.0) == 3 * sum(by_kind.values())
    # the issue's arithmetic: 2.1 GFLOP a token required; of the blocks' 590M forward attention is 74%, its core 28%, the experts 13%
    total = sum(by_kind.values())
    assert 2.0e9 < 3 * total < 2.15e9
    blocks = total - by_kind["head"]
    core = by_kind["scores_values_window"] + by_kind["scores_values_full"]
    assert 580e6 < blocks < 600e6 and 0.72 < (by_kind["attention_projections"] + core) / blocks < 0.76 and 0.26 < core / blocks < 0.30
    assert 0.12 < (by_kind["router"] + by_kind["shared_expert"] + by_kind["routed_experts"]) / blocks < 0.14
    # if all five layers were full, the cores alone would be 295M: the window is what saves it
    assert 5 * 2 * 32 * 256 * 3600.0 == pytest.approx(295e6, rel=0.01)
    # the keys a token attends, over as many documents as the run's corpus has: the issue's 3,614 against 1,602
    documents = flops.corpus_documents(cell.traffic, 45.0, 2, 16384)
    assert documents == int((6 + 45 * 6 + 2 + 2) * 2 * 16385 / (4096 * np.exp(0.5)))
    full_keys, window_keys = flops.attended_keys(cell.traffic["document_tokens"], 16384, documents, 2048)
    assert 3500 < full_keys < 3700 and 1550 < window_keys < 1650
    # at the 8k cells' law a window would do next to nothing: the issue's 1,000 against 819
    short = Spec.load().cell("train-lfm2-moe-packed8k").traffic["document_tokens"]
    full_8k, window_8k = flops.attended_keys(short, 8192, 20000, 2048)
    assert 900 < full_8k < 1300 and window_8k > 0.75 * full_8k
    # a window as long as the row is the full layer; a window of one key is the token itself
    assert flops.attended_keys(cell.traffic["document_tokens"], 16384, 200, 16385)[1] == flops.attended_keys(cell.traffic["document_tokens"], 16384, 200, 16385)[0]
    assert flops.attended_keys(cell.traffic["document_tokens"], 16384, 200, 1)[1] == 1.0


def test_visited_block_pairs_are_the_program_s_tables_on_the_same_rows(cell):
    """`flops_afmoe.visited_block_pairs` against `ops/attention.document_block_pairs` on the rows
    `packed_pieces` packs: the count the program's counters should read a layer and row."""
    import jax.numpy as jnp

    from dolomite_engine_tpu.ops.attention import document_block_pairs

    law = dict(cell.traffic["document_tokens"], median=700, min=16, max=4096)
    rows = flops.packed_pieces(law, 2048, 60)
    assert all(sum(row) == 2048 for row in rows) and len(rows) >= 10
    ids = np.stack([np.repeat(np.arange(1, len(row) + 1), row) for row in rows]).astype(np.int32)
    for window, block in ((300, 128), (512, 128), (2048, 512)):
        full, windowed, causal = flops.visited_block_pairs(law, 2048, 60, window, block)
        n = 2048 // block
        assert causal == n * (n + 1) / 2
        assert full == pytest.approx(int(document_block_pairs(jnp.asarray(ids), block).sum()) / len(rows))
        assert windowed == pytest.approx(int(document_block_pairs(jnp.asarray(ids), block, window).sum()) / len(rows))
        assert windowed <= full <= causal
    # the cell's own: a window layer visits about half of what a full layer does, and that half of the triangle
    documents = flops.corpus_documents(cell.traffic, 45.0, 2, 16384)
    full, windowed, causal = flops.visited_block_pairs(cell.traffic["document_tokens"], 16384, documents, 2048, 512)
    assert causal == 528 and 0.45 < windowed / full < 0.6 and 0.4 < full / causal < 0.6


def test_kernel_counts_by_hand(cfg):
    rows = 16384.0
    assert moe_grouped_matmul_swiglu.train_flops(2048, 1024, rows) == 3 * 3 * 2 * 2048 * 1024 * rows
    bank = 8 * 3 * 2048 * 1024
    expected = rows * (2048 + 2048 + 2048) * 2 + rows * (2 * 2048 + 2 * 2048 + 2048) * 2 + 4 * (bank * 2 + bank * 2 + bank * 4)
    assert moe_grouped_matmul_swiglu.train_bytes(2048, 1024, 8, rows, layer_steps=4) == expected
    # splash over the visited blocks, one call a kind: the window layers' and the full layer's add up
    windowed, full = 4 * 2 * 135.0, 2 * 263.0
    both = splash_attention_visited.train_flops(1, 32, 128, 512, 512, windowed) + splash_attention_visited.train_flops(1, 32, 128, 512, 512, full)
    assert both == 2 * 7 * 128 * (windowed + full) * 512 * 512 * 32
    assert splash_attention_visited.train_bytes is splash_attention.train_bytes


# ---- the readers on a hand-built result

def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def built_result(cfg, cell, named=True) -> RunResult:
    """Two traced steps of 1000 us busy each: a window block (norms 20, projection 60, qk norm 10,
    the splash forward 50 and its backward 70, the gate 30, out-projection 40) before the dense MLP
    120; the full block (projection 30, splash 60 + 90, the gate 20) before experts (router 20,
    dispatch 40, grouped products 60, shared expert 50, combine 30); the loss 90, the optimizer 50,
    and 60 no scope names."""
    scope = (lambda s: "/" + s) if named else (lambda s: "")
    module = "moe" if named else "ffn"  # (the program's module is named as its scope is)
    head = "jit(train_step)/jvp(AfmoeForCausalLM)/head_loss"
    window, full = (f"{scope('attention')}{scope(kind)}/attn" for kind in ("attention_window", "attention_full"))
    splash = lambda name: f"jit(_splash_attention)/{name if named else 'x'}/pallas_call:"  # noqa: E731
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        ops += [
            op("%fusion.0", t, 20, f"{FWD}/h_0{scope('block_norms')}/ln_1/mul:"),
            op("%fusion.1", t + 20, 60, f"{FWD}/h_0{window}/c_attn/dot_general:"),
            op("%fusion.2", t + 80, 10, f"{FWD}/h_0{window}{scope('qk_norm')}/mul:"),
            op("%splash.1", t + 90, 50, f"{FWD}/h_0{window}/{splash('splash_mha_fwd')}"),
            op("%splash.2", t + 140, 70, f"{BWD}/h_0{window}/{splash('splash_mha_dkv')}"),
            op("%fusion.3", t + 210, 30, f"{FWD}/h_0{window}{scope('attention_gate')}/g_proj/dot_general:"),
            op("%fusion.4", t + 240, 40, f"{BWD}/h_0{window}/c_proj/dot_general:"),
            op("%fusion.5", t + 280, 120, f"{FWD}/h_0{scope('dense_mlp')}/mlp/c_fc/dot_general:"),
            op("%fusion.6", t + 400, 30, f"{FWD}/h_2{full}/c_attn/dot_general:"),
            op("%splash.3", t + 430, 60, f"{FWD}/h_2{full}/{splash('splash_mha_fwd')}"),
            op("%splash.4", t + 490, 90, f"{BWD}/h_2{full}/{splash('splash_mha_dq')}"),
            op("%fusion.7", t + 580, 20, f"{FWD}/h_2{full}{scope('attention_gate')}/mul:"),
            op("%fusion.8", t + 600, 20, f"{FWD}/h_2{scope('moe')}/{module}{scope('moe_router')}/dot_general:"),
            op("%fusion.9", t + 620, 40, f"{FWD}/h_2{scope('moe')}/{module}{scope('moe_dispatch')}/gather:"),
            op("%gmm.1", t + 660, 60, f"{BWD}/h_2{scope('moe')}/{module}{scope('moe_experts')}/gmm/pallas_call:"),
            op("%fusion.10", t + 720, 50, f"{FWD}/h_2{scope('moe')}/{module}{scope('moe_shared_expert')}/shared_c_fc/dot_general:"),
            op("%fusion.11", t + 770, 30, f"{FWD}/h_2{scope('moe')}/{module}{scope('moe_combine')}/add:"),
            op("%fusion.12", t + 800, 90, f"{head}/loss_chunks/ce_block/logits/dot_general:"),
            op("%fusion.13", t + 890, 50, "jit(train_step)/optimizer/add:"),
            op("%copy.1", t + 940, 60),
        ]
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    if named:
        telemetry.append({"kind": "event", "event": "splash_block_plan", "block_q": 512, "block_kv": 512, "rows": 2, "tables": "segment_ids", "window": 2048, "window_key_blocks": 5})
        telemetry.append({"kind": "event", "event": "splash_block_plan", "block_q": 512, "block_kv": 512, "rows": 2, "tables": "segment_ids"})
        for step in (11, 12):
            telemetry.append({
                "kind": "event", "event": "step_counters", "step": step,
                "routed_slots": [16000, 16800, 15800, 16600], "absent_slots": [246144, 245344, 246344, 245544],
                "fullest_expert_rows": [4000, 2100, 2500, 8300], "held_expert_rows": [[2048] * 8] * 4,
                "splash_blocks_visited_window": 4 * 270, "splash_blocks_visited_full": 526, "splash_blocks_causal": 5 * 1056,
            })
    facts = dict(
        cfg=cfg, traced_steps=2, traced_first_step=11, tokens_per_step=32768, sequence_length=16384, rows=2, chips=1,
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
    assert read("window_attention_share.train", result, cell) == pytest.approx(100 * (60 + 10 + 50 + 70 + 30 + 40) / 1000)
    assert read("full_attention_share.train", result, cell) == pytest.approx(100 * (30 + 60 + 90 + 20) / 1000)
    assert read("attention_gate_share.train", result, cell) == pytest.approx(100 * (30 + 20) / 1000)
    assert read("moe_share.train", result, cell) == pytest.approx(100 * (20 + 40 + 60 + 50 + 30) / 1000)
    # ... and the table the first of them prints counts every operation once and sums to the busy time
    table = afmoe_trace.exclusive_table(result)
    per_step = {part: round(seconds / 2 * 1e6) for part, seconds in table["part_s"].items()}
    assert per_step == {
        "block_norms": 20, "attention_window": 260, "dense_mlp": 120, "attention_full": 200, "moe": 200, "head_loss": 90, "optimizer": 50, "unattributed": 60,
    }
    assert table["busy_s"] == pytest.approx(2 * 1000e-6) and sum(table["part_s"].values()) == pytest.approx(table["busy_s"])
    assert round(table["sub_s"][("attention_window", "splash_mha")] / 2 * 1e6) == 120 and round(table["sub_s"][("attention_full", "splash_mha")] / 2 * 1e6) == 150
    assert round(table["sub_s"][("attention_window", "attention_gate")] / 2 * 1e6) == 30 and round(table["sub_s"][("attention_window", "qk_norm")] / 2 * 1e6) == 10
    assert round(table["sub_s"][("moe", "moe_shared_expert")] / 2 * 1e6) == 50 and round(table["sub_s"][("moe", "moe_experts")] / 2 * 1e6) == 60
    out = capsys.readouterr().out
    assert "attention_window" in out and "attention_full" in out
    rows = 2 * 65200.0
    least, _ = moe_grouped_matmul_swiglu.roofline_seconds(
        moe_grouped_matmul_swiglu.train_flops(2048, 1024, rows), moe_grouped_matmul_swiglu.train_bytes(2048, 1024, 8, rows, 8), peaks
    )
    assert read("moe_grouped_matmul_roofline.afmoe", result, cell) == pytest.approx(100 * least / (2 * 60e-6))
    # splash: one call a kind on the two counters (sums over the layers of each kind already), every attention layer's rows' bytes
    required = splash_attention_visited.train_flops(1, 32, 128, 512, 512, 2 * 4 * 270) + splash_attention_visited.train_flops(1, 32, 128, 512, 512, 2 * 526)
    least, _ = splash_attention_visited.roofline_seconds(required, splash_attention.train_bytes(5, 32, 4, 128, 16384, 4), peaks)
    assert read("splash_roofline.afmoe", result, cell) == pytest.approx(100 * least / (2 * 270e-6))
    out = capsys.readouterr().out
    assert "window 135.0, full 263.0, under the diagonal 528.0" in out and "flops_afmoe over the corpus law" in out
    ratios = [4000 * 8 / 16000, 2100 * 8 / 16800, 2500 * 8 / 15800, 8300 * 8 / 16600]
    assert read("expert_rows_max_over_mean.train", result, cell) == pytest.approx(sum(ratios) / 4)
    slots = 65200 / 4 / 32768
    full_keys, window_keys = flops.attended_keys(cell.traffic["document_tokens"], 16384, flops.corpus_documents(cell.traffic, 45.0, 2, 16384), 2048)
    assert read("mfu.afmoe_train", result, cell) == pytest.approx(100 * flops.train_flops_per_token(cfg, full_keys, window_keys, slots) * 32768 / 1.97e14)
    # the accepted phase readers the file has printed beside them
    assert read("unattributed_device_share.train", result, cell) == pytest.approx(100 * 60 / 1000)
    assert read("head_loss_ms.train", result, cell) == pytest.approx(90 / 1000)
    assert read("blocks_fwd_ms.train", result, cell) + read("blocks_bwd_ms.train", result, cell) == pytest.approx((20 + 260 + 120 + 200 + 200) / 1000)
    assert read("device_programs_per_step.train", result, cell) == 1.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_where_the_program_has_no_such_scope_or_counter(name, cfg, cell):
    """A program without these scopes and counters (the parent's), another model's cell and
    configuration: nothing is read and nothing is raised."""
    for other_cell in ("train-lfm2-moe-packed8k", "train-3b-packed4k", "train-ouro-loop4-packed8k"):
        other = Spec.load().cell(other_cell)
        result = built_result(cfg, cell, named=False)
        result.facts["cfg"] = other.config["pretrained_config"]
        assert read(name, result, other) is None
        # ... and on a program that has this family's scopes under another configuration's cell, the family's counts stay silent
        named = built_result(cfg, cell)
        named.facts["cfg"] = other.config["pretrained_config"]
        if name in ("mfu.afmoe_train", "splash_roofline.afmoe", "moe_grouped_matmul_roofline.afmoe"):
            assert read(name, named, other) is None
    untraced = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], facts={})
    assert read(name, untraced, cell) is None
    # ... and this configuration on a program that names no scope: still nothing, still no raise
    unnamed = built_result(cfg, cell, named=False)
    assert read(name, unnamed, cell) is None or name == "mfu.afmoe_train"  # (a utilization on the host's clock needs no scope)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_on_the_recorded_small_trace(name, cfg, cell):
    """``benchmark/testdata/small.xplane.pb.gz`` (PR 23: a v5e trace of a program from before any
    scope) under this cell's facts: no `jit_train_step`, no scopes, no counters — nothing to read,
    nothing raised (the utilization on the host's clock needs no trace: it reads)."""
    trace = rt.reduce_trace(os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz"))
    facts = dict(
        cfg=cfg, traced_steps=4, traced_first_step=1, tokens_per_step=32768, sequence_length=16384, rows=2, chips=1,
        rate_steps=4, rate_wall_s=4.0, first_measured_step=1, last_measured_step=4,
    )
    recorded = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], trace=trace, facts=facts)
    value = read(name, recorded, cell)
    assert value is None or (name == "mfu.afmoe_train" and 0 < value < 100)


# ---- the comparisons

OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
KINDS = ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"]
SMALL = dict(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=5, n_head=4, num_key_value_heads=2, attention_head_dim=16, n_inner=96,
    layer_types=KINDS, sliding_window=24, num_dense_layers=1, rope_theta=10000,
    num_experts=16, num_experts_per_tok=3, experts_held=[4, 4], moe_intermediate_size=32, num_shared_experts=1, route_scale=2.826,
    eos_token_id=0, z_loss_coef=1e-4,
)
SWAPPED = ["full_attention" if kind == "sliding_attention" else "sliding_attention" for kind in KINDS]
# what the limits must fail: the reference in fp8, and the reference with each of the two named faults
CONTROLS = {"fp8": dict(quant="fp8"), "kinds_swapped": dict(layer_types=SWAPPED), "a_full_layer_that_rotates": dict(rotate_full=True)}


def small_batches(seed):
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        text = rng.integers(1, SMALL["vocab_size"], size=(2, 129)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 120, size=2)] = 0  # document boundaries: pieces longer than the window of 24 and shorter
        batches.append(text)
    return batches


@pytest.fixture(scope="module")
def sound_runs():
    from benchmark.reference import afmoe as reference

    return {seed: reference.train_steps(SMALL, seed, small_batches(seed), OPTIMIZER) for seed in (2**31 + 1, 17)}


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("seed", [2**31 + 1, 17])
def test_the_control_and_the_two_named_faults_fail_the_cell_s_limits(cell, sound_runs, seed, control):
    from benchmark.reference import afmoe as reference

    sound = sound_runs[seed]
    faulty = reference.train_steps(SMALL, seed, small_batches(seed), OPTIMIZER, **CONTROLS[control])
    rows = lambda out: [r["held_expert_rows"] for r in out["routing"]]  # noqa: E731
    checks = tower_driver.compare_with_reference(faulty["losses"], faulty["grad_norms"], faulty["delta_norms"], rows(faulty), sound, cell.limits)
    failed = [c.name for c in checks if not c.ok]
    assert failed, [(c.name, c.value, c.limit) for c in checks]
    if control != "fp8":  # a fault of the attention's kind moves the first gradient's block leaves, whatever the loss does
        assert "first_grad_norm_worst_block_leaf_gap" in failed, [(c.name, c.value, c.limit) for c in checks]


def test_the_reference_passes_its_own_limits_and_the_groups_are_the_family_s(cell, sound_runs):
    sound = sound_runs[17]
    assert len(sound["routing"][0]["held_expert_rows"]) == 4  # four layers of experts
    rows = [r["held_expert_rows"] for r in sound["routing"]]
    same = tower_driver.compare_with_reference(sound["losses"], sound["grad_norms"], sound["delta_norms"], rows, sound, cell.limits)
    names = {c.name for c in same}
    assert {"first_grad_norm_routed_experts_gap", "routed_rows_histogram_gap", "router_choices_moved_share"} <= names
    assert all(c.value == 0 for c in same if c.name != "router_choices_moved_share")
    # the routed group is the routers and banks of the four layers of experts; the dense MLP, attention with its gate, the shared expert and the head are block leaves
    routed = [k for k in sound["grad_norms"] if k.split(".")[-1] in tower_driver.ROUTED_LEAVES and k.startswith("layer")]
    assert sorted(routed) == sorted(f"layer{i}.{leaf}" for i in (1, 2, 3, 4) for leaf in ("gate", "c_fc", "c_proj"))
    assert {"layer0.mlp_c_fc", "layer0.g_proj", "layer2.q_norm_weight", "layer2.attn_c_proj", "layer4.shared_c_fc", "layer3.ln_2_out", "lm_head"} <= set(sound["grad_norms"])


# ---- the routers' biases, balanced (benchmark/weights_afmoe.py says why)

def test_calibration_documents_follow_the_cell_s_law_in_the_cell_s_rows():
    ends = W.calibration_documents(W.CALIBRATION_ROWS, 16384)
    assert ends.shape == (W.CALIBRATION_ROWS, 16384) and (ends == W.calibration_documents(W.CALIBRATION_ROWS, 16384)).all()
    lengths = np.diff(np.flatnonzero(ends.reshape(-1)))  # eos to eos: a document and its eos
    assert len(lengths) >= 8 and 64 < lengths.min() and lengths.max() <= 16385
    assert 2048 < np.median(lengths) < 8192  # about the law's 4096, a window and more
    assert (lengths > 2048).mean() > 0.5  # most documents are longer than the window: what the cell is for


@pytest.mark.parametrize("top_k,experts", [(8, 128), (3, 16)])
def test_balanced_bias_wants_the_even_share_for_every_expert_and_moves_as_little_as_the_scores(top_k, experts):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(experts)
    tokens = 4096
    # lumpy scores: every token carries one of a few kinds' scores (a Zipf corpus' hot tokens) and a little of its own
    kinds = rng.normal(0, 0.9, size=(12, experts))
    logits = kinds[rng.choice(12, size=tokens, p=np.arange(12, 0, -1) / 78)] + rng.normal(0, 0.4, size=(tokens, experts))
    scores = jnp.asarray(1 / (1 + np.exp(-logits)), jnp.float32)
    bias = W.balanced_bias(scores, top_k)
    share = tokens * top_k // experts
    assert (np.asarray(jnp.sum(scores + bias >= 0, axis=0)) == share).all()  # every expert wants the even share

    def loads(b):
        _, chosen = jax.lax.top_k(scores + b, top_k)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=experts)

    drawn, even = loads(jnp.zeros((experts,))), loads(bias)
    assert np.ptp(even) < 0.5 * np.ptp(drawn) and even.max() < 2 * share and even.min() > 0.5 * share, (np.ptp(drawn), even.min(), even.max(), share)
    # a last bit of the scores moves the bias by as little and no choice but a near-tie's
    nudged = W.balanced_bias(scores + jnp.asarray(rng.normal(0, 1e-7, size=scores.shape), jnp.float32), top_k)
    assert float(jnp.max(jnp.abs(nudged - bias))) < 1e-6
    assert np.abs(loads(nudged) - even).sum() <= 4


@pytest.fixture(scope="module")
def balanced_small():
    import jax

    return jax.jit(lambda key: W.make_all(SMALL, key))(W.base_key(23))


def test_the_program_s_weights_and_the_reference_s_share_the_biases_and_no_weights_program_holds_the_calibration(balanced_small):
    """The driver makes the program's weights under its own jit (and once more after the checked
    steps, beside the train state) and the reference makes its own: both get the numbers kept by
    the seed, to the last bit, and neither program holds the calibration's forward."""
    import jax

    eager = W.make_all(SMALL, 23)
    make = lambda key: W.unrolled_program_tree(W.make_all(SMALL, key), SMALL)  # noqa: E731  (the driver's)
    wrapped = jax.jit(make)(W.base_key(23))
    kept = W.balanced_biases(SMALL, 23)
    assert kept.shape == (4, 16) and kept.dtype == np.float32
    for i in (1, 2, 3, 4):
        want = np.asarray(balanced_small["layers"][i]["e_score_correction_bias"])
        assert np.ptp(want) > 0 and (want == kept[i - 1]).all()
        assert (np.asarray(eager["layers"][i]["e_score_correction_bias"]) == want).all()
        assert (np.asarray(wrapped["transformer"][f"h_{i}"]["moe"]["e_score_correction_bias"]) == want).all()
    assert "e_score_correction_bias" not in balanced_small["layers"][0]  # the dense layer has no router
    text = str(jax.make_jaxpr(make)(W.base_key(23)))
    assert "dot_general" not in text and "sort" not in text and "while" not in text  # draws, and the kept biases as constants
    assert (W.balanced_biases(SMALL, 29) != kept).any()  # another seed, other weights, other biases


def test_make_all_refuses_a_key_it_cannot_tell_the_seed_of():
    import jax

    W._LAST_SEED[0] = None
    with pytest.raises(ValueError, match="base_key"):
        jax.jit(lambda key: W.make_all(SMALL, key))(jax.random.key(5, impl="rbg"))
    W.base_key(23)


@pytest.fixture(scope="module")
def held_on_the_calibration_rows(balanced_small):
    """Through the reference's own forward: (the rows the held experts get in each layer of experts
    on the rows the biases were balanced on, the same with the biases as drawn, the even share)."""
    import jax

    from benchmark.reference import afmoe as reference

    m = W.model_dims(SMALL)
    rows = W.calibration_rows(SMALL, jax.random.fold_in(W.base_key(23), m["n_layer"] + 1))
    forward = jax.jit(lambda weights, row: [f[0].sum() for f in reference.hidden_states(m, weights, row, remat=False)[1]])

    def held(weights):
        return np.sum([np.asarray(forward(weights, row)) for row in rows], axis=0)

    return held(balanced_small), held(W.make_drawn(SMALL, 23)), rows.size * m["top_k"] * m["held"] / m["experts"]


@pytest.mark.parametrize("layer", [1, 2, 3, 4])
def test_balanced_routers_hold_about_the_even_share_on_the_calibration_rows(held_on_the_calibration_rows, layer):
    balanced, drawn, even = held_on_the_calibration_rows
    assert abs(balanced[layer - 1] - even) < 0.25 * even, (balanced, even)
    assert abs(balanced[layer - 1] - even) <= abs(drawn[layer - 1] - even) + 0.05 * even, (balanced, drawn, even)


def test_the_reference_imports_nothing_of_the_program():
    here = os.path.join(ROOT, "benchmark")
    for path in ("reference/afmoe.py", "weights_afmoe.py", "flops_afmoe.py", "afmoe_trace.py"):
        with open(os.path.join(here, path)) as f:
            text = f.read()
        assert "import dolomite_engine_tpu" not in text and "from dolomite_engine_tpu" not in text, path


# ---- the rehearsal

def test_tiny_rehearsal_runs_the_trainer_and_is_never_correct(capsys):
    try:
        line, checks = bench_run.execute(CELL, 2**31 + 5, 6.0, False, tiny=True)
    except RuntimeError as error:
        # a machine so loaded that one toy step outlasts the window (a window needs two steps): once more, with room for them
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
    assert "model_layout" in out and "'experts_held': 4" in out and "'blocks_window': 4" in out and "'chips_sharing_a_layer': 16" in out
