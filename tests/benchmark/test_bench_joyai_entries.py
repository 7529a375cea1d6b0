"""`joyai_llm_flash`'s entries of `BENCHMARK.json`, held from the front. Two tests of
`test_bench_joyai.py` pin joyai's entries as the LAST of their lists; since a later configuration
was appended they stop at those lines (`conftest.py` reports exactly those three lines as expected
failures and nothing else of the two tests). What the pins guarded — joyai's entries where they
were and as they were — and what stands after a pin in its test are asserted here, by position
from the front, so that nothing but "last" is lost. The bodies' other lines (the published widths
against the catalog, `pretrained_config`, `reduced`) still run, and still fail, in the tests that
hold them; they are repeated here too so that this file alone says all of it."""

import json
import os

import pytest

from benchmark.spec import ROOT, Spec
from tests.benchmark.test_bench_joyai import (
    ACCEPTED_READERS_OF_THE_CELL, CATALOG, CELL, NEW_READERS, OWN_READERS, PRINTED_ACCEPTED_READERS,
)

CELLS_WHEN_ACCEPTED = ["train-3b-packed4k", "train-8b-packed4k", "train-nemotron-tower-packed8k", CELL]


@pytest.fixture(scope="module")
def cell():
    return Spec.load().cell(CELL)


def test_published_widths_and_the_cut_with_the_entries_counted_from_the_front(cell):
    public, cfg = cell.config, cell.config["pretrained_config"]
    cut = {"n_routed_experts": 16, "vocab_size": 16160}
    for key, value in CATALOG.items():
        assert public[key] == cut.get(key, value), key
    assert public["published"] == {"n_routed_experts": 256, "vocab_size": 129280, "num_hidden_layers": 40}
    assert public["vocab_size"] * 8 == 129280 and public["n_routed_experts"] * public["chips_sharing_a_layer"] == 256
    assert cfg["n_embd"] == public["hidden_size"] and cfg["n_inner"] == public["intermediate_size"] and cfg["n_head"] == public["num_attention_heads"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace", "num_nextn_predict_layers", "vocab_size"):
        assert public[key] == cfg[key], key
    assert cfg["num_experts"] == 256 and cfg["experts_held"] == [0, public["n_routed_experts"]] and cfg["n_layer"] == 5
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    entry = data["configs"][3]
    assert entry["name"] == "joyai-llm-flash" and entry["file"] == "benchmark/configs/joyai-llm-flash.json"
    assert set(entry["reduced"]) == set(public["reduced"]) and entry["source"] == public["source"]
    assert entry["reduced"] == ["n_layer", "n_routed_experts", "vocab_size", "n_positions", "micro_batch_size", "gradient_accumulation_steps", "lr", "tensor_parallel_size"]
    # the pin, from the front: joyai's configuration and cell are the fourth of their lists, after the three before them
    assert [c["name"] for c in data["configs"]][:4] == ["granite-3b-code", "granite-8b-code", "nemotron-twotower-30b-a3b", "joyai-llm-flash"]
    assert [w["name"] for w in data["workloads"]][:4] == CELLS_WHEN_ACCEPTED and data["workloads"][3]["config"] == "joyai-llm-flash"


def test_the_cell_stands_fourth_in_four_lists_and_its_own_readers_wait_for_a_benchmark_pr(cell):
    from tests.benchmark.test_bench_phases import READERS

    data = Spec.load().data
    names = [m["name"] for m in data["per_layer"]]
    assert names[-7:] == READERS and not set(NEW_READERS) & set(names)
    assert cell.config["layer_metrics_without_an_entry"] == OWN_READERS + PRINTED_ACCEPTED_READERS
    for metric in data["per_layer"]:
        assert (CELL in metric["workloads"]) == (metric["name"] in ACCEPTED_READERS_OF_THE_CELL)
        if CELL in metric["workloads"]:
            assert metric["workloads"][:4] == CELLS_WHEN_ACCEPTED  # where it was appended, and what stood before it
    (rate,) = [m for m in data["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][:4] == CELLS_WHEN_ACCEPTED and rate["bound"] == 0.02
