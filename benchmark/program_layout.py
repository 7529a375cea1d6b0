"""The benchmark's weights in the program's parameter layout (gpt_dolomite), and back:
the one place that knows the names of the program's parameter tree."""

from __future__ import annotations

# toy widths of the CPU rehearsal (--tiny), which can never report correct
TINY = dict(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_inner=256)


def unrolled_program_tree(weights: dict) -> dict:
    """The benchmark's weights in the program's unrolled parameter layout."""
    transformer = {
        "wte": {"embedding": weights["outer"]["wte"]},
        "ln_f": {"weight": weights["outer"]["ln_f"]},
    }
    for i, p in enumerate(weights["layers"]):
        transformer[f"h_{i}"] = {
            "ln_1": {"weight": p["ln_1"]},
            "attn": {"c_attn": {"kernel": p["c_attn"]}, "c_proj": {"kernel": p["attn_c_proj"]}},
            "ln_2": {"weight": p["ln_2"]},
            "mlp": {"c_fc": {"kernel": p["c_fc"]}, "c_proj": {"kernel": p["mlp_c_proj"]}},
        }
    return {"transformer": transformer}


def leaves_by_name(unrolled: dict) -> dict:
    """{"wte": x, "layer0.c_attn": x, ...} from a tree in the program's unrolled layout."""
    t = unrolled["transformer"]
    out = {"wte": t["wte"]["embedding"], "ln_f": t["ln_f"]["weight"]}
    for key, block in t.items():
        if not key.startswith("h_"):
            continue
        i = int(key[2:])
        out[f"layer{i}.ln_1"] = block["ln_1"]["weight"]
        out[f"layer{i}.c_attn"] = block["attn"]["c_attn"]["kernel"]
        out[f"layer{i}.attn_c_proj"] = block["attn"]["c_proj"]["kernel"]
        out[f"layer{i}.ln_2"] = block["ln_2"]["weight"]
        out[f"layer{i}.c_fc"] = block["mlp"]["c_fc"]["kernel"]
        out[f"layer{i}.mlp_c_proj"] = block["mlp"]["c_proj"]["kernel"]
    return out
