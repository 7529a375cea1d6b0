"""Seeded weights of `ouro` (Ouro-2.6B, a looped language model) in the benchmark's own layout,
made on the device from ``--seed``; the program under test and the plain reference both get
theirs from here. ONE set of blocks: the loop applies it `total_ut_steps` times.

    outer:     wte [V, d], lm_head [V, d] (untied), ln_f [d] ones (it closes every pass),
               gate_w [d, 1], gate_b [1] zeros (the exit gate)
    layer i:   ln_1, ln_1_out, ln_2, ln_2_out [d] ones (each sub-layer's input and output norm)
               c_attn [d, 3 heads head] ([Q | K | V]), attn_c_proj [heads head, d]
               c_fc [d, 2 n_inner] ([up | gate]), mlp_c_proj [n_inner, d]

Initial values the public ``config.json`` does not give (``assumed`` in the configuration's
file): matrices normal(0, initializer_range), the residual out-projections divided by
sqrt(2 n_layer) (the depth of ONE pass: the published code knows no other); the gate's weight
normal(0, initializer_range) and its bias 0, so a fresh gate stops with probability one half
after every pass and the four passes weigh 1/2, 1/4, 1/8, 1/8.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _key, _normal, base_key  # noqa: F401  (base_key: the callers' key maker)


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    return dict(
        vocab=cfg["vocab_size"], d=cfg["n_embd"], n_layer=cfg["n_layer"], passes=cfg.get("total_ut_steps", 4),
        n_head=cfg["n_head"], n_kv=cfg.get("num_key_value_heads") or cfg["n_head"], head_dim=cfg["n_embd"] // cfg["n_head"],
        n_inner=cfg["n_inner"], rope_theta=cfg.get("rope_theta", 1e6), beta=cfg.get("exit_entropy_coef", 0.05),
        std=cfg.get("initializer_range", 0.02), eps=cfg.get("layer_norm_epsilon", 1e-6),
        eos=cfg.get("eos_token_id", 0), z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def make_layer(cfg: dict, seed, index: int, dtype=jnp.float32) -> dict:
    """Block ``index`` (a Python int). ``seed`` is the whole number or ``base_key(seed)``."""
    m = model_dims(cfg)
    keys = jax.random.split(jax.random.fold_in(_key(seed), index + 1), 4)
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    d, heads, kv, head = m["d"], m["n_head"], m["n_kv"], m["head_dim"]
    ones = jnp.ones((d,), dtype)
    return dict(
        ln_1=ones, ln_1_out=ones, ln_2=ones, ln_2_out=ones,
        c_attn=_normal(keys[0], (d, (heads + 2 * kv) * head), m["std"], dtype),
        attn_c_proj=_normal(keys[1], (heads * head, d), proj_std, dtype),
        c_fc=_normal(keys[2], (d, 2 * m["n_inner"]), m["std"], dtype),
        mlp_c_proj=_normal(keys[3], (m["n_inner"], d), proj_std, dtype),
    )


def make_outer(cfg: dict, seed, dtype=jnp.float32) -> dict:
    m = model_dims(cfg)
    keys = jax.random.split(jax.random.fold_in(_key(seed), 0), 3)
    return {
        "wte": _normal(keys[0], (m["vocab"], m["d"]), m["std"], dtype),
        "lm_head": _normal(keys[1], (m["vocab"], m["d"]), m["std"], dtype),
        "ln_f": jnp.ones((m["d"],), dtype),
        "gate_w": _normal(keys[2], (m["d"], 1), m["std"], dtype),
        "gate_b": jnp.zeros((1,), dtype),
    }


def make_all(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """Every weight as ``{"outer": {...}, "layers": [{...}, ...]}``; call it under one jit."""
    key = _key(seed)
    return {
        "outer": make_outer(cfg, key, dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(model_dims(cfg)["n_layer"])],
    }


def count_parameters(cfg: dict) -> dict:
    """Parameter counts from the shapes: a block's matmul parameters by part, a block, the two
    tables, the gate, and the total held (every weight ONCE, however often the loop reads it)."""
    m = model_dims(cfg)
    d, heads, kv, head = m["d"], m["n_head"], m["n_kv"], m["head_dim"]
    attention_matmul = d * (heads + 2 * kv) * head + heads * head * d
    mlp_matmul = 3 * d * m["n_inner"]
    block = attention_matmul + mlp_matmul + 4 * d
    table = m["vocab"] * d
    gate = d + 1
    return dict(
        attention_matmul=attention_matmul, mlp_matmul=mlp_matmul, block=block, table=table, gate=gate,
        passes=m["passes"], block_applications=m["passes"] * m["n_layer"],
        total=m["n_layer"] * block + 2 * table + d + gate,
    )


# ---------------------------------------------------------------- the program's layout

_BLOCK_LEAVES = {
    "ln_1": ("ln_1", "weight"), "ln_1_out": ("ln_1_out", "weight"), "ln_2": ("ln_2", "weight"), "ln_2_out": ("ln_2_out", "weight"),
    "c_attn": ("attn", "c_attn", "kernel"), "attn_c_proj": ("attn", "c_proj", "kernel"),
    "c_fc": ("mlp", "c_fc", "kernel"), "mlp_c_proj": ("mlp", "c_proj", "kernel"),
}  # ours -> the path inside a block of the program (models/ouro.py)


def unrolled_program_tree(weights: dict, cfg: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (models/ouro.py): the blocks and
    the final norm under the loop's one stack."""
    outer = weights["outer"]
    stack: dict = {"ln_f": {"weight": outer["ln_f"]}}
    for i, p in enumerate(weights["layers"]):
        block: dict = {}
        for name, leaf in p.items():
            node = block
            *parents, last = _BLOCK_LEAVES[name]
            for key in parents:
                node = node.setdefault(key, {})
            node[last] = leaf
        stack[f"h_{i}"] = block
    return {
        "transformer": {"wte": {"embedding": outer["wte"]}, "stack": stack},
        "lm_head": {"kernel": outer["lm_head"]},
        "exit_gate": {"kernel": outer["gate_w"], "bias": outer["gate_b"]},
    }


def leaves_by_name(tree: dict) -> dict:
    """{"wte": x, "lm_head": x, "gate_w": x, "layer0.c_attn": x, ...} from a tree in the program's layout."""
    stack = tree["transformer"]["stack"]
    out = {
        "wte": tree["transformer"]["wte"]["embedding"], "lm_head": tree["lm_head"]["kernel"], "ln_f": stack["ln_f"]["weight"],
        "gate_w": tree["exit_gate"]["kernel"], "gate_b": tree["exit_gate"]["bias"],
    }
    for key, block in stack.items():
        if not key.startswith("h_"):
            continue
        for name, path in _BLOCK_LEAVES.items():
            node = block
            for part in path:
                node = node[part]
            out[f"layer{key[2:]}.{name}"] = node
    return out
