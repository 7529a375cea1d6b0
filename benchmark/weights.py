"""Seeded weights in the benchmark's own layout, made on the device from ``--seed``.

The program under test and the plain reference both get their weights from here, so the
reference takes nothing that the program has made. The layout is flat and per layer:

    wte           [vocab, n_embd]            std = initializer_range
    ln_f          [n_embd]                   ones
    layer i:  ln_1, ln_2   [n_embd]          ones
              c_attn       [n_embd, (n_head + 2 * n_kv) * head_dim]   [Q | K | V]
              attn_c_proj  [n_head * head_dim, n_embd]                std / sqrt(2 * n_layer)
              c_fc         [n_embd, 2 * n_inner]                      [up | gate]
              mlp_c_proj   [n_inner, n_embd]                          std / sqrt(2 * n_layer)

A layer's values depend on the seed and the layer's index only, so the reference can
remake one layer at a time (a 32-layer model in float32 does not fit beside anything).
Values are drawn in float32 and rounded to ``dtype``; the reference upcasts the rounded
values, so both sides hold the same numbers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    n_head = cfg["n_head"]
    n_kv = cfg.get("num_key_value_heads") or n_head
    return dict(
        vocab=cfg["vocab_size"],
        d=cfg["n_embd"],
        n_layer=cfg["n_layer"],
        n_head=n_head,
        n_kv=n_kv,
        head_dim=cfg["n_embd"] // n_head,
        n_inner=cfg["n_inner"],
        std=cfg.get("initializer_range", 0.02),
        eps=cfg.get("layer_norm_epsilon", 1e-5),
        rope_theta=cfg.get("rope_theta", 10000),
        eos=cfg.get("eos_token_id", 0),
        z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def base_key(seed: int) -> jax.Array:
    """A key for any whole number (seeds above 2**31 do not fit a key's int32 seed). The
    ``rbg`` implementation draws bits with the device's own generator: on the chip the 3.5
    billion weights of a served model take seconds, where threefry's took 34-49 s of every
    run's set-up (PR 23, chip). A draw depends on the key, the shape and the backend only,
    so the reference remakes a layer bit for bit on the machine that served it; values
    differ between a CPU and a TPU, which nothing here compares."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _key(seed_or_key):
    return base_key(seed_or_key) if isinstance(seed_or_key, int) else seed_or_key


def make_layer(cfg: dict, seed, index, dtype=jnp.float32) -> dict:
    """Layer ``index`` (a Python int or a traced scalar). ``seed`` is the whole number or
    ``base_key(seed)``: hand a jitted function the key as an argument, so that the compiled
    program does not depend on the seed and the compile cache serves every seed."""
    m = model_dims(cfg)
    keys = jax.random.split(jax.random.fold_in(_key(seed), index + 1), 4)
    qkv = (m["n_head"] + 2 * m["n_kv"]) * m["head_dim"]
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    return {
        "ln_1": jnp.ones((m["d"],), dtype),
        "c_attn": _normal(keys[0], (m["d"], qkv), m["std"], dtype),
        "attn_c_proj": _normal(keys[1], (m["n_head"] * m["head_dim"], m["d"]), proj_std, dtype),
        "ln_2": jnp.ones((m["d"],), dtype),
        "c_fc": _normal(keys[2], (m["d"], 2 * m["n_inner"]), m["std"], dtype),
        "mlp_c_proj": _normal(keys[3], (m["n_inner"], m["d"]), proj_std, dtype),
    }


def make_outer(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """The embedding (tied head) and the final norm."""
    m = model_dims(cfg)
    return {
        "wte": _normal(jax.random.fold_in(_key(seed), 0), (m["vocab"], m["d"]), m["std"], dtype),
        "ln_f": jnp.ones((m["d"],), dtype),
    }


def make_all(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """Every weight as ``{"outer": {...}, "layers": [{...}, ...]}``; call it under one jit."""
    key = _key(seed)
    return {
        "outer": make_outer(cfg, key, dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(cfg["n_layer"])],
    }


def count_parameters(cfg: dict) -> dict:
    """Parameter counts from the shapes: matmul parameters (tied head counted once, it is
    one table) and all parameters."""
    m = model_dims(cfg)
    qkv = (m["n_head"] + 2 * m["n_kv"]) * m["head_dim"]
    per_layer = m["d"] * qkv + m["n_head"] * m["head_dim"] * m["d"] + 3 * m["d"] * m["n_inner"]
    table = m["vocab"] * m["d"]
    return dict(
        per_layer_matmul=per_layer,
        table=table,
        total=m["n_layer"] * (per_layer + 2 * m["d"]) + table + m["d"],
    )
