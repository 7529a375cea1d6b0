"""Seeded weights of the `nemotron_h` tower in the benchmark's own layout, made on the device
from ``--seed``; the program under test and the plain reference both get theirs from here.

    outer:    wte [V, d], lm_head [V, d] (untied; std = initializer_range), ln_f [d] ones
    layer i:  ln_1 [d] ones, and by the pattern's letter
      M  in_proj [d, d_inner + conv_dim + heads] ([z | xBC | dt]), conv_weight [conv_dim, K],
         conv_bias [conv_dim], dt_bias / A_log / D [heads], norm_weight [d_inner] ones,
         out_proj [d_inner, d]
      E  gate [d, E_all], e_score_correction_bias [E_all], c_fc [E_held, d, f],
         c_proj [E_held, f, d], shared_c_fc [d, f_shared], shared_c_proj [f_shared, d]
      *  c_attn [d, (heads + 2 kv) head_dim] ([Q | K | V]), attn_c_proj [heads head_dim, d]

An expert's weights depend on the seed, the layer and the expert's own index among ALL the
router's experts, so a share ``experts_held = (first, count)`` holds exactly the experts
``first .. first + count - 1`` of the uncut layer: the shares of a layer add up to it
(tests/benchmark/test_bench_tower.py).

Initial values the public ``config.json`` does not give (listed under ``assumed`` in the
configuration's file): matrices normal(0, initializer_range), residual out-projections
divided by sqrt(2 n_layer); the convolution uniform(+-1/sqrt(K)) (torch's Conv1d default);
``A_log`` = log uniform(1, 16); ``dt_bias`` = softplus^-1 of a log-uniform step in
[time_step_min, time_step_max] floored at time_step_floor; ``D`` ones; the router's
correction bias normal(0, 0.05) and held there.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _key, _normal, base_key  # noqa: F401  (base_key: the callers' key maker)

CORRECTION_BIAS_STD = 0.05


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["mamba_n_groups"], cfg["ssm_state_size"]
    inner = heads * width
    first, held = cfg.get("experts_held") or (0, cfg["num_experts"])
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["n_layer"], (pattern, cfg["n_layer"])
    return dict(
        vocab=cfg["vocab_size"], d=cfg["n_embd"], n_layer=cfg["n_layer"], pattern=pattern,
        n_head=cfg["n_head"], n_kv=cfg["num_key_value_heads"], head_dim=cfg["attention_head_dim"],
        m_heads=heads, m_width=width, m_groups=groups, m_state=state, m_inner=inner,
        conv_dim=inner + 2 * groups * state, conv_kernel=cfg["conv_kernel"], chunk=cfg.get("chunk_size", 128),
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"], first_expert=first, held=held,
        f=cfg["moe_intermediate_size"], f_shared=cfg["moe_shared_expert_intermediate_size"],
        scale=cfg.get("routed_scaling_factor", 1.0),
        dt_min=cfg.get("time_step_min", 0.001), dt_max=cfg.get("time_step_max", 0.1), dt_floor=cfg.get("time_step_floor", 1e-4),
        std=cfg.get("initializer_range", 0.02), eps=cfg.get("layer_norm_epsilon", 1e-5),
        eos=cfg.get("eos_token_id", 0), z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def _uniform(key, shape, bound, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)


def make_layer(cfg: dict, seed, index: int, dtype=jnp.float32) -> dict:
    """Layer ``index`` (a Python int: its kind is read from the pattern). ``seed`` is the
    whole number or ``base_key(seed)`` (hand a jitted function the key as an argument)."""
    m = model_dims(cfg)
    kind = m["pattern"][index]
    key = jax.random.fold_in(_key(seed), index + 1)
    keys = jax.random.split(key, 8)
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    layer = {"ln_1": jnp.ones((m["d"],), dtype)}
    if kind == "M":
        low, high = math.log(m["dt_min"]), math.log(m["dt_max"])
        step = jnp.exp(jax.random.uniform(keys[3], (m["m_heads"],), jnp.float32) * (high - low) + low)
        step = jnp.maximum(step, m["dt_floor"])
        bound = 1.0 / math.sqrt(m["conv_kernel"])
        layer.update(
            in_proj=_normal(keys[0], (m["d"], m["m_inner"] + m["conv_dim"] + m["m_heads"]), m["std"], dtype),
            conv_weight=_uniform(keys[1], (m["conv_dim"], m["conv_kernel"]), bound, dtype),
            conv_bias=_uniform(keys[2], (m["conv_dim"],), bound, dtype),
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            A_log=jnp.log(jax.random.uniform(keys[4], (m["m_heads"],), jnp.float32, 1.0, 16.0)).astype(dtype),
            D=jnp.ones((m["m_heads"],), dtype),
            norm_weight=jnp.ones((m["m_inner"],), dtype),
            out_proj=_normal(keys[5], (m["m_inner"], m["d"]), proj_std, dtype),
        )
    elif kind == "E":
        def bank(base, shape, std):
            # one draw an expert, keyed by its index among ALL experts (no vmap: a batched
            # draw of the device's generator is not the single draws side by side)
            ids = range(m["first_expert"], m["first_expert"] + m["held"])
            return jnp.stack([_normal(jax.random.fold_in(base, e), shape, std, dtype) for e in ids])

        layer.update(
            gate=_normal(keys[0], (m["d"], m["experts"]), m["std"], dtype),
            e_score_correction_bias=_normal(keys[1], (m["experts"],), CORRECTION_BIAS_STD, dtype),
            c_fc=bank(keys[2], (m["d"], m["f"]), m["std"]),
            c_proj=bank(keys[3], (m["f"], m["d"]), proj_std),
            shared_c_fc=_normal(keys[4], (m["d"], m["f_shared"]), m["std"], dtype),
            shared_c_proj=_normal(keys[5], (m["f_shared"], m["d"]), proj_std, dtype),
        )
    elif kind == "*":
        qkv = (m["n_head"] + 2 * m["n_kv"]) * m["head_dim"]
        layer.update(
            c_attn=_normal(keys[0], (m["d"], qkv), m["std"], dtype),
            attn_c_proj=_normal(keys[1], (m["n_head"] * m["head_dim"], m["d"]), proj_std, dtype),
        )
    else:
        raise ValueError(f"unknown layer kind {kind!r} in pattern {m['pattern']!r}")
    return layer


def make_outer(cfg: dict, seed, dtype=jnp.float32) -> dict:
    m = model_dims(cfg)
    keys = jax.random.split(jax.random.fold_in(_key(seed), 0), 2)
    return {
        "wte": _normal(keys[0], (m["vocab"], m["d"]), m["std"], dtype),
        "lm_head": _normal(keys[1], (m["vocab"], m["d"]), m["std"], dtype),
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
    """Parameter counts from the shapes, by kind of layer (matmul parameters of one layer:
    the routed banks as held here) and in all."""
    m = model_dims(cfg)
    mamba = m["d"] * (m["m_inner"] + m["conv_dim"] + m["m_heads"]) + m["m_inner"] * m["d"]
    mamba_other = m["conv_dim"] * (m["conv_kernel"] + 1) + 3 * m["m_heads"] + m["m_inner"]
    attention = m["d"] * (m["n_head"] + 2 * m["n_kv"]) * m["head_dim"] + m["n_head"] * m["head_dim"] * m["d"]
    routed_expert = 2 * m["d"] * m["f"]
    shared = 2 * m["d"] * m["f_shared"]
    router = m["d"] * m["experts"]
    experts = router + shared + m["held"] * routed_expert
    tables = 2 * m["vocab"] * m["d"]
    kinds = {k: m["pattern"].count(k) for k in "ME*"}
    total = (
        kinds["M"] * (mamba + mamba_other) + kinds["*"] * attention + kinds["E"] * (experts + m["experts"])
        + m["n_layer"] * m["d"] + tables + m["d"]
    )
    return dict(
        mamba_matmul=mamba, attention_matmul=attention, routed_expert=routed_expert, shared_expert=shared,
        router=router, tables=tables, layers_of_kind=kinds, total=total,
    )


# ---------------------------------------------------------------- the program's layout


def unrolled_program_tree(weights: dict, cfg: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (models/nemotron_h.py)."""
    pattern = cfg["hybrid_override_pattern"]
    transformer = {
        "wte": {"embedding": weights["outer"]["wte"]},
        "ln_f": {"weight": weights["outer"]["ln_f"]},
    }
    for i, p in enumerate(weights["layers"]):
        block = {"ln_1": {"weight": p["ln_1"]}}
        if pattern[i] == "M":
            block["mixer"] = {
                "in_proj": {"kernel": p["in_proj"]}, "out_proj": {"kernel": p["out_proj"]},
                **{k: p[k] for k in ("conv_weight", "conv_bias", "dt_bias", "A_log", "D", "norm_weight")},
            }
        elif pattern[i] == "E":
            block["moe"] = {
                "gate": p["gate"], "e_score_correction_bias": p["e_score_correction_bias"],
                **{k: {"kernel": p[k]} for k in ("c_fc", "c_proj", "shared_c_fc", "shared_c_proj")},
            }
        else:
            block["attn"] = {"c_attn": {"kernel": p["c_attn"]}, "c_proj": {"kernel": p["attn_c_proj"]}}
        transformer[f"h_{i}"] = block
    return {"transformer": transformer, "lm_head": {"kernel": weights["outer"]["lm_head"]}}


def leaves_by_name(tree: dict) -> dict:
    """{"wte": x, "lm_head": x, "layer0.in_proj": x, ...} from a tree in the program's layout."""
    t = tree["transformer"]
    out = {"wte": t["wte"]["embedding"], "ln_f": t["ln_f"]["weight"], "lm_head": tree["lm_head"]["kernel"]}
    for key, block in t.items():
        if not key.startswith("h_"):
            continue
        prefix = f"layer{int(key[2:])}."
        out[prefix + "ln_1"] = block["ln_1"]["weight"]
        for module in ("mixer", "moe", "attn"):
            for name, leaf in block.get(module, {}).items():
                if name == "c_proj" and module == "attn":
                    name = "attn_c_proj"
                out[prefix + name] = leaf["kernel"] if isinstance(leaf, dict) else leaf
    return out
