"""Seeded weights of `joyai_llm_flash` (JoyAI-LLM-Flash) in the benchmark's own layout, made on
the device from ``--seed``; the program under test and the plain reference both get theirs
from here.

    outer:     wte [V, d], lm_head [V, d] (untied), ln_f [d] ones
    layer i:   ln_1, ln_2 [d] ones; latent attention q_a_proj [d, r_q], q_a_layernorm [r_q] ones,
               q_b_proj [r_q, heads (nope + rope)], kv_a_proj_with_mqa [d, r_kv + rope],
               kv_a_layernorm [r_kv] ones, kv_b_proj [r_kv, heads (nope + v)], o_proj [heads v, d];
      dense    (i < first_k_dense_replace) mlp_c_fc [d, 2 n_inner] ([up | gate]), mlp_c_proj [n_inner, d]
      experts  gate [d, E_all], e_score_correction_bias [E_all], c_fc [E_held, d, 2 f] ([up | gate]),
               c_proj [E_held, f, d], shared_c_fc [d, 2 f_shared], shared_c_proj [f_shared, d]
    layer n_layer (the multi-token-prediction module, where the configuration has one): an
               expert layer's leaves and mtp_enorm, mtp_hnorm, mtp_norm [d] ones,
               mtp_eh_proj [2 d, d] ([embedding ; hidden])

An expert's weights depend on the seed, the layer and the expert's own index among ALL the
router's experts, so the shares of a layer add up to it (tests/models/test_joyai_flash.py).
Initial values the public ``config.json`` does not give (``assumed`` in the configuration's
file): matrices normal(0, initializer_range), the residual out-projections (o_proj, the MLPs'
and experts' down) divided by sqrt(2 n_layer); the router's correction bias normal(0, 0.05)
and held there.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _key, _normal, base_key  # noqa: F401  (base_key: the callers' key maker)

CORRECTION_BIAS_STD = 0.05
ATTENTION_LEAVES = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj", "o_proj")
MTP_LEAVES = {"mtp_enorm": "enorm", "mtp_hnorm": "hnorm", "mtp_norm": "norm", "mtp_eh_proj": "eh_proj"}  # ours -> the program's


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    first, held = cfg.get("experts_held") or (0, cfg["num_experts"])
    return dict(
        vocab=cfg["vocab_size"], d=cfg["n_embd"], n_layer=cfg["n_layer"], dense_layers=cfg.get("first_k_dense_replace", 1),
        mtp=cfg.get("num_nextn_predict_layers", 0), mtp_coef=cfg.get("mtp_loss_coef", 0.3),
        n_head=cfg["n_head"], q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"], rope_theta=cfg.get("rope_theta", 10000.0),
        n_inner=cfg["n_inner"], experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"], first_expert=first, held=held,
        f=cfg["moe_intermediate_size"], f_shared=cfg.get("n_shared_experts", 1) * cfg["moe_intermediate_size"],
        scale=cfg.get("routed_scaling_factor", 1.0),
        std=cfg.get("initializer_range", 0.02), eps=cfg.get("layer_norm_epsilon", 1e-6),
        eos=cfg.get("eos_token_id", 0), z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def layer_kinds(cfg: dict) -> str:
    """A letter a layer of the benchmark's list: ``D`` dense, ``E`` experts, ``P`` the
    multi-token-prediction module (last)."""
    m = model_dims(cfg)
    return "D" * m["dense_layers"] + "E" * (m["n_layer"] - m["dense_layers"]) + "P" * m["mtp"]


def make_layer(cfg: dict, seed, index: int, dtype=jnp.float32) -> dict:
    """Layer ``index`` (a Python int; ``n_layer`` is the multi-token-prediction module).
    ``seed`` is the whole number or ``base_key(seed)``."""
    m = model_dims(cfg)
    kind = layer_kinds(cfg)[index]
    keys = jax.random.split(jax.random.fold_in(_key(seed), index + 1), 12)
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    heads, d = m["n_head"], m["d"]
    layer = dict(
        ln_1=ones(d), ln_2=ones(d),
        q_a_proj=_normal(keys[0], (d, m["q_rank"]), m["std"], dtype), q_a_layernorm=ones(m["q_rank"]),
        q_b_proj=_normal(keys[1], (m["q_rank"], heads * (m["nope"] + m["rope"])), m["std"], dtype),
        kv_a_proj_with_mqa=_normal(keys[2], (d, m["kv_rank"] + m["rope"]), m["std"], dtype), kv_a_layernorm=ones(m["kv_rank"]),
        kv_b_proj=_normal(keys[3], (m["kv_rank"], heads * (m["nope"] + m["v"])), m["std"], dtype),
        o_proj=_normal(keys[4], (heads * m["v"], d), proj_std, dtype),
    )
    if kind == "D":
        layer.update(
            mlp_c_fc=_normal(keys[5], (d, 2 * m["n_inner"]), m["std"], dtype),
            mlp_c_proj=_normal(keys[6], (m["n_inner"], d), proj_std, dtype),
        )
        return layer

    def bank(base, shape, std):
        # one draw an expert, keyed by its index among ALL experts, one after the other in a loop
        # the compiler sees once (`lax.map`; no vmap: a batched draw of the device's generator is
        # not the single draws side by side; a Python loop of 16 draws a bank took the weights'
        # program 77 s to compile on the chip)
        ids = jnp.arange(m["first_expert"], m["first_expert"] + m["held"])
        return jax.lax.map(lambda e: _normal(jax.random.fold_in(base, e), shape, std, dtype), ids)

    layer.update(
        gate=_normal(keys[5], (d, m["experts"]), m["std"], dtype),
        e_score_correction_bias=_normal(keys[6], (m["experts"],), CORRECTION_BIAS_STD, dtype),
        c_fc=bank(keys[7], (d, 2 * m["f"]), m["std"]),
        c_proj=bank(keys[8], (m["f"], d), proj_std),
        shared_c_fc=_normal(keys[9], (d, 2 * m["f_shared"]), m["std"], dtype),
        shared_c_proj=_normal(keys[10], (m["f_shared"], d), proj_std, dtype),
    )
    if kind == "P":
        layer.update(mtp_enorm=ones(d), mtp_hnorm=ones(d), mtp_norm=ones(d), mtp_eh_proj=_normal(keys[11], (2 * d, d), m["std"], dtype))
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
    """Every weight as ``{"outer": {...}, "layers": [{...}, ...]}`` (the multi-token-prediction
    module last); call it under one jit."""
    key = _key(seed)
    return {
        "outer": make_outer(cfg, key, dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(len(layer_kinds(cfg)))],
    }


def count_parameters(cfg: dict) -> dict:
    """Parameter counts from the shapes: the matmul parameters of each part (the routed banks
    one expert at a time) and the total of everything held here."""
    m = model_dims(cfg)
    d, heads = m["d"], m["n_head"]
    attention = (
        d * m["q_rank"] + m["q_rank"] * heads * (m["nope"] + m["rope"]) + d * (m["kv_rank"] + m["rope"])
        + m["kv_rank"] * heads * (m["nope"] + m["v"]) + heads * m["v"] * d
    )
    norms = 2 * d + m["q_rank"] + m["kv_rank"]
    dense_mlp = 3 * d * m["n_inner"]
    routed_expert, shared = 3 * d * m["f"], 3 * d * m["f_shared"]
    router = d * m["experts"]
    dense_block = attention + norms + dense_mlp
    expert_block = attention + norms + router + m["experts"] + shared + m["held"] * routed_expert
    mtp_projection = 2 * d * d
    mtp_module = expert_block + mtp_projection + 3 * d
    tables = 2 * m["vocab"] * d
    kinds = layer_kinds(cfg)
    total = kinds.count("D") * dense_block + kinds.count("E") * expert_block + kinds.count("P") * mtp_module + tables + d
    return dict(
        attention_matmul=attention, dense_mlp=dense_mlp, routed_expert=routed_expert, shared_expert=shared, router=router,
        mtp_projection=mtp_projection, dense_block=dense_block, expert_block=expert_block, mtp_module=mtp_module,
        tables=tables, layers_of_kind={k: kinds.count(k) for k in "DEP"}, total=total,
    )


# ---------------------------------------------------------------- the program's layout


def _program_block(p: dict) -> dict:
    block = {
        "ln_1": {"weight": p["ln_1"]}, "ln_2": {"weight": p["ln_2"]},
        "attn": {k: {"weight" if k.endswith("layernorm") else "kernel": p[k]} for k in ATTENTION_LEAVES},
    }
    if "mlp_c_fc" in p:
        block["mlp"] = {"c_fc": {"kernel": p["mlp_c_fc"]}, "c_proj": {"kernel": p["mlp_c_proj"]}}
    else:
        block["moe"] = {
            "gate": p["gate"], "e_score_correction_bias": p["e_score_correction_bias"],
            **{k: {"kernel": p[k]} for k in ("c_fc", "c_proj", "shared_c_fc", "shared_c_proj")},
        }
    return block


def unrolled_program_tree(weights: dict, cfg: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (models/joyai_flash.py)."""
    transformer = {"wte": {"embedding": weights["outer"]["wte"]}, "ln_f": {"weight": weights["outer"]["ln_f"]}}
    for i, (kind, p) in enumerate(zip(layer_kinds(cfg), weights["layers"])):
        if kind == "P":
            transformer["mtp"] = {
                "block": _program_block(p),
                **{theirs: {"kernel" if ours == "mtp_eh_proj" else "weight": p[ours]} for ours, theirs in MTP_LEAVES.items()},
            }
        else:
            transformer[f"h_{i}"] = _program_block(p)
    return {"transformer": transformer, "lm_head": {"kernel": weights["outer"]["lm_head"]}}


def leaves_by_name(tree: dict) -> dict:
    """{"wte": x, "lm_head": x, "layer0.q_a_proj": x, ...} from a tree in the program's layout;
    the multi-token-prediction module is the layer after the last block."""
    t = tree["transformer"]
    out = {"wte": t["wte"]["embedding"], "ln_f": t["ln_f"]["weight"], "lm_head": tree["lm_head"]["kernel"]}
    blocks = {int(key[2:]): block for key, block in t.items() if key.startswith("h_")}
    if "mtp" in t:
        index = len(blocks)
        blocks[index] = t["mtp"]["block"]
        for ours, theirs in MTP_LEAVES.items():
            (out[f"layer{index}.{ours}"],) = t["mtp"][theirs].values()
    for index, block in blocks.items():
        prefix = f"layer{index}."
        out[prefix + "ln_1"], out[prefix + "ln_2"] = block["ln_1"]["weight"], block["ln_2"]["weight"]
        for name, leaf in block["attn"].items():
            (out[prefix + name],) = leaf.values()
        for name, leaf in block.get("mlp", {}).items():
            out[prefix + "mlp_" + name] = leaf["kernel"]
        for name, leaf in block.get("moe", {}).items():
            out[prefix + name] = leaf["kernel"] if isinstance(leaf, dict) else leaf
    return out
