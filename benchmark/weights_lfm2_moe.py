"""Seeded weights of `lfm2_moe` (LFM2-24B-A2B) in the benchmark's own layout, made on the device
from ``--seed``; the program under test and the plain reference both get theirs from here.

    outer:     wte [V, d] (the head's table too: tied), ln_f [d] ones
    layer i:   ln_1, ln_2 [d] ones; by layer_types[i]
      conv            in_proj [d, 3 d] ([B | C | u]), conv_weight [d, taps], out_proj [d, d]
      full_attention  c_attn [d, (heads + 2 kv) head] ([Q | K | V]), q_norm_weight, k_norm_weight
                      [head] ones, attn_c_proj [heads head, d]
               and by depth
      dense    (i < num_dense_layers) mlp_c_fc [d, 2 n_inner] ([up | gate]), mlp_c_proj [n_inner, d]
      experts  gate [d, E_all], e_score_correction_bias [E_all], c_fc [E_held, d, 2 f] ([up | gate]),
               c_proj [E_held, f, d]; no shared expert

An expert's weights depend on the seed, the layer and the expert's own index among ALL the
router's experts, so the shares of a layer add up to it (tests/models/test_lfm2_moe.py).
Initial values the public ``config.json`` does not give (``assumed`` in the configuration's
file): matrices normal(0, initializer_range), the residual out-projections (out_proj,
attn_c_proj, the MLP's and the experts' down) divided by sqrt(2 n_layer); the convolution's
filter uniform(-1/sqrt(taps), 1/sqrt(taps)) (torch's Conv1d default); the router's bias
normal(0, 0.05) and held there.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _key, _normal, base_key  # noqa: F401  (base_key: the callers' key maker)

EXPERT_BIAS_STD = 0.05


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    first, held = cfg.get("experts_held") or (0, cfg["num_experts"])
    return dict(
        vocab=cfg["vocab_size"], d=cfg["n_embd"], n_layer=cfg["n_layer"], layer_types=tuple(cfg["layer_types"]),
        dense_layers=cfg.get("num_dense_layers", 2), taps=cfg.get("conv_L_cache", 3),
        n_head=cfg["n_head"], n_kv=cfg["num_key_value_heads"], head_dim=cfg["n_embd"] // cfg["n_head"],
        rope_theta=cfg.get("rope_theta", 1e6), n_inner=cfg["n_inner"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"], first_expert=first, held=held,
        f=cfg["moe_intermediate_size"], scale=cfg.get("routed_scaling_factor", 1.0),
        route_epsilon=cfg.get("norm_topk_prob_epsilon", 1e-6),
        std=cfg.get("initializer_range", 0.02), eps=cfg.get("layer_norm_epsilon", 1e-5),
        eos=cfg.get("eos_token_id", 0), z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def layer_kinds(cfg: dict) -> list:
    """(operator, feed-forward) a layer: ``("conv" | "full_attention", "dense" | "experts")``."""
    m = model_dims(cfg)
    return [(operator, "dense" if i < m["dense_layers"] else "experts") for i, operator in enumerate(m["layer_types"])]


def make_layer(cfg: dict, seed, index: int, dtype=jnp.float32) -> dict:
    """Layer ``index`` (a Python int). ``seed`` is the whole number or ``base_key(seed)``."""
    m = model_dims(cfg)
    operator, feed_forward = layer_kinds(cfg)[index]
    keys = jax.random.split(jax.random.fold_in(_key(seed), index + 1), 8)
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    d = m["d"]
    layer = dict(ln_1=ones(d), ln_2=ones(d))
    if operator == "conv":
        bound = 1.0 / math.sqrt(m["taps"])
        layer.update(
            in_proj=_normal(keys[0], (d, 3 * d), m["std"], dtype),
            conv_weight=jax.random.uniform(keys[1], (d, m["taps"]), jnp.float32, -bound, bound).astype(dtype),
            out_proj=_normal(keys[2], (d, d), proj_std, dtype),
        )
    else:
        heads, kv, head = m["n_head"], m["n_kv"], m["head_dim"]
        layer.update(
            c_attn=_normal(keys[0], (d, (heads + 2 * kv) * head), m["std"], dtype),
            q_norm_weight=ones(head), k_norm_weight=ones(head),
            attn_c_proj=_normal(keys[2], (heads * head, d), proj_std, dtype),
        )
    if feed_forward == "dense":
        layer.update(
            mlp_c_fc=_normal(keys[3], (d, 2 * m["n_inner"]), m["std"], dtype),
            mlp_c_proj=_normal(keys[4], (m["n_inner"], d), proj_std, dtype),
        )
        return layer

    def bank(base, shape, std):
        # one draw an expert, keyed by its index among ALL experts, one after the other in a loop
        # the compiler sees once (`lax.map`; no vmap: a batched draw of the device's generator is
        # not the single draws side by side; a Python loop of draws takes the weights' program
        # over a minute to compile on the chip: PERF.md section 6, PR 30)
        ids = jnp.arange(m["first_expert"], m["first_expert"] + m["held"])
        return jax.lax.map(lambda e: _normal(jax.random.fold_in(base, e), shape, std, dtype), ids)

    layer.update(
        gate=_normal(keys[3], (d, m["experts"]), m["std"], dtype),
        e_score_correction_bias=_normal(keys[4], (m["experts"],), EXPERT_BIAS_STD, dtype),
        c_fc=bank(keys[5], (d, 2 * m["f"]), m["std"]),
        c_proj=bank(keys[6], (m["f"], d), proj_std),
    )
    return layer


def make_outer(cfg: dict, seed, dtype=jnp.float32) -> dict:
    m = model_dims(cfg)
    key = jax.random.fold_in(_key(seed), 0)
    return {"wte": _normal(key, (m["vocab"], m["d"]), m["std"], dtype), "ln_f": jnp.ones((m["d"],), dtype)}


def make_all(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """Every weight as ``{"outer": {...}, "layers": [{...}, ...]}``; call it under one jit."""
    key = _key(seed)
    return {
        "outer": make_outer(cfg, key, dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(model_dims(cfg)["n_layer"])],
    }


def count_parameters(cfg: dict) -> dict:
    """Parameter counts from the shapes: the matmul parameters of each part (the routed banks
    one expert at a time) and the total of everything held here."""
    m = model_dims(cfg)
    d, heads, kv, head = m["d"], m["n_head"], m["n_kv"], m["head_dim"]
    conv_matmul = d * 3 * d + d * d
    conv_operator = conv_matmul + d * m["taps"]
    attention_matmul = d * (heads + 2 * kv) * head + heads * head * d
    attention_operator = attention_matmul + 2 * head
    dense_mlp = 3 * d * m["n_inner"]
    routed_expert = 3 * d * m["f"]
    router = d * m["experts"]
    experts_layer = router + m["experts"] + m["held"] * routed_expert
    norms = 2 * d
    kinds = layer_kinds(cfg)
    operators = {"conv": conv_operator, "full_attention": attention_operator}
    feed_forwards = {"dense": dense_mlp, "experts": experts_layer}
    blocks = [operators[o] + feed_forwards[f] + norms for o, f in kinds]
    table = m["vocab"] * d
    return dict(
        conv_matmul=conv_matmul, conv_operator=conv_operator, attention_matmul=attention_matmul,
        attention_operator=attention_operator, dense_mlp=dense_mlp, routed_expert=routed_expert, router=router,
        experts_layer=experts_layer, blocks=blocks, table=table,
        layers_of_kind={
            "conv": sum(o == "conv" for o, _ in kinds), "full_attention": sum(o == "full_attention" for o, _ in kinds),
            "dense": sum(f == "dense" for _, f in kinds), "experts": sum(f == "experts" for _, f in kinds),
        },
        total=sum(blocks) + table + d,
    )


# ---------------------------------------------------------------- the program's layout

_OPERATOR_LEAVES = {
    "in_proj": ("conv", "in_proj", "kernel"), "conv_weight": ("conv", "conv_weight"), "out_proj": ("conv", "out_proj", "kernel"),
    "c_attn": ("attn", "c_attn", "kernel"), "q_norm_weight": ("attn", "q_norm_weight"), "k_norm_weight": ("attn", "k_norm_weight"),
    "attn_c_proj": ("attn", "c_proj", "kernel"),
    "mlp_c_fc": ("mlp", "c_fc", "kernel"), "mlp_c_proj": ("mlp", "c_proj", "kernel"),
    "gate": ("moe", "gate"), "e_score_correction_bias": ("moe", "e_score_correction_bias"),
    "c_fc": ("moe", "c_fc", "kernel"), "c_proj": ("moe", "c_proj", "kernel"),
    "ln_1": ("ln_1", "weight"), "ln_2": ("ln_2", "weight"),
}  # ours -> the path inside a block of the program (models/lfm2_moe.py)


def unrolled_program_tree(weights: dict, cfg: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (models/lfm2_moe.py)."""
    transformer: dict = {"wte": {"embedding": weights["outer"]["wte"]}, "ln_f": {"weight": weights["outer"]["ln_f"]}}
    for i, p in enumerate(weights["layers"]):
        block: dict = {}
        for name, leaf in p.items():
            node = block
            *parents, last = _OPERATOR_LEAVES[name]
            for key in parents:
                node = node.setdefault(key, {})
            node[last] = leaf
        transformer[f"h_{i}"] = block
    return {"transformer": transformer}


def leaves_by_name(tree: dict) -> dict:
    """{"wte": x, "layer0.in_proj": x, ...} from a tree in the program's layout."""
    t = tree["transformer"]
    out = {"wte": t["wte"]["embedding"], "ln_f": t["ln_f"]["weight"]}
    for key, block in t.items():
        if not key.startswith("h_"):
            continue
        for name, path in _OPERATOR_LEAVES.items():
            node = block
            for part in path:
                node = node.get(part) if isinstance(node, dict) else None
                if node is None:
                    break
            if node is not None:
                out[f"layer{key[2:]}.{name}"] = node
    return out
