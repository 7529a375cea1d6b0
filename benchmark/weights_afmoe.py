"""Seeded weights of `afmoe` (Trinity-Mini) in the benchmark's own layout, made on the device from
``--seed``; the program under test and the plain reference both get theirs from here.

    outer:     wte [V, d], lm_head [V, d] (untied), ln_f [d] ones
    layer i:   ln_1, ln_1_out, ln_2, ln_2_out [d] ones (each sub-layer's input and output norm);
               c_attn [d, (heads + 2 kv) head] ([Q | K | V]), g_proj [d, heads head] (the gate on
               attention's output), q_norm_weight, k_norm_weight [head] ones, attn_c_proj
               [heads head, d] — whatever the layer's kind: a window layer and a full layer hold
               the same leaves —; and by depth
      dense    (i < num_dense_layers) mlp_c_fc [d, 2 n_inner] ([up | gate]), mlp_c_proj [n_inner, d]
      experts  gate [d, E_all] (the router), e_score_correction_bias [E_all], c_fc [E_held, d, 2 f]
               ([up | gate]), c_proj [E_held, f, d], shared_c_fc [d, 2 f_shared], shared_c_proj
               [f_shared, d]

An expert's weights depend on the seed, the layer and the expert's own index among ALL the
router's experts, so the shares of a layer add up to it (tests/models/test_afmoe.py). Initial
values the public ``config.json`` does not give (``assumed`` in the configuration's file):
matrices normal(0, initializer_range), the residual out-projections (attn_c_proj, the MLP's, the
experts' and the shared expert's down) divided by sqrt(2 n_layer); the router's bias drawn
normal(0, 0.05) a layer (``make_layer``) and, in a whole model (``make_all``), set where the
family's balancing rule would rest on rows drawn under the corpus' law (``balanced_biases``),
and held there. It imports nothing of the program.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as _dense
from .traffic import lognormal_quantiles
from .weights import _normal

EXPERT_BIAS_STD = 0.05
NORMS = ("ln_1", "ln_1_out", "ln_2", "ln_2_out")

_LAST_SEED = [None]  # the whole number ``base_key`` was last asked for


def base_key(seed: int) -> jax.Array:
    """``weights.base_key``, and the seed remembered: a caller hands ``make_all`` this key as
    the argument of its own jit, and a traced key does not say which seed's balanced biases
    (``balanced_biases``, kept by the seed) go with it. Make the key, then the weights."""
    _LAST_SEED[0] = seed
    return _dense.base_key(seed)


def _key(seed_or_key):
    return base_key(seed_or_key) if isinstance(seed_or_key, int) else seed_or_key


def model_dims(cfg: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file's ``pretrained_config``."""
    first, held = cfg.get("experts_held") or (0, cfg["num_experts"])
    window = cfg.get("sliding_window", 2048)
    return dict(
        vocab=cfg["vocab_size"], d=cfg["n_embd"], n_layer=cfg["n_layer"], layer_types=tuple(cfg["layer_types"]),
        window=window, windows=tuple(window if kind == "sliding_attention" else None for kind in cfg["layer_types"]),
        dense_layers=cfg.get("num_dense_layers", 2),
        n_head=cfg["n_head"], n_kv=cfg["num_key_value_heads"], head_dim=cfg.get("attention_head_dim") or cfg["n_embd"] // cfg["n_head"],
        rope_theta=cfg.get("rope_theta", 10000), n_inner=cfg["n_inner"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"], first_expert=first, held=held,
        f=cfg["moe_intermediate_size"], f_shared=cfg.get("num_shared_experts", 1) * cfg["moe_intermediate_size"],
        scale=cfg.get("route_scale", 2.826), route_epsilon=cfg.get("route_norm_epsilon", 1e-20),
        embedding_multiplier=math.sqrt(cfg["n_embd"]) if cfg.get("mup_enabled", True) else 1.0,
        std=cfg.get("initializer_range", 0.02), eps=cfg.get("layer_norm_epsilon", 1e-5),
        eos=cfg.get("eos_token_id", 0), z_loss_coef=cfg.get("z_loss_coef", 0.0),
    )


def layer_kinds(cfg: dict) -> list:
    """(attention, feed-forward) a layer: ``("sliding_attention" | "full_attention", "dense" | "experts")``."""
    m = model_dims(cfg)
    return [(kind, "dense" if i < m["dense_layers"] else "experts") for i, kind in enumerate(m["layer_types"])]


def make_layer(cfg: dict, seed, index: int, dtype=jnp.float32) -> dict:
    """Layer ``index`` (a Python int). ``seed`` is the whole number or ``base_key(seed)``."""
    m = model_dims(cfg)
    _, feed_forward = layer_kinds(cfg)[index]
    keys = jax.random.split(jax.random.fold_in(_key(seed), index + 1), 10)
    proj_std = m["std"] / math.sqrt(2 * m["n_layer"])
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    d, heads, kv, head = m["d"], m["n_head"], m["n_kv"], m["head_dim"]
    layer = {name: ones(d) for name in NORMS}
    layer.update(
        c_attn=_normal(keys[0], (d, (heads + 2 * kv) * head), m["std"], dtype),
        g_proj=_normal(keys[1], (d, heads * head), m["std"], dtype),
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
        # one draw an expert, keyed by its index among ALL experts, in a loop the compiler sees
        # once (`lax.map`: `weights_lfm2_moe.make_layer` says why neither vmap nor a Python loop)
        ids = jnp.arange(m["first_expert"], m["first_expert"] + m["held"])
        return jax.lax.map(lambda e: _normal(jax.random.fold_in(base, e), shape, std, dtype), ids)

    layer.update(
        gate=_normal(keys[3], (d, m["experts"]), m["std"], dtype),
        e_score_correction_bias=_normal(keys[4], (m["experts"],), EXPERT_BIAS_STD, dtype),
        c_fc=bank(keys[5], (d, 2 * m["f"]), m["std"]),
        c_proj=bank(keys[6], (m["f"], d), proj_std),
        shared_c_fc=_normal(keys[7], (d, 2 * m["f_shared"]), m["std"], dtype),
        shared_c_proj=_normal(keys[8], (m["f_shared"], d), proj_std, dtype),
    )
    return layer


def make_outer(cfg: dict, seed, dtype=jnp.float32) -> dict:
    m = model_dims(cfg)
    keys = jax.random.split(jax.random.fold_in(_key(seed), 0), 2)
    return {
        "wte": _normal(keys[0], (m["vocab"], m["d"]), m["std"], dtype),
        "lm_head": _normal(keys[1], (m["vocab"], m["d"]), m["std"], dtype),
        "ln_f": jnp.ones((m["d"],), dtype),
    }


def make_drawn(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """Every weight as the seed draws it, the routers' biases too: ``make_all`` before it balances."""
    key = _key(seed)
    return {
        "outer": make_outer(cfg, key, dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(model_dims(cfg)["n_layer"])],
    }


def make_all(cfg: dict, seed, dtype=jnp.float32) -> dict:
    """Every weight as ``{"outer": {...}, "layers": [{...}, ...]}``, the routers' biases where
    ``balanced_biases`` puts them; call it under one jit, with ``base_key``'s key."""
    weights = make_drawn(cfg, seed, dtype)
    with_experts = [layer for layer in weights["layers"] if "gate" in layer]
    if with_experts:
        number = seed if isinstance(seed, int) else _LAST_SEED[0]
        for layer, bias in zip(with_experts, balanced_biases(cfg, number)):
            layer["e_score_correction_bias"] = jnp.asarray(bias, layer["e_score_correction_bias"].dtype)
    return weights


# ---------------------------------------------------------------- the routers' biases, balanced
#
# Fresh routers are not even. A token's scores are those of its embedding (under the Zipf law
# one token is a tenth of the corpus) plus what attention's mean over thousands of keys adds to
# every token alike, so a layer's hot experts take most rows, and the 8 held here do or do not
# include one: by seed a layer held 2,497-35,492 rows of the even 16,384 (my chip runs, PR 40),
# the step's time followed them (3% between seeds) and a layer left with 3,000 rows put the
# few slots that bfloat16 rounding moves over the comparison's limits. A deployment's routers
# are kept even by the family's rule for the bias (it rises while an expert gets less than the
# even load and falls while it gets more), which is not built. Where that rule rests is
# written down here instead: an expert's bias is minus the score that the even share of the
# calibration rows' tokens exceeds, so every expert wants the same number of tokens. A
# quantile moves as little as the scores do, so a last bit that differs between two machines
# or two compilations moves no choice (a rule iterated over loads, counted in whole rows,
# turns that bit into another bias); within a process the numbers are computed once
# (``balanced_biases``) and the program and the reference share them.

CALIBRATION_ROWS = 4  # of n_positions tokens each: nine documents at the cell's sizes


def calibration_documents(rows: int, seq: int) -> np.ndarray:
    """[rows, seq] bool, True where a document ends: lengths at the stratified quantiles of the
    cell's law relative to the row (lognormal about a quarter of it, sigma 1, from 1/256 of it
    to the whole: median 4096, 64 to 16384 at 16384), in one fixed order, laid end to end."""
    median = seq / 4
    count = max(int(rows * seq / (median * math.exp(0.5))), 1)
    lengths = lognormal_quantiles(count, median, 1.0, max(seq // 256, 1), seq)
    ends = np.cumsum(np.random.default_rng(0).permutation(lengths) + 1) - 1
    mask = np.zeros(rows * seq, bool)
    mask[ends[ends < rows * seq]] = True
    return mask.reshape(rows, seq)


def calibration_rows(cfg: dict, key) -> jax.Array:
    """[rows, n_positions] tokens under the corpus' law (``benchmark/traffic.write_packed_corpus``:
    rank r of the vocabulary with probability 1 / r, token 0 eos at the end of a document)."""
    m, seq = model_dims(cfg), cfg["n_positions"]
    cumulative = jnp.cumsum(1.0 / jnp.arange(1, m["vocab"], dtype=jnp.float32))
    drawn = jnp.searchsorted(cumulative, jax.random.uniform(key, (CALIBRATION_ROWS, seq)) * cumulative[-1])
    tokens = 1 + jnp.minimum(drawn, m["vocab"] - 2).astype(jnp.int32)
    return jnp.where(calibration_documents(CALIBRATION_ROWS, seq), m["eos"], tokens)


def balanced_bias(scores, top_k: int):
    """[experts]: minus each expert's score at the even share of ``scores`` [tokens, experts]."""
    tokens, experts = scores.shape
    share = max(tokens * top_k // experts, 1)
    return -jnp.sort(scores, axis=0)[tokens - share]


def calibrated_biases(cfg: dict, weights: dict, key) -> jax.Array:
    """[layers of experts, experts] float32: every router's bias at ``balanced_bias`` of the
    calibration rows' scores. The rows run through the plain reference's blocks in float32 (the
    chip's share of the experts, as the model is run); a layer's bias is set before the rows go
    through its experts, so the next layer is balanced on what this one gives."""
    from .reference import afmoe as reference  # here: the reference imports this module
    from .reference.gpt_dense import segments_from_eos

    m = model_dims(cfg)
    last = max(i for i, p in enumerate(weights["layers"]) if "gate" in p)
    biases = []
    with jax.default_matmul_precision("highest"):
        rows = calibration_rows(cfg, jax.random.fold_in(key, m["n_layer"] + 1))
        places = jax.vmap(lambda row: jnp.stack(segments_from_eos(row, m["eos"])))(rows)  # [rows, (segments, positions), T]
        x = m["embedding_multiplier"] * weights["outer"]["wte"][rows].astype(jnp.float32)
        for i, (window, rotate) in enumerate(reference.layer_masks(m)[: last + 1]):
            p = jax.tree.map(lambda leaf: leaf.astype(jnp.float32), weights["layers"][i])
            a, u = jax.lax.map(lambda row: reference.attention_half(m, p, row[0], row[1][1], row[1][0], window, rotate), (x, places))
            if "gate" in p:
                p["e_score_correction_bias"] = balanced_bias(jax.nn.sigmoid(jnp.dot(u.reshape(-1, u.shape[-1]), p["gate"])), m["top_k"])
                biases.append(p["e_score_correction_bias"])
            if i < last:
                x = jax.lax.map(lambda row: reference.feed_forward_half(m, p, *row), (a, u))
    return jnp.stack(biases)


_BIASES: dict = {}  # (the configuration, the seed) -> calibrated_biases, on the host


def balanced_biases(cfg: dict, seed: int) -> np.ndarray:
    """``calibrated_biases`` of the seed's own weights, computed once a process in a program of
    its own and kept: the program under test and the reference get the same numbers to the last
    bit, and the program that makes the weights (the driver runs it again after the checked
    steps, beside the train state) stays the few draws it was — the calibration's 3.8 GiB of
    temporaries and 102 MiB of code (compiled for a described v5e) are gone before the first
    step. Kept beside the train step, that code cost one step in two runs a stall of 1.0-1.8 s
    (my chip runs, PR 40: three of six runs, none in sixteen before it)."""
    if seed is None:
        raise ValueError("make_all got a key that base_key did not make: the balanced biases are kept by the seed")
    kept = (json.dumps(cfg, sort_keys=True, default=str), seed)
    if kept not in _BIASES:
        program = jax.jit(lambda key: calibrated_biases(cfg, make_drawn(cfg, key), key))
        with jax.ensure_compile_time_eval():  # run now, whoever's program is being traced around this call
            _BIASES[kept] = np.asarray(program(_dense.base_key(seed)))
        program.clear_cache()  # 102 MiB of code on a v5e: not beside the train step
    return _BIASES[kept]


def count_parameters(cfg: dict) -> dict:
    """Parameter counts from the shapes: the matmul parameters of each part (the routed banks
    one expert at a time) and the total of everything held here."""
    m = model_dims(cfg)
    d, heads, kv, head = m["d"], m["n_head"], m["n_kv"], m["head_dim"]
    qkv, gate, out = d * (heads + 2 * kv) * head, d * heads * head, heads * head * d
    attention_matmul = qkv + gate + out
    attention = attention_matmul + 2 * head
    dense_mlp = 3 * d * m["n_inner"]
    routed_expert = 3 * d * m["f"]
    shared_expert = 3 * d * m["f_shared"]
    router = d * m["experts"]
    experts_layer = router + m["experts"] + shared_expert + m["held"] * routed_expert
    norms = 4 * d
    kinds = layer_kinds(cfg)
    feed_forwards = {"dense": dense_mlp, "experts": experts_layer}
    blocks = [attention + feed_forwards[f] + norms for _, f in kinds]
    tables = 2 * m["vocab"] * d
    return dict(
        qkv=qkv, gate=gate, out=out, attention_matmul=attention_matmul, attention=attention, dense_mlp=dense_mlp,
        routed_expert=routed_expert, shared_expert=shared_expert, router=router, experts_layer=experts_layer,
        blocks=blocks, tables=tables,
        layers_of_kind={
            "sliding_attention": sum(a == "sliding_attention" for a, _ in kinds), "full_attention": sum(a == "full_attention" for a, _ in kinds),
            "dense": sum(f == "dense" for _, f in kinds), "experts": sum(f == "experts" for _, f in kinds),
        },
        total=sum(blocks) + tables + d,
    )


# ---------------------------------------------------------------- the program's layout

_BLOCK_LEAVES = {
    **{name: (name, "weight") for name in NORMS},
    "c_attn": ("attn", "c_attn", "kernel"), "g_proj": ("attn", "g_proj", "kernel"),
    "q_norm_weight": ("attn", "q_norm_weight"), "k_norm_weight": ("attn", "k_norm_weight"),
    "attn_c_proj": ("attn", "c_proj", "kernel"),
    "mlp_c_fc": ("mlp", "c_fc", "kernel"), "mlp_c_proj": ("mlp", "c_proj", "kernel"),
    "gate": ("moe", "gate"), "e_score_correction_bias": ("moe", "e_score_correction_bias"),
    "c_fc": ("moe", "c_fc", "kernel"), "c_proj": ("moe", "c_proj", "kernel"),
    "shared_c_fc": ("moe", "shared_c_fc", "kernel"), "shared_c_proj": ("moe", "shared_c_proj", "kernel"),
}  # ours -> the path inside a block of the program (models/afmoe.py)


def unrolled_program_tree(weights: dict, cfg: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (models/afmoe.py)."""
    transformer: dict = {"wte": {"embedding": weights["outer"]["wte"]}, "ln_f": {"weight": weights["outer"]["ln_f"]}}
    for i, p in enumerate(weights["layers"]):
        block: dict = {}
        for name, leaf in p.items():
            node = block
            *parents, last = _BLOCK_LEAVES[name]
            for key in parents:
                node = node.setdefault(key, {})
            node[last] = leaf
        transformer[f"h_{i}"] = block
    return {"transformer": transformer, "lm_head": {"kernel": weights["outer"]["lm_head"]}}


def leaves_by_name(tree: dict) -> dict:
    """{"wte": x, "lm_head": x, "layer0.c_attn": x, ...} from a tree in the program's layout."""
    t = tree["transformer"]
    out = {"wte": t["wte"]["embedding"], "lm_head": tree["lm_head"]["kernel"], "ln_f": t["ln_f"]["weight"]}
    for key, block in t.items():
        if not key.startswith("h_"):
            continue
        for name, path in _BLOCK_LEAVES.items():
            node = block
            for part in path:
                node = node.get(part) if isinstance(node, dict) else None
                if node is None:
                    break
            if node is not None:
                out[f"layer{key[2:]}.{name}"] = node
    return out
