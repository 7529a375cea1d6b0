"""Plain reference of the dense GPT (gpt_dolomite with rmsnorm, rope, swiglu, a tied head).

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no kernels, no cache, no packing beyond the segment mask. It imports nothing of the
program; its weights come from ``benchmark.weights`` (the seed), never from the program.
Departures from a textbook forward, all to fit a chip's memory and none changing a value:
attention runs one head at a time (``lax.map``) and each layer is re-computed in the
backward pass (``jax.checkpoint``).

``quant="fp8"`` is the *control*: the same arithmetic with every linear layer (the head
too) computed as an fp8 recipe does — both operands of the forward matmul rounded to e4m3,
the incoming gradient of both backward matmuls to e5m2, each tensor on its absmax scale.
It is the step in precision below the bfloat16 that the configurations state, and the one
the program itself offers (``mixed_precision_args.dtype: fp8``), so the one that would
tempt a later PR. (An int8 forward with per-row scales was read first, PR 23: it moves a
leaf's gradient norm by less than twice what bfloat16 does, so no limit could tell them
apart.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import weights as W

IGNORE = -100


E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _qdq(x, dtype, largest):
    """Scale a tensor's absmax to the format's largest value, round to the format, scale back."""
    scale = jnp.max(jnp.abs(x)) / largest
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    return jnp.dot(_qdq(x, jnp.float8_e4m3fn, E4M3_MAX), _qdq(w, jnp.float8_e4m3fn, E4M3_MAX))


def _fp8_matmul_fwd(x, w):
    xq, wq = _qdq(x, jnp.float8_e4m3fn, E4M3_MAX), _qdq(w, jnp.float8_e4m3fn, E4M3_MAX)
    return jnp.dot(xq, wq), (xq, wq)


def _fp8_matmul_bwd(saved, dy):
    xq, wq = saved
    dyq = _qdq(dy, jnp.float8_e5m2, E5M2_MAX)
    return jnp.dot(dyq, wq.T), jnp.dot(xq.T, dyq)


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def matmul(x, w, quant=None):
    """x: [rows, k], w: [k, n]."""
    if quant == "fp8":
        return _fp8_matmul(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.dot(x, w)


def rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x: [T, heads, head_dim]; rotate-half (NeoX) rotary embedding."""
    head_dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = positions[:, None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(emb)


def segments_from_eos(tokens, eos):
    """Packed documents: a segment ends after each eos; positions restart with a segment."""
    is_eos = tokens == eos
    shifted = jnp.concatenate([jnp.zeros_like(is_eos[:1]), is_eos[:-1]])
    segments = jnp.cumsum(shifted.astype(jnp.int32)) + 1
    index = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    starts = jax.lax.cummax(jnp.where(shifted, index, 0))
    return segments, index - starts


def attention(q, k, v, segments):
    """Causal attention inside a segment. q: [T, H, hd]; k, v: [T, KV, hd]; query head h
    reads K/V head h // (H // KV)."""
    seq, n_head, head_dim = q.shape
    group = n_head // k.shape[1]
    index = jnp.arange(seq)
    mask = (index[:, None] >= index[None, :]) & (segments[:, None] == segments[None, :])
    qt, kt, vt = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))

    @jax.checkpoint
    def one_head(h):
        scores = jnp.dot(qt[h], kt[h // group].T) * head_dim**-0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.dot(probs, vt[h // group])

    return jnp.swapaxes(jax.lax.map(one_head, jnp.arange(n_head)), 0, 1)  # [T, H, hd]


def layer(m, p, h, positions, segments, quant=None):
    """One pre-norm block on one sequence. h: [T, d]."""
    seq = h.shape[0]
    x = rmsnorm(h, p["ln_1"], m["eps"])
    qkv = matmul(x, p["c_attn"], quant)
    nq, nkv = m["n_head"] * m["head_dim"], m["n_kv"] * m["head_dim"]
    q = rope(qkv[:, :nq].reshape(seq, m["n_head"], m["head_dim"]), positions, m["rope_theta"])
    k = rope(qkv[:, nq : nq + nkv].reshape(seq, m["n_kv"], m["head_dim"]), positions, m["rope_theta"])
    v = qkv[:, nq + nkv :].reshape(seq, m["n_kv"], m["head_dim"])
    h = h + matmul(attention(q, k, v, segments).reshape(seq, nq), p["attn_c_proj"], quant)
    x = rmsnorm(h, p["ln_2"], m["eps"])
    up, gate = jnp.split(matmul(x, p["c_fc"], quant), 2, axis=-1)
    return h + matmul(up * jax.nn.silu(gate), p["mlp_c_proj"], quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ------------------------------------------------------------------------------ training


def sequence_loss_terms(m, params, text, quant=None):
    """(sum of token losses, sum of logsumexp**2, count of valid labels) of one packed row
    ``text`` of length T + 1."""
    tokens, labels = text[:-1], text[1:]
    segments, positions = segments_from_eos(tokens, m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    valid = next_segments[1:] == segments  # a label across a document boundary is no label
    h = params["outer"]["wte"][tokens]
    block = jax.checkpoint(functools.partial(layer, m, quant=quant))
    for p in params["layers"]:
        h = block(p, h, positions, segments)
    h = rmsnorm(h, params["outer"]["ln_f"], m["eps"])
    logits = matmul(h, params["outer"]["wte"].T, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    valid_f = valid.astype(jnp.float32)
    return jnp.sum((lse - picked) * valid_f), jnp.sum(jnp.square(lse) * valid_f), jnp.sum(valid_f)


def batch_loss(m, params, batch, quant=None):
    """Mean loss over the valid labels of all rows, with the z-loss the config states."""
    terms = [sequence_loss_terms(m, params, row, quant) for row in batch]
    loss_sum, z_sum, count = (sum(t[i] for t in terms) for i in range(3))
    count = jnp.maximum(count, 1.0)
    return loss_sum / count + m["z_loss_coef"] * z_sum / count


def leaf_norms(tree) -> dict:
    """{"wte": norm, "ln_f": norm, "layer0.c_attn": norm, ...} of a tree in the benchmark's
    layout."""
    out = {name: jnp.sqrt(jnp.sum(jnp.square(a))) for name, a in tree["outer"].items()}
    for i, p in enumerate(tree["layers"]):
        out.update({f"layer{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a))) for name, a in p.items()})
    return out


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights, loss and
    gradient of each batch ([rows, T + 1] int tokens), global-norm clipping, AdamW.

    Returns each step's loss, the per-leaf norms of the first gradient as the optimizer
    gets it (after clipping) and the per-leaf norms of the parameters' change after the
    last step.
    """
    m = W.model_dims(cfg)
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    (b1, b2), eps = optimizer["betas"], optimizer["eps"]
    clip = optimizer["gradient_clipping"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, batch):
        loss, grads = jax.value_and_grad(lambda p: batch_loss(m, p, batch, quant))(params)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / (norm + 1e-6)), grads)
        count = count + 1
        mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), nu, grads)
        c1, c2 = 1 - b1**count, 1 - b2**count
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), params, mu, nu
        )
        return params, mu, nu, count, loss, leaf_norms(grads)

    with jax.default_matmul_precision("highest"):
        key = W.base_key(seed)
        init = jax.jit(lambda k: W.make_all(cfg, k, jnp.float32))
        params = init(key)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        losses, first_grad = [], None
        for batch in batches:
            params, mu, nu, count, loss, grad_norms = step(params, mu, nu, count, jnp.asarray(batch))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: float(v) for k, v in grad_norms.items()}
        delta = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0)))(params, init(key))
        return dict(
            losses=losses,
            grad_norms=first_grad,
            delta_norms={k: float(v) for k, v in delta.items()},
        )


# ------------------------------------------------------------------------------- serving


def served_token_gaps(cfg: dict, seed: int, sequences, dtype=jnp.bfloat16, bucket: int = 512, control: bool = False):
    """For each served request ``(prompt_ids, served_ids)``: one full forward over prompt +
    served tokens, layer by layer (each layer's weights are remade from the seed and
    freed), then at every position that produced a served token the gap by which that
    token's logit lies below the row's best, in units of the row's standard deviation (the
    spread of a row of logits grows with the width, a share of it does not). Returns a list of dicts with, per request,
    ``gap`` (the reference, float32, on the weights as served in ``dtype``) and
    with ``control=True`` ``control_gap`` (the gap of the token the fp8 control puts
    first), each an array with one entry per served token.
    """
    import numpy as np

    m = W.model_dims(cfg)

    def embed(outer, tokens):
        return outer["wte"][tokens]

    def layer_step(weights, h, positions, segments, quant):
        return layer(m, weights, h, positions, segments, quant)

    def head(outer, h, served, quant):
        logits = matmul(rmsnorm(h, outer["ln_f"], m["eps"]), outer["wte"].T, quant)
        best = jnp.max(logits, axis=-1)
        return logits, (best - jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]) / jnp.std(logits, axis=-1)

    embed_j = jax.jit(embed)
    layer_j = jax.jit(layer_step, static_argnames="quant")
    head_j = jax.jit(head, static_argnames="quant")

    quants = (None, "fp8") if control else (None,)
    with jax.default_matmul_precision("highest"):
        key = W.base_key(seed)
        outer = _f32(jax.jit(lambda k: W.make_outer(cfg, k, dtype))(key))
        prepared = []
        for prompt, served in sequences:
            total = len(prompt) + len(served)
            width = -(-total // bucket) * bucket
            tokens = np.zeros(width, np.int32)
            tokens[:total] = list(prompt) + list(served)
            prepared.append(
                dict(
                    # the right padding is its own segment: causal attention hides it anyway
                    segments=jnp.asarray((np.arange(width) >= total).astype(np.int32) + 1),
                    positions=jnp.arange(width, dtype=jnp.int32),
                    served=jnp.asarray(np.asarray(served, np.int32)),
                    rows=slice(len(prompt) - 1, total - 1),
                    hidden={q: embed_j(outer, jnp.asarray(tokens)) for q in quants},
                )
            )
        make_j = jax.jit(lambda k, index: _f32(W.make_layer(cfg, k, index, dtype)))
        for index in range(m["n_layer"]):  # a layer's weights are made once, for every request
            weights = make_j(key, jnp.asarray(index, jnp.int32))
            for item in prepared:
                for quant in quants:
                    item["hidden"][quant] = layer_j(weights, item["hidden"][quant], item["positions"], item["segments"], quant)
        out = []
        for item in prepared:
            logits_ref, gap = head_j(outer, item["hidden"][None][item["rows"]], item["served"], None)
            gaps = {"gap": np.asarray(gap, np.float64)}
            if control:
                logits, _ = head_j(outer, item["hidden"]["fp8"][item["rows"]], item["served"], "fp8")
                first = jnp.argmax(logits, axis=-1)
                picked = jnp.take_along_axis(logits_ref, first[:, None], axis=-1)[:, 0]
                spread = jnp.std(logits_ref, axis=-1)
                gaps["control_gap"] = np.asarray((jnp.max(logits_ref, axis=-1) - picked) / spread, np.float64)
            out.append(gaps)
    return out


def forward_logits(cfg: dict, params: dict, tokens, quant=None):
    """Logits of one unpacked sequence from weights held whole (tests, small sizes)."""
    m = W.model_dims(cfg)
    tokens = jnp.asarray(tokens)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    segments = jnp.ones_like(positions)
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        h = params["outer"]["wte"][tokens]
        for p in params["layers"]:
            h = layer(m, p, h, positions, segments, quant)
        return matmul(rmsnorm(h, params["outer"]["ln_f"], m["eps"]), params["outer"]["wte"].T, quant)
