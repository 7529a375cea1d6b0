"""Plain reference of the `nemotron_h` tower (Nemotron-H / Nemotron-Labs-TwoTower's first
tower), given one chip's share of the experts and of the vocabulary.

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``: no
kernels, no chunked scan, no grouped products, no packing beyond the segment test. It
imports nothing of the program; its weights come from ``benchmark.weights_nemotron_h`` (the
seed). The layers, as the public ``config.json`` (``model_type: nemotron_h``) defines them —
``u`` a layer's normed input, one packed row at a time:

  block   x <- x + mixer_l(RMSNorm(x)), the mixer by the pattern's letter; after the last
          block RMSNorm and the untied head. No position embedding anywhere.
  M       [z | xBC | dt] = u W_in; xBC <- silu(causal depthwise conv(xBC) + b), split into
          x [heads, P], B and C [groups, N]; dt <- softplus(dt + dt_bias);
          a_t = exp(-exp(A_log) dt_t); S_t = a_t S_{t-1} + dt_t x_t B_t^T, S zero before a
          document's first token; y_t = S_t C_t + D x_t — run TOKEN BY TOKEN; then the grouped
          RMSNorm of y * silu(z) with a weight, and W_out.
  *       q, k, v without bias or rotary, grouped-query causal attention inside a document.
  E       s = sigmoid(u W_r) over ALL experts; top-k of s + b (b the correction bias, a
          buffer: it takes no gradient and no update); w_i = scale s_i / (sum of the chosen
          s + 1e-20), the sum over all chosen, held here or not; y = sum over the chosen
          experts HELD HERE of w_i relu(u W_up_i)^2 W_down_i, plus the shared expert. What
          the absent experts would add is left out, as in the program (the chip's share).

Departures from a textbook forward, to fit a chip's memory, none changing a value:
attention one head at a time, every layer re-computed in the backward pass, and the
recurrence's backward re-computed a block of 128 tokens at a time.

``quant="fp8"`` is the control (see ``gpt_dense``): every linear layer — projections,
experts, the head — computed as an fp8 recipe computes; the router stays float32, as it
does in the program. ``router_input_dtype`` rounds the router's input as the program's
bfloat16 activations are rounded: the share of top-k choices that this alone moves is the
reference's own estimate of how often program and reference route a slot apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_nemotron_h as W
from .gpt_dense import attention, matmul, rmsnorm, segments_from_eos

RECURRENCE_BLOCK = 128  # tokens whose states the backward pass keeps at once


def causal_conv(x, weight, bias, segments):
    """y_t = b + sum_k w[:, k] x_{t-(K-1-k)}, taps of another document or before the row
    reading zero. x [T, C], weight [C, K]."""
    taps = weight.shape[-1]
    y = bias + x * weight[:, taps - 1]
    for back in range(1, taps):
        shifted = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
        earlier = jnp.concatenate([jnp.full((back,), -1, segments.dtype), segments[:-back]])
        y = y + jnp.where((earlier == segments)[:, None], shifted, 0.0) * weight[:, taps - 1 - back]
    return y


def selective_scan(x, dt, decay, b, c, first):
    """The recurrence, token by token. x [T, H, P]; dt, decay [T, H]; b, c [T, H, N] (already
    spread over the heads of their group); first [T] marks a document's first token."""
    length, heads, width = x.shape
    state = b.shape[-1]

    def token(s, inputs):
        x_t, dt_t, a_t, b_t, c_t, first_t = inputs
        s = jnp.where(first_t, 0.0, a_t)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    @jax.checkpoint
    def block(s, inputs):
        return jax.lax.scan(token, s, inputs, unroll=8)  # eight tokens an iteration of the loop: still one by one

    blocks = -(-length // RECURRENCE_BLOCK)
    pad = blocks * RECURRENCE_BLOCK - length

    def blocked(a):
        a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return a.reshape((blocks, RECURRENCE_BLOCK) + a.shape[1:])

    _, y = jax.lax.scan(
        block, jnp.zeros((heads, width, state), jnp.float32), tuple(map(blocked, (x, dt, decay, b, c, first)))
    )
    return y.reshape((blocks * RECURRENCE_BLOCK, heads, width))[:length]


def mamba_mixer(m, p, u, segments, quant=None):
    seq = u.shape[0]
    heads, width, groups, state, inner = m["m_heads"], m["m_width"], m["m_groups"], m["m_state"], m["m_inner"]
    z, xbc, dt = jnp.split(matmul(u, p["in_proj"], quant), [inner, inner + m["conv_dim"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_weight"], p["conv_bias"], segments))
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    x = x.reshape(seq, heads, width)
    per_group = heads // groups
    b = jnp.repeat(b.reshape(seq, groups, state), per_group, axis=1)
    c = jnp.repeat(c.reshape(seq, groups, state), per_group, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    decay = jnp.exp(-jnp.exp(p["A_log"]) * dt)
    first = jnp.concatenate([jnp.ones((1,), bool), segments[1:] != segments[:-1]])
    y = selective_scan(x, dt, decay, b, c, first) + p["D"][:, None] * x
    y = y.reshape(seq, inner) * jax.nn.silu(z)
    grouped = y.reshape(seq, groups, inner // groups)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + m["eps"])
    return matmul(grouped.reshape(seq, inner) * p["norm_weight"], p["out_proj"], quant)


def attention_mixer(m, p, u, segments, quant=None):
    seq = u.shape[0]
    nq, nkv = m["n_head"] * m["head_dim"], m["n_kv"] * m["head_dim"]
    qkv = matmul(u, p["c_attn"], quant)
    q = qkv[:, :nq].reshape(seq, m["n_head"], m["head_dim"])
    k = qkv[:, nq : nq + nkv].reshape(seq, m["n_kv"], m["head_dim"])
    v = qkv[:, nq + nkv :].reshape(seq, m["n_kv"], m["head_dim"])
    return matmul(attention(q, k, v, segments).reshape(seq, nq), p["attn_c_proj"], quant)


def route(m, p, u):
    """(weights [T, k], chosen experts [T, k]) over ALL the router's experts."""
    scores = jax.nn.sigmoid(jnp.dot(u, p["gate"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), m["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return m["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20), chosen


def relu2(h):
    return jnp.square(jax.nn.relu(h))


def experts_mixer(m, p, u, quant=None):
    """The chip's share: experts ``first_expert .. first_expert + held - 1`` of the router's
    ``experts``, each run over every token and weighed by the router (zero where the token
    did not choose it), plus the shared expert."""
    weights, chosen = route(m, p, u)
    combine = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(chosen, m["experts"], dtype=weights.dtype))
    combine = combine[:, m["first_expert"] : m["first_expert"] + m["held"]]

    @jax.checkpoint
    def one_expert(w_up, w_down, gate):
        return matmul(relu2(matmul(u, w_up, quant)), w_down, quant) * gate[:, None]

    out = jnp.zeros_like(u)
    for e in range(m["held"]):
        out = out + one_expert(p["c_fc"][e], p["c_proj"][e], combine[:, e])
    return out + matmul(relu2(matmul(u, p["shared_c_fc"], quant)), p["shared_c_proj"], quant)


def layer(m, kind, p, h, segments, quant=None):
    u = rmsnorm(h, p["ln_1"], m["eps"])
    if kind == "M":
        return h + mamba_mixer(m, p, u, segments, quant)
    if kind == "E":
        return h + experts_mixer(m, p, u, quant)
    return h + attention_mixer(m, p, u, segments, quant)


# ------------------------------------------------------------------------------ training


def router_facts(m, p, h, router_input_dtype=jnp.bfloat16):
    """Of one layer of experts on one row: the rows each held expert gets ([held]) and the
    count of top-k choices that differ when the router's input is rounded to
    ``router_input_dtype`` as the program's activations are. Nothing is differentiated."""
    u = rmsnorm(jax.lax.stop_gradient(h), p["ln_1"], m["eps"])
    _, chosen = route(m, p, u)
    _, rounded = route(m, p, u.astype(router_input_dtype).astype(jnp.float32))
    held = jax.nn.one_hot(chosen - m["first_expert"], m["held"], dtype=jnp.int32)
    in_both = jnp.sum(chosen[:, :, None] == rounded[:, None, :])
    return jnp.sum(held, axis=(0, 1)), chosen.size - in_both


def sequence_loss_terms(m, params, text, quant=None):
    """(sum of token losses, sum of logsumexp**2, count of valid labels, routing facts) of one
    packed row ``text`` of length T + 1; the facts are ``held_expert_rows`` [layers of experts,
    held] and ``moved`` [layers of experts] (`router_facts`)."""
    tokens, labels = text[:-1], text[1:]
    segments, _ = segments_from_eos(tokens, m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    valid = next_segments[1:] == segments  # a label across a document boundary is no label
    h = params["outer"]["wte"][tokens]
    facts = []
    for kind, p in zip(m["pattern"], params["layers"]):
        if kind == "E":
            facts.append(router_facts(m, p, h))
        h = jax.checkpoint(functools.partial(layer, m, kind, quant=quant))(p, h, segments)
    h = rmsnorm(h, params["outer"]["ln_f"], m["eps"])
    logits = matmul(h, params["outer"]["lm_head"].T, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    valid_f = valid.astype(jnp.float32)
    routing = {}
    if facts:
        routing = {"held_expert_rows": jnp.stack([f[0] for f in facts]), "moved": jnp.stack([f[1] for f in facts])}
    return jnp.sum((lse - picked) * valid_f), jnp.sum(jnp.square(lse) * valid_f), jnp.sum(valid_f), routing


def sequence_loss_terms_count(m, text):
    """The count of valid labels of one packed row (no parameter moves it)."""
    segments, _ = segments_from_eos(text[:-1], m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    return jnp.sum((next_segments[1:] == segments).astype(jnp.float32))


def leaf_norms(tree) -> dict:
    """{"wte": norm, "layer0.in_proj": norm, ...} of a tree in the benchmark's layout."""
    out = {name: jnp.sqrt(jnp.sum(jnp.square(a))) for name, a in tree["outer"].items()}
    for i, p in enumerate(tree["layers"]):
        out.update({f"layer{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a))) for name, a in p.items()})
    return out


def hold_buffers(update: dict, reference: dict) -> dict:
    """The router's correction bias is a buffer: whatever the optimizer would do to it, it
    stays (``reference``'s value)."""
    layers = [
        dict(p, e_score_correction_bias=r["e_score_correction_bias"]) if "e_score_correction_bias" in p else p
        for p, r in zip(update["layers"], reference["layers"])
    ]
    return {"outer": update["outer"], "layers": layers}


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None, params=None) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights (or
    ``params``, for the tests), loss and gradient of each batch ([rows, T + 1] int tokens),
    global-norm clipping, AdamW with the buffers held.

    To fit beside 14 bytes a parameter of float32 state on one chip, a batch's rows are
    differentiated one at a time (the batch's loss is a sum over rows divided by a count
    that no parameter moves, so its gradient is the rows' gradients added), and the two
    moments wait on the host while a gradient is computed. No value depends on either.

    Returns each step's loss, the per-leaf norms of the first gradient as the optimizer
    gets it (after clipping), the per-leaf norms of the parameters' change after the last
    step, and each step's routing facts (``held_expert_rows`` and ``moved_share``, a layer
    of experts each, over the batch's rows).
    """
    m = W.model_dims(cfg)
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    (b1, b2), eps = optimizer["betas"], optimizer["eps"]
    clip = optimizer["gradient_clipping"]

    @jax.jit
    def valid_labels(batch):
        return sum(sequence_loss_terms_count(m, row) for row in batch)

    @jax.jit
    def row_gradient(params, row, count):
        def scaled(p):
            loss_sum, z_sum, _, routing = sequence_loss_terms(m, p, row, quant)
            return (loss_sum + m["z_loss_coef"] * z_sum) / count, routing

        return jax.value_and_grad(scaled, has_aux=True)(params)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(params, grads, mu, nu, count):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / (norm + 1e-6)), grads)
        mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), nu, grads)
        c1, c2 = 1 - b1**count, 1 - b2**count
        new = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), params, mu, nu
        )
        return hold_buffers(new, params), mu, nu, leaf_norms(grads)

    with jax.default_matmul_precision("highest"):
        key = W.base_key(seed)
        init = jax.jit(lambda k: W.make_all(cfg, k, jnp.float32))
        start = (lambda: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)) if params is not None else (lambda: init(key))
        current = start()
        # the moments on the host between updates (numpy: zeros cost nothing until written)
        mu = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), current)
        nu = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), current)
        losses, first_grad, routing = [], None, []
        for step, batch in enumerate(batches):
            batch = jnp.asarray(batch)
            count = jnp.maximum(valid_labels(batch), 1.0)
            loss, grads, facts = 0.0, None, []
            for row in batch:
                (row_loss, row_facts), row_grads = row_gradient(current, row, count)
                loss += float(row_loss)
                facts.append(jax.device_get(row_facts))
                grads = row_grads if grads is None else add(grads, row_grads)
            if facts[0]:
                slots = (batch.shape[1] - 1) * m["top_k"] * len(facts)
                routing.append({
                    "held_expert_rows": sum(f["held_expert_rows"] for f in facts).tolist(),
                    "moved_share": (sum(f["moved"] for f in facts) / slots).tolist(),
                })
            current, mu, nu, grad_norms = update(
                current, grads, jax.device_put(mu), jax.device_put(nu), jnp.asarray(step + 1.0, jnp.float32)
            )
            mu, nu = jax.device_get((mu, nu))
            losses.append(loss)
            if first_grad is None:
                first_grad = {k: float(v) for k, v in grad_norms.items()}
        del mu, nu
        delta = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0)))(current, start())
        return dict(
            losses=losses,
            grad_norms=first_grad,
            delta_norms={k: float(v) for k, v in delta.items()},
            routing=routing,
        )
