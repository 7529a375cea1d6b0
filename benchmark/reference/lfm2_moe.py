"""Plain reference of `lfm2_moe` (LFM2-24B-A2B), given one chip's share of the experts and of
the vocabulary.

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``: no
kernels, no grouped products, no packing beyond the segment test. It imports nothing of the
program; its weights come from ``benchmark.weights_lfm2_moe`` (the seed). ``x`` a row of hidden
states, ``N`` an RMSNorm with a weight, eps 1e-5, one packed row at a time:

  block l   a = x + Op_l(N1(x)); y = a + F_l(N2(a)).
  conv      [B | C | u] = W_in h; z = B * u; c_t = w[:, 0] z_{t-2} + w[:, 1] z_{t-1} + w[:, 2] z_t
            (three shifted products; a z before the row's start or in another document is zero);
            Op(h) = W_out (C * c). No activation, no bias.
  attention [q | k | v] = W_qkv h in heads of 64, 32 query heads over 8 key/value heads;
            q <- N_q(q), k <- N_k(k) per head over its columns (eps as the block norms'), THEN
            rope over the whole head by halves at rope_theta, positions counted from each
            document's start; o_h = softmax(q_h k_g^T / sqrt(head) + causal, same-document mask)
            v_g with g = h // 4; Op(h) = W_o concat(o_h). No bias.
  F_l       l < num_dense_layers: W_2 (silu(W_1 u) * W_3 u). Later: s = sigmoid(W_g u) over ALL
            experts; the top-k of s + b are chosen (b the expert bias, a buffer: no gradient, no
            update); w_i = scale s_i / (sum of the chosen s + 1e-6); F(u) = sum over the chosen
            experts HELD HERE of w_i E_i(u), every E a SwiGLU MLP. What the absent experts would
            add is left out. No shared expert.
  loss      mean cross-entropy of the next token inside its document, over the vocabulary rows
            held, the head being the embedding's table (tied), with the trainer's z-loss.

Departures from the public description, none changing a value (each also under ``assumed`` in
the configuration's file): the embedding is tied to the head (the config has no key; the
family's convention); the head dimension is hidden / heads; the QK norms' eps is norm_eps; the
router's scores are float32 (everything here is); attention runs one head at a time and every
block is re-computed in the backward pass; the held experts run over every token one after the
other (a `lax.scan`, PR 30's lesson on compile time) and are weighed by the router (zero where
the token did not choose one).

``quant="fp8"`` is the control (see ``gpt_dense``): every linear layer — the operators'
projections, the MLP, the experts, the head — computed as an fp8 recipe computes; the router
and the convolution's taps and gates stay float32, as they do in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_lfm2_moe as W
from .gpt_dense import attention, matmul, rmsnorm, rope, segments_from_eos
from .joyai_flash import swiglu  # W_down (up * silu(gate)) on [up | gate] banks: one for both gated references
from .nemotron_h_tower import hold_buffers, leaf_norms


def short_conv(m, p, u, segments, quant=None):
    """The gated short convolution on one row: ``u`` [T, d], ``segments`` [T]."""
    gate_in, gate_out, x = jnp.split(matmul(u, p["in_proj"], quant), 3, axis=-1)
    z = gate_in * x
    taps = p["conv_weight"].shape[-1]
    c = z * p["conv_weight"][:, taps - 1]
    for back in range(1, taps):
        earlier = jnp.concatenate([jnp.zeros_like(z[:back]), z[:-back]])
        same = jnp.concatenate([jnp.zeros((back,), bool), segments[back:] == segments[:-back]])
        c = c + jnp.where(same[:, None], earlier, 0.0) * p["conv_weight"][:, taps - 1 - back]
    return matmul(gate_out * c, p["out_proj"], quant)


def qk_norm_attention(m, p, u, positions, segments, quant=None):
    seq, heads, kv, head = u.shape[0], m["n_head"], m["n_kv"], m["head_dim"]
    qkv = matmul(u, p["c_attn"], quant)
    q = qkv[:, : heads * head].reshape(seq, heads, head)
    k = qkv[:, heads * head : (heads + kv) * head].reshape(seq, kv, head)
    v = qkv[:, (heads + kv) * head :].reshape(seq, kv, head)
    q = rope(rmsnorm(q, p["q_norm_weight"], m["eps"]), positions, m["rope_theta"])
    k = rope(rmsnorm(k, p["k_norm_weight"], m["eps"]), positions, m["rope_theta"])
    return matmul(attention(q, k, v, segments).reshape(seq, heads * head), p["attn_c_proj"], quant)


def route(m, p, u):
    """(weights [T, k], chosen experts [T, k]) over ALL the router's experts."""
    scores = jax.nn.sigmoid(jnp.dot(u, p["gate"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), m["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return m["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + m["route_epsilon"]), chosen


def experts(m, p, u, quant=None):
    """The chip's share: experts ``first_expert .. first_expert + held - 1`` of the router's
    ``experts``, and nothing beside them."""
    weights, chosen = route(m, p, u)
    combine = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(chosen, m["experts"], dtype=weights.dtype))
    combine = combine[:, m["first_expert"] : m["first_expert"] + m["held"]]

    @jax.checkpoint
    def one_expert(out, bank):
        w_up_gate, w_down, gate = bank
        return out + swiglu(u, w_up_gate, w_down, quant) * gate[:, None], None

    # every held expert over every token, one after the other (a loop the compiler sees once)
    held = m["held"]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), (p["c_fc"][:held], p["c_proj"][:held], combine.T))
    return out


def router_facts(m, p, u, router_input_dtype=jnp.bfloat16):
    """Of one layer of experts on one row: the rows each held expert gets ([held]) and the
    count of top-k choices that differ when the router's input ``u`` is rounded to
    ``router_input_dtype`` as the program's activations are. Nothing is differentiated."""
    u = jax.lax.stop_gradient(u)
    _, chosen = route(m, p, u)
    _, rounded = route(m, p, u.astype(router_input_dtype).astype(jnp.float32))
    held = jax.nn.one_hot(chosen - m["first_expert"], m["held"], dtype=jnp.int32)
    in_both = jnp.sum(chosen[:, :, None] == rounded[:, None, :])
    return jnp.sum(held, axis=(0, 1)), chosen.size - in_both


def block(m, p, x, positions, segments, quant=None):
    """(y, routing facts or None) of one block: the operator and the feed-forward its leaves name."""
    u = rmsnorm(x, p["ln_1"], m["eps"])
    if "in_proj" in p:
        a = x + short_conv(m, p, u, segments, quant)
    else:
        a = x + qk_norm_attention(m, p, u, positions, segments, quant)
    u = rmsnorm(a, p["ln_2"], m["eps"])
    if "mlp_c_fc" in p:
        return a + swiglu(u, p["mlp_c_fc"], p["mlp_c_proj"], quant), None
    return a + experts(m, p, u, quant), router_facts(m, p, u)


def sequence_loss_terms(m, params, text, quant=None):
    """(sum of token losses, sum of logsumexp**2, count of valid labels, routing facts) of one packed
    row ``text`` of length T + 1; the facts are ``held_expert_rows`` [layers of experts, held] and ``moved``
    [layers of experts]."""
    outer = params["outer"]
    tokens, labels = text[:-1], text[1:]
    segments, positions = segments_from_eos(tokens, m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    valid = (next_segments[1:] == segments).astype(jnp.float32)  # a label across a document boundary is no label
    run = lambda p, x: jax.checkpoint(functools.partial(block, m, quant=quant))(p, x, positions, segments)  # noqa: E731
    h, facts = outer["wte"][tokens], []
    for p in params["layers"]:
        h, layer_facts = run(p, h)
        if layer_facts is not None:
            facts.append(layer_facts)
    logits = matmul(rmsnorm(h, outer["ln_f"], m["eps"]), outer["wte"].T, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    routing = {}
    if facts:
        routing = {"held_expert_rows": jnp.stack([f[0] for f in facts]), "moved": jnp.stack([f[1] for f in facts])}
    return jnp.sum((lse - picked) * valid), jnp.sum(jnp.square(lse) * valid), jnp.sum(valid), routing


def forward_logits(cfg: dict, params: dict, tokens) -> jax.Array:
    """[T, V] logits of one row of tokens taken as its documents by eos (the tests)."""
    m = W.model_dims(cfg)
    segments, positions = segments_from_eos(tokens, m["eos"])
    h = params["outer"]["wte"][tokens]
    for p in params["layers"]:
        h, _ = block(m, p, h, positions, segments)
    return jnp.dot(rmsnorm(h, params["outer"]["ln_f"], m["eps"]), params["outer"]["wte"].T)


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None, params=None) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights (or
    ``params``, for the tests), loss and gradient of each batch ([rows, T + 1] int tokens),
    global-norm clipping, AdamW with the buffers held.

    As ``nemotron_h_tower.train_steps``, to fit beside 14 bytes a parameter of float32 state
    on one chip: a batch's rows are differentiated one at a time (the batch's loss is a sum over
    rows divided by a count that no parameter moves) and the two moments wait on the host while
    a gradient is computed. No value depends on either.

    Returns each step's loss, the per-leaf norms of the first gradient as the optimizer gets it
    (after clipping), the per-leaf norms of the parameters' change after the last step, and each
    step's routing facts (``held_expert_rows`` and ``moved_share``, a layer of experts each,
    over the batch's rows).
    """
    m = W.model_dims(cfg)
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    (b1, b2), eps = optimizer["betas"], optimizer["eps"]
    clip = optimizer["gradient_clipping"]
    z = m["z_loss_coef"]

    @jax.jit
    def valid_labels(batch):
        def count(row):
            segments, _ = segments_from_eos(row, m["eos"])
            return jnp.sum((segments[1:] == segments[:-1]).astype(jnp.float32))

        return jnp.maximum(sum(count(row) for row in batch), 1.0)

    @jax.jit
    def row_gradient(params, row, count):
        def scaled(p):
            loss_sum, z_sum, _, routing = sequence_loss_terms(m, p, row, quant)
            return (loss_sum + z * z_sum) / count, routing

        return jax.value_and_grad(scaled, has_aux=True)(params)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(params, grads, mu, nu, count):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / (norm + 1e-6)), grads)
        mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), nu, grads)
        c1, c2 = 1 - b1**count, 1 - b2**count
        new = jax.tree.map(lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), params, mu, nu)
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
            count = valid_labels(batch)
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
