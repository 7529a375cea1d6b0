"""Plain reference of `afmoe` (Trinity-Mini), given one chip's share of the experts and of the
vocabulary.

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``: dense
masks built from positions and segment ids, no block tables, no kernels, no grouped products. It
imports nothing of the program; its weights come from ``benchmark.weights_afmoe`` (the seed).
``x`` a row of hidden states, ``N`` an RMSNorm with a weight, eps 1e-5, one packed row at a time:

  embedding h_0 = sqrt(d) E[token]                                   (mup_enabled)
  block l   a = x + N2(Attn_l(N1(x)));  y = a + N4(F_l(N3(a)))       four norms, Ouro's order
  Attn_l    [q | k | v] = W_qkv u in heads of 128, 32 query heads over 4 key/value heads;
            g = W_g u (as wide as the heads' output); q <- N_q(q), k <- N_k(k) per head over its
            columns (eps as the block norms'), BEFORE any rotation.
            sliding_attention: rope over the whole head by halves at rope_theta, positions
            counted from each document's start; query i sees key j iff j <= i, same document,
            i - j < sliding_window (itself and the window - 1 before it).
            full_attention: NO rotation; query i sees every j <= i of its document.
            o_h = softmax(q_h k_g^T / sqrt(head) + mask) v_g with g = h // 8, the softmax in
            float32 (everything here is); Attn(u) = W_o (concat(o_h) * sigmoid(g)). No bias.
  F_l       l < num_dense_layers: W_2 (silu(W_1 u) * W_3 u). Later: s = sigmoid(W_r u) over ALL
            experts; the top-k of s + b are chosen (b the expert bias, a buffer: no gradient, no
            update); w_i = route_scale s_i / (sum of the chosen s + 1e-20); F(u) = Shared(u) +
            sum over the chosen experts HELD HERE of w_i E_i(u), every E and the shared one a
            SwiGLU MLP. What the absent experts would add is left out.
  loss      mean cross-entropy of the next token inside its document, over the vocabulary rows
            held, the head an untied table, with the trainer's z-loss.

Departures from the public modeling code, none changing a value (each also under ``assumed`` in
the configuration's file): the QK norms' eps is rms_norm_eps; the router's scores are float32
(everything here is); attention runs one head and one block of 2048 queries at a time against
all keys under the dense mask (so that a 16384-token row's scores fit: 128 MiB a block) and every
block is re-computed in the backward pass; the head reads the row in blocks of 4096 tokens; the
held experts run over every token one after the other (a `lax.scan`, PR 30's lesson on compile
time) and are weighed by the router (zero where the token did not choose one); the expert bias
is held where the weights' maker put it (``weights_afmoe.balanced_biases``, which runs its
calibration rows through ``attention_half`` and ``feed_forward_half`` below: where the family's
update rule would rest; the rule itself is a training recipe: not built).

``quant="fp8"`` is the control (see ``gpt_dense``): every linear layer — attention's
projections with the gate's, the MLP, the experts and the shared one, the head — computed as an
fp8 recipe computes; the router stays float32, as it does in the program.

``layer_types`` (a list in the configuration's words) and ``rotate_full`` are the two named
faults the cell's limits must catch (``benchmark/limits/...json``, ``reasons.faults``): the
reference run with other kinds than the configuration's (a window layer run as a full layer and
the reverse), and with a full layer that rotates. Never set by the benchmark's command.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_afmoe as W
from .gpt_dense import matmul, rmsnorm, rope, segments_from_eos
from .joyai_flash import experts, router_facts, swiglu  # the shared expert's layer: route over ALL experts, 1e-20, the share held
from .nemotron_h_tower import hold_buffers, leaf_norms

QUERY_BLOCK = 2048  # queries scored at a time against all keys
HEAD_BLOCK = 4096  # tokens whose logits are held at a time


def attention(q, k, v, segments, window=None, query_block: int = QUERY_BLOCK):
    """Causal attention inside a segment, under a window where the layer has one. q: [T, H, hd];
    k, v: [T, KV, hd]; query head h reads K/V head h // (H // KV). The mask is dense, built for
    one block of queries at a time from the tokens' places and segments."""
    seq, n_head, head_dim = q.shape
    group = n_head // k.shape[1]
    block = query_block if seq % query_block == 0 else seq
    place = jnp.arange(seq)
    qt, kt, vt = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))

    @jax.checkpoint
    def one_block(head_and_start):
        h, start = head_and_start
        rows = jax.lax.dynamic_slice_in_dim(qt[h], start, block)
        mine = start + jnp.arange(block)
        mask = (mine[:, None] >= place[None, :]) & (jax.lax.dynamic_slice_in_dim(segments, start, block)[:, None] == segments[None, :])
        if window is not None:
            mask = mask & (mine[:, None] - place[None, :] < window)
        scores = jnp.dot(rows, kt[h // group].T) * head_dim**-0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.dot(probs, vt[h // group])

    heads, starts = jnp.meshgrid(jnp.arange(n_head), jnp.arange(0, seq, block), indexing="ij")
    out = jax.lax.map(one_block, (heads.reshape(-1), starts.reshape(-1)))  # [H * blocks, block, hd]
    return jnp.swapaxes(out.reshape(n_head, seq, head_dim), 0, 1)  # [T, H, hd]


def gated_attention(m, p, u, positions, segments, window, rotate: bool, quant=None):
    seq, heads, kv, head = u.shape[0], m["n_head"], m["n_kv"], m["head_dim"]
    qkv = matmul(u, p["c_attn"], quant)
    q = rmsnorm(qkv[:, : heads * head].reshape(seq, heads, head), p["q_norm_weight"], m["eps"])
    k = rmsnorm(qkv[:, heads * head : (heads + kv) * head].reshape(seq, kv, head), p["k_norm_weight"], m["eps"])
    v = qkv[:, (heads + kv) * head :].reshape(seq, kv, head)
    if rotate:  # the window layers only
        q, k = rope(q, positions, m["rope_theta"]), rope(k, positions, m["rope_theta"])
    out = attention(q, k, v, segments, window).reshape(seq, heads * head)
    return matmul(out * jax.nn.sigmoid(matmul(u, p["g_proj"], quant)), p["attn_c_proj"], quant)


def attention_half(m, p, x, positions, segments, window=None, rotate=None, quant=None):
    """(a, u) of one block: the stream after attention under ``window`` (None: a full layer;
    ``rotate``: whether it takes positions, by default the window layers only), and the normed
    input of the feed-forward (and of the router, where the layer has one)."""
    rotate = window is not None if rotate is None else rotate
    out = gated_attention(m, p, rmsnorm(x, p["ln_1"], m["eps"]), positions, segments, window, rotate, quant)
    a = x + rmsnorm(out, p["ln_1_out"], m["eps"])
    return a, rmsnorm(a, p["ln_2"], m["eps"])


def feed_forward_half(m, p, a, u, quant=None):
    """The block's output: the stream plus the normed feed-forward its leaves name."""
    out = swiglu(u, p["mlp_c_fc"], p["mlp_c_proj"], quant) if "mlp_c_fc" in p else experts(m, p, u, quant)
    return a + rmsnorm(out, p["ln_2_out"], m["eps"])


def block(m, p, x, positions, segments, window=None, rotate=None, quant=None):
    """(y, routing facts or None) of one block."""
    a, u = attention_half(m, p, x, positions, segments, window, rotate, quant)
    return feed_forward_half(m, p, a, u, quant), None if "mlp_c_fc" in p else router_facts(m, p, u)


def layer_masks(m, layer_types=None, rotate_full: bool = False) -> list:
    """(window, rotate) a layer; ``layer_types`` and ``rotate_full`` are the named faults."""
    kinds = m["layer_types"] if layer_types is None else tuple(layer_types)
    return [(m["window"], True) if kind == "sliding_attention" else (None, rotate_full) for kind in kinds]


def hidden_states(m, params, tokens, quant=None, layer_types=None, rotate_full=False, remat=True):
    """(the last block's output [T, d], routing facts a layer of experts) of one row of tokens."""
    segments, positions = segments_from_eos(tokens, m["eos"])
    h, facts = m["embedding_multiplier"] * params["outer"]["wte"][tokens], []
    for p, (window, rotate) in zip(params["layers"], layer_masks(m, layer_types, rotate_full)):
        run = functools.partial(block, m, window=window, rotate=rotate, quant=quant)
        h, layer_facts = (jax.checkpoint(run) if remat else run)(p, h, positions, segments)
        if layer_facts is not None:
            facts.append(layer_facts)
    return h, facts


def head_terms(h, table, labels, valid, quant=None, head_block: int = HEAD_BLOCK):
    """(sum of token losses, sum of logsumexp**2) over the valid positions of normed ``h``, the
    logits held a block of tokens at a time."""
    seq = h.shape[0]
    size = head_block if seq % head_block == 0 else seq

    @jax.checkpoint
    def one_block(args):
        rows, targets, mask = args
        logits = matmul(rows, table.T, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * mask), jnp.sum(jnp.square(lse) * mask)

    blocks = lambda a: a.reshape(seq // size, size, *a.shape[1:])  # noqa: E731
    losses, squares = jax.lax.map(one_block, (blocks(h), blocks(labels), blocks(valid)))
    return jnp.sum(losses), jnp.sum(squares)


def sequence_loss_terms(m, params, text, quant=None, layer_types=None, rotate_full=False):
    """(sum of token losses, sum of logsumexp**2, count of valid labels, routing facts) of one packed
    row ``text`` of length T + 1; the facts are ``held_expert_rows`` [layers of experts, held] and ``moved``
    [layers of experts]."""
    outer = params["outer"]
    tokens, labels = text[:-1], text[1:]
    segments, _ = segments_from_eos(tokens, m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    valid = (next_segments[1:] == segments).astype(jnp.float32)  # a label across a document boundary is no label
    h, facts = hidden_states(m, params, tokens, quant, layer_types, rotate_full)
    loss_sum, z_sum = head_terms(rmsnorm(h, outer["ln_f"], m["eps"]), outer["lm_head"], labels, valid, quant)
    routing = {}
    if facts:
        routing = {"held_expert_rows": jnp.stack([f[0] for f in facts]), "moved": jnp.stack([f[1] for f in facts])}
    return loss_sum, z_sum, jnp.sum(valid), routing


def forward_logits(cfg: dict, params: dict, tokens, layer_types=None, rotate_full=False) -> jax.Array:
    """[T, V] logits of one row of tokens taken as its documents by eos (the tests)."""
    m = W.model_dims(cfg)
    h, _ = hidden_states(m, params, tokens, None, layer_types, rotate_full, remat=False)
    return jnp.dot(rmsnorm(h, params["outer"]["ln_f"], m["eps"]), params["outer"]["lm_head"].T)


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None, params=None, layer_types=None, rotate_full=False) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights (or
    ``params``, for the tests), loss and gradient of each batch ([rows, T + 1] int tokens),
    global-norm clipping, AdamW with the buffers held.

    As ``lfm2_moe.train_steps`` (the loop is that file's, over this family's row: the accepted
    files take no other), to fit beside 14 bytes a parameter of float32 state on one chip: a
    batch's rows are differentiated one at a time (the batch's loss is a sum over rows divided by
    a count that no parameter moves) and the two moments wait on the host while a gradient is
    computed. No value depends on either.

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
            loss_sum, z_sum, _, routing = sequence_loss_terms(m, p, row, quant, layer_types, rotate_full)
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
