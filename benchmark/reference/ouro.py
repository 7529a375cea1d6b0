"""Plain reference of `ouro` (Ouro-2.6B, a looped language model).

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``: no
kernels, no scan, no stacked rows — a Python ``for`` over passes and over layers, the loss
written token by token as below. It imports nothing of the program; its weights come from
``benchmark.weights_ouro`` (the seed). ``x`` a packed row's hidden states ``[T, d]``, ``N`` an
RMSNorm with a weight, eps 1e-6:

  block l   a = x + N2_l(Attn_l(N1_l(x)));  y = a + N4_l(MLP_l(N3_l(a)))
            Attn: [q | k | v] = W_qkv u in 16 heads of 128; rope over the whole head by halves at
            rope_theta 1e6, positions counted from each document's start; o_h = softmax(q_h k_h^T
            / sqrt(128) + causal, same-document mask) v_h; W_o concat(o_h). No bias.
            MLP(u) = W_down (silu(W_gate u) * W_up u) on the [up | gate] bank.
  pass t    h_t = N_f(block_L(... block_1(h_{t-1}))), h_0 = E[tokens], the SAME weights every pass
  gate      lambda_t = sigmoid(h_t . w_g + b_g);  p_1 = lambda_1,
            p_t = lambda_t (1 - lambda_1) ... (1 - lambda_{t-1}) for 1 < t < T,
            p_T = (1 - lambda_1) ... (1 - lambda_{T-1})
  loss      a target token's: sum_t p_t (CE(W_head h_t, y) + z lse_t^2) - beta H(p),
            H(p) = - sum_t p_t log p_t; the batch's: their mean over the target tokens (a label
            across a document boundary is no target).

To fit beside its own float32 state on one chip: a batch's rows are differentiated one at a
time, every block application and every pass's head (in blocks of 2048 rows) are re-computed
in the backward pass (``jax.checkpoint``), attention runs one head at a time, and the two
moments wait on the host while a gradient is computed (``lfm2_moe.train_steps``'s arrangement).
No value depends on any of it.

Controls (``benchmark/limits/train-ouro-loop4-packed8k.json`` says which limit each fails):
``quant="fp8"`` — every linear layer (the blocks' four, the head) as an fp8 recipe computes it
(see ``gpt_dense``), the gate and the norms float32 as in the program; ``passes=3`` — a loop
that drops a pass; ``weigh=False`` — a loss that ignores the gate: the plain mean of the
passes' cross-entropies (and z-losses), no entropy term.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_ouro as W
from .gpt_dense import attention, matmul, rmsnorm, rope, segments_from_eos
from .joyai_flash import swiglu  # W_down (up * silu(gate)) on an [up | gate] bank
from .nemotron_h_tower import leaf_norms


def block(m, p, x, positions, segments, quant=None):
    """One sandwich-normed block on one packed row ``x`` [T, d]."""
    seq, heads, kv, head = x.shape[0], m["n_head"], m["n_kv"], m["head_dim"]
    qkv = matmul(rmsnorm(x, p["ln_1"], m["eps"]), p["c_attn"], quant)
    q = rope(qkv[:, : heads * head].reshape(seq, heads, head), positions, m["rope_theta"])
    k = rope(qkv[:, heads * head : (heads + kv) * head].reshape(seq, kv, head), positions, m["rope_theta"])
    v = qkv[:, (heads + kv) * head :].reshape(seq, kv, head)
    out = matmul(attention(q, k, v, segments).reshape(seq, heads * head), p["attn_c_proj"], quant)
    a = x + rmsnorm(out, p["ln_1_out"], m["eps"])
    out = swiglu(rmsnorm(a, p["ln_2"], m["eps"]), p["c_fc"], p["mlp_c_proj"], quant)
    return a + rmsnorm(out, p["ln_2_out"], m["eps"])


HEAD_ROWS = 2048  # tokens whose [tokens, V] float32 logits live at a time (8192 x 49152 would be 1.6 GB a copy)


def head_terms(h, table, labels, quant=None):
    """(cross-entropy, log-sum-exp) of every token of one pass, [T] each, in blocks of rows whose
    logits are re-computed in the backward pass."""
    def block(h, labels):
        logits = matmul(h, table.T, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0], lse

    parts = [jax.checkpoint(block)(h[i : i + HEAD_ROWS], labels[i : i + HEAD_ROWS]) for i in range(0, h.shape[0], HEAD_ROWS)]
    return jnp.concatenate([p[0] for p in parts]), jnp.concatenate([p[1] for p in parts])


def passes_hidden(m, params, tokens, quant=None, passes=None, remat=True):
    """[h_1, ..., h_T] of one row of tokens taken as its documents by eos."""
    segments, positions = segments_from_eos(tokens, m["eos"])
    run = functools.partial(block, m, quant=quant)
    run = jax.checkpoint(run) if remat else run
    h, out = params["outer"]["wte"][tokens], []
    for _ in range(passes or m["passes"]):
        for p in params["layers"]:
            h = run(p, h, positions, segments)
        h = rmsnorm(h, params["outer"]["ln_f"], m["eps"])
        out.append(h)
    return out


def exit_probabilities(outer, hidden) -> list:
    """[p_1, ..., p_T], a [tokens] vector each, written as the family's report writes them."""
    stop = [jax.nn.sigmoid(jnp.dot(h, outer["gate_w"][:, 0]) + outer["gate_b"][0]) for h in hidden]
    went_on, out = jnp.ones_like(stop[0]), []
    for lam in stop[:-1]:
        out.append(lam * went_on)
        went_on = went_on * (1.0 - lam)
    return out + [went_on]  # the last pass takes what is left


def sequence_loss_terms(m, params, text, quant=None, passes=None, weigh=True):
    """(sum over the row's target tokens of the token's loss, parts) of one packed row ``text`` of
    length T + 1; the parts are sums over target tokens too: every pass's cross-entropy
    (``pass_ce`` [passes]) and exit probability (``exit_mass`` [passes]), the weighted
    cross-entropy, the entropy, and the count."""
    outer = params["outer"]
    tokens, labels = text[:-1], text[1:]
    segments, _ = segments_from_eos(tokens, m["eos"])
    next_segments, _ = segments_from_eos(text, m["eos"])
    valid = (next_segments[1:] == segments).astype(jnp.float32)  # a label across a document boundary is no label
    hidden = passes_hidden(m, params, tokens, quant, passes)
    terms = [head_terms(h, outer["lm_head"], labels, quant) for h in hidden]
    ce, lse = [t[0] for t in terms], [t[1] for t in terms]
    p = exit_probabilities(outer, hidden)
    entropy = -sum(jnp.where(p_t > 0, p_t * jnp.log(jnp.where(p_t > 0, p_t, 1.0)), 0.0) for p_t in p)
    weighted = sum(p_t * ce_t for p_t, ce_t in zip(p, ce))
    if weigh:
        token_loss = weighted + m["z_loss_coef"] * sum(p_t * jnp.square(l) for p_t, l in zip(p, lse)) - m["beta"] * entropy
    else:
        token_loss = sum(ce_t + m["z_loss_coef"] * jnp.square(l) for ce_t, l in zip(ce, lse)) / len(ce)
    total = lambda x: jnp.sum(x * valid)  # noqa: E731
    parts = dict(
        pass_ce=jnp.stack([total(c) for c in ce]), exit_mass=jnp.stack([total(p_t) for p_t in p]),
        weighted=total(weighted), entropy=total(entropy), count=jnp.sum(valid),
    )
    return total(token_loss), jax.lax.stop_gradient(parts)


def forward_logits(cfg: dict, params: dict, tokens, passes=None) -> list:
    """Every pass's [T, V] logits of one row of tokens (the tests)."""
    m = W.model_dims(cfg)
    hidden = passes_hidden(m, params, tokens, passes=passes, remat=False)
    return [jnp.dot(h, params["outer"]["lm_head"].T) for h in hidden]


def batch_loss(cfg: dict, params: dict, batch, quant=None, passes=None, weigh=True):
    """(loss, parts as means over the batch's target tokens) of ``batch`` [rows, T + 1] (the tests)."""
    m = W.model_dims(cfg)
    rows = [sequence_loss_terms(m, params, row, quant, passes, weigh) for row in batch]
    count = jnp.maximum(sum(parts["count"] for _, parts in rows), 1.0)
    means = {k: sum(parts[k] for _, parts in rows) / count for k in ("pass_ce", "exit_mass", "weighted", "entropy")}
    return sum(loss for loss, _ in rows) / count, means


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None, params=None, passes=None, weigh=True) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights (or ``params``,
    for the tests), loss and gradient of each batch ([rows, T + 1] int tokens), global-norm
    clipping, AdamW.

    Returns each step's loss, each step's parts (``pass_losses`` and ``exit_mass``, a pass an
    entry; ``weighted_losses``; ``exit_entropies``: means over the batch's target tokens), the
    per-leaf norms of the first gradient as the optimizer gets it (after clipping) and the
    per-leaf norms of the parameters' change after the last step.
    """
    m = W.model_dims(cfg)
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    (b1, b2), eps = optimizer["betas"], optimizer["eps"]
    clip = optimizer["gradient_clipping"]

    @jax.jit
    def valid_labels(batch):
        def count(row):
            segments, _ = segments_from_eos(row, m["eos"])
            return jnp.sum((segments[1:] == segments[:-1]).astype(jnp.float32))

        return jnp.maximum(sum(count(row) for row in batch), 1.0)

    @jax.jit
    def row_gradient(params, row, count):
        def scaled(p):
            loss_sum, parts = sequence_loss_terms(m, p, row, quant, passes, weigh)
            return loss_sum / count, parts

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
        return new, mu, nu, leaf_norms(grads)

    with jax.default_matmul_precision("highest"):
        key = W.base_key(seed)
        init = jax.jit(lambda k: W.make_all(cfg, k, jnp.float32))
        start = (lambda: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)) if params is not None else (lambda: init(key))
        current = start()
        # the moments on the host between updates (numpy: zeros cost nothing until written)
        mu = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), current)
        nu = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), current)
        out: dict = dict(losses=[], pass_losses=[], exit_mass=[], weighted_losses=[], exit_entropies=[])
        first_grad = None
        for step, batch in enumerate(batches):
            batch = jnp.asarray(batch)
            count = valid_labels(batch)
            loss, grads, parts = 0.0, None, []
            for row in batch:
                (row_loss, row_parts), row_grads = row_gradient(current, row, count)
                loss += float(row_loss)
                parts.append(jax.device_get(row_parts))
                grads = row_grads if grads is None else add(grads, row_grads)
            mean = lambda k: (sum(p[k] for p in parts) / float(count)).tolist()  # noqa: E731, B023
            out["losses"].append(loss)
            out["pass_losses"].append(mean("pass_ce"))
            out["exit_mass"].append(mean("exit_mass"))
            out["weighted_losses"].append(mean("weighted"))
            out["exit_entropies"].append(mean("entropy"))
            current, mu, nu, grad_norms = update(
                current, grads, jax.device_put(mu), jax.device_put(nu), jnp.asarray(step + 1.0, jnp.float32)
            )
            mu, nu = jax.device_get((mu, nu))
            if first_grad is None:
                first_grad = {k: float(v) for k, v in grad_norms.items()}
        del mu, nu
        delta = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0)))(current, start())
        return dict(out, grad_norms=first_grad, delta_norms={k: float(v) for k, v in delta.items()})
