"""Plain reference of `joyai_llm_flash` (JoyAI-LLM-Flash; the DeepSeek-V3 family's layers),
given one chip's share of the experts and of the vocabulary.

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``: no
kernels, no grouped products, no packing beyond the segment test. It imports nothing of the
program; its weights come from ``benchmark.weights_joyai_flash`` (the seed). ``x`` a row of
hidden states, ``N`` an RMSNorm with a weight, eps 1e-6, one packed row at a time:

  block l   a = x + MLA(N1(x)); y = a + F_l(N2(a)); F_0 the dense MLP W_d(silu(W_g u) * W_u u),
            F_l, l >= 1, the experts.
  MLA       c_q = N_q(W_qa u); q = W_qb c_q -> heads of [q_nope | q_rope]. [c_kv | k_rope] =
            W_kva u; c_kv <- N_kv(c_kv); W_kvb c_kv -> heads of [k_nope | v]. q_rope and the
            single k_rope are rotated at rope_theta over the rope columns, positions counted
            from each document's start; k_h = [k_nope_h | k_rope], q_h = [q_nope_h | q_rope_h];
            o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal, same-document mask) v_h;
            out = W_o concat(o_h). No bias. Keys and values are expanded per head (the training form).
  experts   s = sigmoid(W_r u) over ALL experts; the top-k of s + b are chosen (b the
            correction bias, a buffer: no gradient, no update); w_i = scale s_i / (sum of the
            chosen s + 1e-20); F(u) = sum over the chosen experts HELD HERE of w_i E_i(u), plus
            E_shared(u); every E a SwiGLU MLP. What the absent experts would add is left out.
  MTP       h'_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)] with h_i the last block's output before
            the final norm; one more expert block over h' (same masks and positions); N_s and
            the main model's head; L_mtp the mean cross-entropy of position i against t_{i+2}
            over the positions whose t_{i+1} and t_{i+2} lie in t_i's document.
  loss      L = L_main + mtp_coef L_mtp, each with the trainer's z-loss.

Departures, none changing a value. The rotation is written as the public checkpoint lays its
columns out — neighbours (x_2j, x_2j+1) are a pair, rotated by pos theta^(-2j/rope) — where
the program first de-interleaves the columns and then rotates halves: a score does not change
with the order of the columns that queries and keys share. Attention runs one head at a time,
every block is re-computed in the backward pass, the held experts run over every token one after
the other (a `lax.scan`) and are weighed by the router (zero where the token did not choose one).

``quant="fp8"`` is the control (see ``gpt_dense``): every linear layer — projections,
experts, the MTP projection, the head — computed as an fp8 recipe computes; the router stays
float32, as it does in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_joyai_flash as W
from .gpt_dense import attention, matmul, rmsnorm, segments_from_eos
from .nemotron_h_tower import hold_buffers, leaf_norms, route


def rotate_pairs(x, positions, theta):
    """x [T, heads, rope]: columns (2j, 2j + 1) rotated by ``positions theta^(-2j / rope)``."""
    width = x.shape[-1]
    angle = positions[:, None].astype(jnp.float32) / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def latent_attention(m, p, u, positions, segments, quant=None):
    seq, heads, nope, rope, v = u.shape[0], m["n_head"], m["nope"], m["rope"], m["v"]
    c_q = rmsnorm(matmul(u, p["q_a_proj"], quant), p["q_a_layernorm"], m["eps"])
    q = matmul(c_q, p["q_b_proj"], quant).reshape(seq, heads, nope + rope)
    c_kv, k_rope = jnp.split(matmul(u, p["kv_a_proj_with_mqa"], quant), [m["kv_rank"]], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_a_layernorm"], m["eps"])
    kv = matmul(c_kv, p["kv_b_proj"], quant).reshape(seq, heads, nope + v)
    q_rope = rotate_pairs(q[..., nope:], positions, m["rope_theta"])
    k_rope = rotate_pairs(k_rope[:, None, :], positions, m["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (seq, heads, rope))], axis=-1)
    out = attention(q, k, kv[..., nope:], segments)  # scores / sqrt(nope + rope), values of v
    return matmul(out.reshape(seq, heads * v), p["o_proj"], quant)


def swiglu(u, w_up_gate, w_down, quant=None):
    up, gate = jnp.split(matmul(u, w_up_gate, quant), 2, axis=-1)
    return matmul(up * jax.nn.silu(gate), w_down, quant)


def experts(m, p, u, quant=None):
    """The chip's share: experts ``first_expert .. first_expert + held - 1`` of the router's
    ``experts``, plus the shared expert."""
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
    return out + swiglu(u, p["shared_c_fc"], p["shared_c_proj"], quant)


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
    """(y, routing facts or None) of one block; the dense MLP where the layer has one."""
    a = x + latent_attention(m, p, rmsnorm(x, p["ln_1"], m["eps"]), positions, segments, quant)
    u = rmsnorm(a, p["ln_2"], m["eps"])
    if "mlp_c_fc" in p:
        return a + swiglu(u, p["mlp_c_fc"], p["mlp_c_proj"], quant), None
    return a + experts(m, p, u, quant), router_facts(m, p, u)


def head_terms(m, outer, h, labels, valid, quant=None):
    """(sum of token losses, sum of logsumexp**2) over the valid positions of normed ``h``."""
    logits = matmul(h, outer["lm_head"].T, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    valid = valid.astype(jnp.float32)
    return jnp.sum((lse - picked) * valid), jnp.sum(jnp.square(lse) * valid)


def label_masks(m, text):
    """(valid main labels [T], valid MTP targets [T]) of one packed row ``text`` [T + 1]: a
    label across a document boundary is no label; an MTP target needs t_{i+1} and t_{i+2} in
    t_i's document (and t_{i+2} in the row)."""
    segments, _ = segments_from_eos(text, m["eos"])
    main = segments[1:] == segments[:-1]
    second = jnp.concatenate([main[1:] & main[:-1], jnp.zeros((1,), bool)])
    return main, second


def sequence_loss_terms(m, params, text, quant=None):
    """((main loss sum, main z sum), (MTP loss sum, MTP z sum), routing facts) of one packed
    row ``text`` of length T + 1; the facts are ``held_expert_rows`` [layers of experts, held]
    and ``moved`` [layers of experts] (the MTP module's layer last)."""
    outer = params["outer"]
    tokens = text[:-1]
    segments, positions = segments_from_eos(tokens, m["eos"])
    main_valid, second_valid = label_masks(m, text)
    run = lambda p, x: jax.checkpoint(functools.partial(block, m, quant=quant))(p, x, positions, segments)  # noqa: E731
    h, facts = outer["wte"][tokens], []
    for p in params["layers"][: m["n_layer"]]:
        h, layer_facts = run(p, h)
        if layer_facts is not None:
            facts.append(layer_facts)
    main = head_terms(m, outer, rmsnorm(h, outer["ln_f"], m["eps"]), text[1:], main_valid, quant)
    second = (jnp.zeros(()), jnp.zeros(()))
    if m["mtp"]:
        p = params["layers"][m["n_layer"]]
        both = jnp.concatenate([rmsnorm(outer["wte"][text[1:]], p["mtp_enorm"], m["eps"]), rmsnorm(h, p["mtp_hnorm"], m["eps"])], axis=-1)
        h2, layer_facts = run(p, matmul(both, p["mtp_eh_proj"], quant))
        facts.append(layer_facts)
        # position i against t_{i+2}; the last position has no target (any label serves: masked)
        targets = jnp.concatenate([text[2:], text[:1]])
        second = head_terms(m, outer, rmsnorm(h2, p["mtp_norm"], m["eps"]), targets, second_valid, quant)
    routing = {}
    if facts:
        routing = {"held_expert_rows": jnp.stack([f[0] for f in facts]), "moved": jnp.stack([f[1] for f in facts])}
    return main, second, routing


def forward_logits(cfg: dict, params: dict, tokens) -> jax.Array:
    """[T, V] main logits of one row of tokens taken as its documents by eos (the tests)."""
    m = W.model_dims(cfg)
    segments, positions = segments_from_eos(tokens, m["eos"])
    h = params["outer"]["wte"][tokens]
    for p in params["layers"][: m["n_layer"]]:
        h, _ = block(m, p, h, positions, segments)
    return jnp.dot(rmsnorm(h, params["outer"]["ln_f"], m["eps"]), params["outer"]["lm_head"].T)


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, quant=None, params=None) -> dict:
    """Follow the trainer's first ``len(batches)`` steps: seeded float32 weights (or
    ``params``, for the tests), loss and gradient of each batch ([rows, T + 1] int tokens),
    global-norm clipping, AdamW with the buffers held.

    As ``nemotron_h_tower.train_steps``, to fit beside 14 bytes a parameter of float32 state
    on one chip: a batch's rows are differentiated one at a time (each part of the batch's loss
    is a sum over rows divided by a count that no parameter moves) and the two moments wait on
    the host while a gradient is computed. No value depends on either.

    Returns each step's loss (``losses``) and its two parts (``main_losses``, ``mtp_losses``:
    ``loss = main + mtp_coef x mtp``), the per-leaf norms of the first gradient as the
    optimizer gets it (after clipping), the per-leaf norms of the parameters' change after the
    last step, and each step's routing facts (``held_expert_rows`` and ``moved_share``, a layer
    of experts each with the MTP module's last, over the batch's rows).
    """
    m = W.model_dims(cfg)
    lr, wd = optimizer["lr"], optimizer["weight_decay"]
    (b1, b2), eps = optimizer["betas"], optimizer["eps"]
    clip = optimizer["gradient_clipping"]
    coef, z = m["mtp_coef"], m["z_loss_coef"]

    @jax.jit
    def valid_labels(batch):
        masks = [label_masks(m, row) for row in batch]
        count = lambda which: jnp.maximum(sum(jnp.sum(mask[which].astype(jnp.float32)) for mask in masks), 1.0)  # noqa: E731
        return count(0), count(1)

    @jax.jit
    def row_gradient(params, row, counts):
        def scaled(p):
            main, second, routing = sequence_loss_terms(m, p, row, quant)
            main_loss = (main[0] + z * main[1]) / counts[0]
            mtp_loss = (second[0] + z * second[1]) / counts[1]
            return main_loss + coef * mtp_loss, (main_loss, mtp_loss, routing)

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
        losses, main_losses, mtp_losses, first_grad, routing = [], [], [], None, []
        for step, batch in enumerate(batches):
            batch = jnp.asarray(batch)
            counts = valid_labels(batch)
            loss, main_loss, mtp_loss, grads, facts = 0.0, 0.0, 0.0, None, []
            for row in batch:
                (row_loss, (row_main, row_mtp, row_facts)), row_grads = row_gradient(current, row, counts)
                loss, main_loss, mtp_loss = loss + float(row_loss), main_loss + float(row_main), mtp_loss + float(row_mtp)
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
            main_losses.append(main_loss)
            mtp_losses.append(mtp_loss)
            if first_grad is None:
                first_grad = {k: float(v) for k, v in grad_norms.items()}
        del mu, nu
        delta = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0)))(current, start())
        return dict(
            losses=losses, main_losses=main_losses, mtp_losses=mtp_losses,
            grad_norms=first_grad,
            delta_norms={k: float(v) for k, v in delta.items()},
            routing=routing,
        )
