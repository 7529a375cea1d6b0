"""Loss functions: causal-LM cross entropy + MoE load-balancing aux loss.

Parity:
  - pretraining CE on shifted tokens: reference `model_wrapper/pretraining.py:89-127` computes
    loss externally with `F.cross_entropy` on fp32-upcast logits; labels = inputs shifted by one.
  - padding-free boundary masking: reference `gpt_dolomite/main.py:179-202` masks the shift across
    document boundaries via cu_seqlens; here that falls out of segment_ids (label position whose
    segment differs from its input position is ignored).
  - loss_parallel (vocab-TP CE, `gpt_dolomite_TP/main.py:158-166`): on TPU the logits stay
    vocab-sharded ("act_vocab" -> tp); the logsumexp/gather below is computed by GSPMD with a psum
    over the tp axis — no explicit collective code needed.
  - MoE aux loss: reference `moe_dolomite/moe/base.py:24-43` reuses HF mixtral
    `load_balancing_loss_func` (switch-transformer style fraction-of-tokens x router-prob).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100


def cross_entropy_terms(
    logits: jax.Array,
    labels: jax.Array,
    upcast: bool = True,
    want_z: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Token-level CE reduced to (loss_sum, z_sum, num_tokens).

    ``z_sum`` is the PaLM-style z-loss numerator — sum over valid tokens of
    ``logsumexp(logits)^2`` — computed only when `want_z` (an extra reduction over the
    vocab axis otherwise). The single formula shared by the unchunked and chunked loss
    paths, so their parity is summation-order-only (1-2 float32 ulp).
    """
    if upcast:
        logits = logits.astype(jnp.float32)

    mask = labels != IGNORE_INDEX
    safe_labels = jnp.where(mask, labels, 0)

    logprobs = jax.nn.log_softmax(logits, axis=-1)
    token_logprobs = jnp.take_along_axis(logprobs, safe_labels[..., None], axis=-1)[..., 0]

    loss_sum = -jnp.sum(jnp.where(mask, token_logprobs, 0.0))
    num_tokens = jnp.sum(mask.astype(jnp.float32))
    z_sum = jnp.zeros((), jnp.float32)
    if want_z:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        z_sum = jnp.sum(jnp.where(mask, jnp.square(lse.astype(jnp.float32)), 0.0))
    return loss_sum, z_sum, num_tokens


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    upcast: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Token-level CE. logits [..., V]; labels [...] with IGNORE_INDEX masking.

    Returns (sum_loss, num_tokens) so callers can all-reduce numerator/denominator separately
    (exact mean over the global batch regardless of per-shard masking).
    """
    loss_sum, _, num_tokens = cross_entropy_terms(logits, labels, upcast=upcast)
    return loss_sum, num_tokens


def derive_causal_labels(
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Next-token labels: `input_ids` shifted left by one. Positions are IGNORE_INDEX when:
    the shifted-out last position, padding (attention_mask == 0 / segment 0), or a document
    boundary (segment of label != segment of input — the `reset_attention_mask` doc isolation).
    """
    labels = jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], IGNORE_INDEX)], axis=1
    )
    if attention_mask is not None:
        shifted_mask = jnp.concatenate(
            [attention_mask[:, 1:], jnp.zeros_like(attention_mask[:, :1])], axis=1
        )
        labels = jnp.where(shifted_mask.astype(bool), labels, IGNORE_INDEX)
    if segment_ids is not None:
        next_seg = jnp.concatenate(
            [segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1
        )
        valid = (next_seg == segment_ids) & (segment_ids != 0)
        labels = jnp.where(valid, labels, IGNORE_INDEX)
    return labels


def causal_lm_loss(
    logits: jax.Array,
    input_ids: jax.Array,
    upcast: bool = True,
    attention_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    labels: jax.Array | None = None,
    z_loss_coef: float = 0.0,
) -> jax.Array:
    """Mean next-token CE over valid positions (labels derived per `derive_causal_labels`).

    ``z_loss_coef > 0`` adds the PaLM z-loss ``coef * mean(logsumexp(logits)^2)`` — the
    softmax-normalizer regularizer that keeps logits from drifting (the chunked fused
    path in `fused_linear_cross_entropy` computes the identical term per chunk)."""
    if labels is None:
        labels = derive_causal_labels(input_ids, attention_mask, segment_ids)

    loss_sum, z_sum, num_tokens = cross_entropy_terms(
        logits, labels, upcast=upcast, want_z=z_loss_coef != 0.0
    )
    denom = jnp.maximum(num_tokens, 1.0)
    loss = loss_sum / denom
    if z_loss_coef != 0.0:
        loss = loss + z_loss_coef * (z_sum / denom)
    return loss


@jax.named_scope("ce_chunk")  # a scan body starts with no name of its own in a profile
def _chunk_ce_terms(
    h: jax.Array,
    table: jax.Array,
    y: jax.Array,
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    want_z: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk's LM-head matmul + CE reduction, XLA reference lowering.

    The chunk's ``[B, chunk, V]`` logits exist only inside this function — forward AND
    backward (the `_chunked_ce_terms` custom_vjp re-runs it under `jax.vjp` per chunk).
    """
    from ..parallel.sharding import logical_constraint

    # Pin the table to its ACTIVATION layout (vocab over tp only; replicated otherwise)
    # INSIDE the per-chunk body so the backward replay sees it too. Under ZeRO-3 the tied
    # table arrives fsdp-sharded along vocab; without this boundary the partitioner
    # propagates that layout into the chunk's log_softmax backward where it collides
    # with the batch-sharded logits constraint below — XLA then falls back to
    # "involuntary full rematerialization" (full replication) of the logits-sized
    # gradient. With it, the table is gathered at a clean boundary and grad_emb leaves
    # as a reduce-scatter — exactly ZeRO-3's gather/compute/scatter contract.
    table = logical_constraint(table, ("act_vocab", None))
    logits = jnp.dot(h.astype(compute_dtype), table.T)
    # keep the CE vocab-parallel ("act_vocab" -> tp) instead of all-gathering the table
    # per chunk. The chunk-local seq axis stays UNSHARDED (None, not "act_seq"): the
    # S -> (n_chunks, chunk) reshape already broke any sp sharding, and re-claiming
    # "act_seq" here forces an SPMD reshard of every chunk on sp>1 meshes.
    logits = logical_constraint(logits, ("act_batch", None, "act_vocab"))
    if logit_scale is not None:
        logits = logits * logit_scale
    return cross_entropy_terms(logits, y, upcast=upcast, want_z=want_z)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_ce_terms(
    hidden_c: jax.Array,  # [n_chunks, B, chunk, H]
    labels_c: jax.Array,  # [n_chunks, B, chunk]
    table: jax.Array,  # [V, H] in compute dtype
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    want_z: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(loss_sum, z_sum, num_tokens) over all chunks; at most one chunk's logits live.

    The ``fused_ce`` kernel family dispatches here: with the family on Pallas the
    per-chunk reduction runs `ops/pallas/fused_ce.fused_ce_chunk` (vocab-tiled online
    logsumexp — the chunk logits never leave VMEM); the XLA reference scans
    `_chunk_ce_terms`. The custom_vjp below makes BOTH backwards the same per-chunk
    recompute + autodiff of the reference body, so gradients cannot depend on the
    forward backend.
    """
    from ..ops.pallas import use_pallas

    if use_pallas("fused_ce"):
        from ..ops.pallas.fused_ce import fused_ce_chunk

        def body(carry, xs):
            h, y = xs
            loss_sum, z_sum, num = fused_ce_chunk(
                h, table, y, logit_scale=logit_scale, upcast=upcast,
                compute_dtype=compute_dtype,
            )
            return (carry[0] + loss_sum, carry[1] + z_sum, carry[2] + num), None
    else:

        def body(carry, xs):
            h, y = xs
            loss_sum, z_sum, num = _chunk_ce_terms(
                h, table, y, logit_scale, upcast, compute_dtype, want_z
            )
            return (carry[0] + loss_sum, carry[1] + z_sum, carry[2] + num), None

    zero = jnp.zeros((), jnp.float32)
    with jax.named_scope("loss_chunks"):
        (loss_sum, z_sum, num_tokens), _ = jax.lax.scan(
            body, (zero, zero, zero), (hidden_c, labels_c)
        )
    return loss_sum, z_sum, num_tokens


def _chunked_ce_terms_fwd(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z):
    out = _chunked_ce_terms(
        hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z
    )
    # residuals are exactly the inputs — O(B*S*H + V*H), nothing logits-sized is saved
    return out, (hidden_c, labels_c, table)


def _chunked_ce_terms_bwd(logit_scale, upcast, compute_dtype, want_z, residuals, cts):
    hidden_c, labels_c, table = residuals

    def body(dtable_acc, xs):
        h, y = xs
        terms, chunk_vjp = jax.vjp(
            lambda h_, t_: _chunk_ce_terms(
                h_, t_, y, logit_scale, upcast, compute_dtype, want_z
            ),
            h,
            table,
        )
        # the scan carry sums the chunk terms in fp32, but without `upcast` a bf16 chunk
        # emits bf16 terms — and jax.vjp takes cotangents only in its outputs' own dtype
        dh, dt = chunk_vjp(tuple(ct.astype(term.dtype) for ct, term in zip(cts, terms)))
        # fp32 accumulation across chunks regardless of the table's compute dtype (the
        # unchunked reference accumulates its table grad inside one fp32 matmul)
        return dtable_acc + dt.astype(jnp.float32), dh

    # a custom_vjp's backward rule is traced under the scopes of the call (the model's
    # `head_loss`) but not under those its forward opened: it opens the forward's own, so
    # that a profile tells the two scans apart by JAX's `transpose(...)` wrapper alone
    with jax.named_scope("loss_chunks"):
        dtable, dhidden_c = jax.lax.scan(
            body, jnp.zeros(table.shape, jnp.float32), (hidden_c, labels_c)
        )
        return dhidden_c, None, dtable.astype(table.dtype)


_chunked_ce_terms.defvjp(_chunked_ce_terms_fwd, _chunked_ce_terms_bwd)


def fused_linear_cross_entropy(
    hidden: jax.Array,
    embedding: jax.Array,
    labels: jax.Array,
    *,
    chunk_size: int = 256,
    upcast: bool = True,
    logit_scale: float | None = None,
    compute_dtype=jnp.bfloat16,
    z_loss_coef: float = 0.0,
) -> jax.Array:
    """LM-head matmul + CE without ever materializing the [B, S, V] logits.

    The sequence axis is cut into chunks of `chunk_size`; a `lax.scan` computes each
    chunk's logits ([B, chunk, V]), reduces them to (loss_sum, z_sum, count), and
    discards them. The whole reduction sits behind a `custom_vjp` whose residuals are
    just (hidden, labels, table): backward re-runs each chunk's forward under `jax.vjp`
    and accumulates the table grad in fp32, so peak logits memory is O(chunk) in both
    directions. Peak logits memory drops S/chunk_size-fold (at seq 2048 / vocab 50k the
    full tensor is the single largest allocation in a train step). The reference has no
    counterpart (it materializes logits and calls F.cross_entropy,
    `model_wrapper/pretraining.py:89-127`); this is the TPU/HBM-side answer to that cost
    — the same move as Liger-kernel's chunked fused CE on GPU.

    With the ``fused_ce`` kernel family on Pallas the per-chunk reduction additionally
    runs as a vocab-tiled online-logsumexp kernel (`ops/pallas/fused_ce.py`) whose
    logits tiles never leave VMEM; gradients are backend-independent by construction
    (see `_chunked_ce_terms`).

    hidden: [B, S, H]; embedding: [V, H] (tied-embedding layout); labels: [B, S] with
    IGNORE_INDEX. Chunking is along sequence, so dp/fsdp/ep batch sharding is untouched.
    ``z_loss_coef`` adds ``coef * mean(logsumexp^2)`` exactly like `causal_lm_loss`.
    """
    B, S, H = hidden.shape
    chunk_size = min(chunk_size, S)
    if S % chunk_size != 0:
        # pad the sequence up to a chunk multiple; padded positions carry IGNORE_INDEX labels
        # so they contribute nothing to loss_sum/num_tokens
        pad = chunk_size - S % chunk_size
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=IGNORE_INDEX)
        S += pad
    n_chunks = S // chunk_size

    hidden_c = hidden.reshape(B, n_chunks, chunk_size, H).swapaxes(0, 1)
    labels_c = labels.reshape(B, n_chunks, chunk_size).swapaxes(0, 1)

    emb = embedding.astype(compute_dtype)
    loss_sum, z_sum, num_tokens = _chunked_ce_terms(
        hidden_c, labels_c, emb, logit_scale, upcast, compute_dtype, z_loss_coef != 0.0
    )
    denom = jnp.maximum(num_tokens, 1.0)
    loss = loss_sum / denom
    if z_loss_coef != 0.0:
        loss = loss + z_loss_coef * (z_sum / denom)
    return loss


def load_balancing_loss(
    router_logits: jax.Array,
    num_experts: int,
    num_experts_per_tok: int,
    valid_mask: jax.Array | None = None,
) -> jax.Array:
    """Switch-Transformer load balancing loss over all layers' router logits, matching HF
    mixtral `load_balancing_loss_func` exactly: layers are CONCATENATED into one token axis
    (mean over L*T), the top-k axis is SUMMED, result scaled by num_experts.

    router_logits: [layers, tokens, num_experts] (or [tokens, num_experts]).
    """
    if router_logits.ndim == 3:
        router_logits = router_logits.reshape(-1, num_experts)

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [LT, E]
    _, top_idx = jax.lax.top_k(probs, num_experts_per_tok)
    expert_mask = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)  # [LT, K, E]

    if valid_mask is not None:
        w = jnp.tile(valid_mask.astype(jnp.float32).reshape(-1), probs.shape[0] // valid_mask.size)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        tokens_per_expert = jnp.einsum("tke,t->ke", expert_mask, w) / denom  # [K, E]
        router_prob_per_expert = jnp.einsum("te,t->e", probs, w) / denom  # [E]
    else:
        tokens_per_expert = jnp.mean(expert_mask, axis=0)  # [K, E]
        router_prob_per_expert = jnp.mean(probs, axis=0)  # [E]

    return jnp.sum(tokens_per_expert * router_prob_per_expert[None, :]) * num_experts
