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
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

IGNORE_INDEX = -100


def _ce_terms(
    logits: jax.Array, labels: jax.Array, upcast: bool, want_z: bool, with_lse: bool = False
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array | None]:
    """(loss_sum, z_sum, num_tokens, lse): `lse` is the per-token log-sum-exp in float32 —
    all the chunked loss's backward rule keeps of the logits — or None unless `with_lse`."""
    if upcast:
        logits = logits.astype(jnp.float32)

    mask = labels != IGNORE_INDEX
    safe_labels = jnp.where(mask, labels, 0)

    logprobs = jax.nn.log_softmax(logits, axis=-1)
    token_logprobs = jnp.take_along_axis(logprobs, safe_labels[..., None], axis=-1)[..., 0]

    loss_sum = -jnp.sum(jnp.where(mask, token_logprobs, 0.0))
    num_tokens = jnp.sum(mask.astype(jnp.float32))
    z_sum = jnp.zeros((), jnp.float32)
    lse = None
    if want_z or with_lse:
        lse = jax.scipy.special.logsumexp(logits, axis=-1).astype(jnp.float32)
    if want_z:
        z_sum = jnp.sum(jnp.where(mask, jnp.square(lse), 0.0))
    return loss_sum, z_sum, num_tokens, lse if with_lse else None


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
    return _ce_terms(logits, labels, upcast, want_z)[:3]


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


class LossTiling(NamedTuple):
    """How the chunked loss's backward rule cuts the ``[tokens, vocab]`` logits it recomputes
    (:func:`plan_loss_backward`). Static and hashable: it is resolved where the forward is
    traced — a `custom_vjp`'s backward rule is traced later, outside the model's
    logical-axis rules — and handed to the rule as a non-differentiable argument."""

    token_blocks: int  # outer loop: blocks of whole forward chunks (every batch row of each)
    vocab_tiles: int  # inner scan: tiles of the vocabulary
    vocab_shards: int  # shards of "act_vocab"; a tile takes `tile_rows` rows of EVERY shard
    tile_rows: int
    batch_axes: tuple | str | None  # mesh axes of "act_batch" / "act_vocab" for the rule's
    vocab_axes: tuple | str | None  # constraints; `constrain` False: no mesh, no constraint
    constrain: bool

    def logits_tile(self, batch: int, n_chunks: int, chunk: int) -> tuple[int, ...]:
        """Shape of the logits the backward rule holds at a time (the forward holds
        ``[batch, chunk, V]``): ``[chunks a block, batch, chunk, shards, tile_rows]``."""
        return (n_chunks // self.token_blocks, batch, chunk, self.vocab_shards, self.tile_rows)


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def plan_loss_backward(
    batch: int, n_chunks: int, chunk: int, vocab: int, hidden_size: int
) -> tuple[LossTiling, dict]:
    """Choose the backward rule's tiling from the shapes the loss sees, and price it.

    The rule has two gradients to accumulate: the table's ``[V, H]``, summed over tokens,
    and the hidden states' ``[T, H]``, summed over the vocabulary. A loop over token blocks
    carries the first, a loop over vocabulary tiles the second, and a carried float32
    accumulator is read and written once an iteration. With the live logits held at the
    forward's budget (``batch x chunk x V`` elements: ``token_blocks x vocab_tiles >=
    n_chunks``) the float32 bytes a device moves for its accumulators are about

        token_blocks x 2 x V_local x H x 4   +   vocab_tiles x 2 x T_local x H x 4

    (once, not twice, for a loop of one iteration: nothing is carried). The plan is the
    pair that makes this smallest over the divisors of ``n_chunks``; one packed row of 4096
    tokens against a 49152-row table gives 1 x 16 (tiles of 3072 rows), four rows a device
    with the table over tp 4 gives 4 x 4. Local sizes follow the ambient mesh and rules:
    tokens are sharded as "act_batch", the vocabulary as "act_vocab", and a tile is cut
    INSIDE each vocabulary shard's rows (never across shards: the partitioner would gather
    the table every tile). A vocabulary the tile count does not divide is padded with rows
    that the rule masks out of the softmax.

    Returns the tiling and the record of it the telemetry event ``loss_tiling`` carries.
    """
    from ..parallel.sharding import logical_spec

    batch_axes = vocab_axes = None
    batch_shards = vocab_shards = 1
    resolved = logical_spec((batch, vocab), ("act_batch", "act_vocab"))
    if resolved is not None:
        mesh, (batch_axes, vocab_axes) = resolved

        def size(axes) -> int:
            names = () if axes is None else (axes,) if isinstance(axes, str) else axes
            return math.prod(mesh.shape[a] for a in names)

        batch_shards, vocab_shards = size(batch_axes), size(vocab_axes)
    tokens_local = batch * n_chunks * chunk // batch_shards
    vocab_local = vocab // vocab_shards

    def accumulator_elements(token_blocks: int) -> int:
        vocab_tiles = min(-(-n_chunks // token_blocks), vocab_local)
        table_trips = 1 if token_blocks == 1 else 2 * token_blocks
        hidden_trips = 1 if vocab_tiles == 1 else 2 * vocab_tiles
        return table_trips * vocab_local + hidden_trips * tokens_local

    token_blocks = min(_divisors(n_chunks), key=accumulator_elements)
    vocab_tiles = min(-(-n_chunks // token_blocks), vocab_local)
    tile_rows = -(-vocab_local // vocab_tiles)
    if vocab_local % vocab_tiles and tile_rows > 128:
        tile_rows = -(-tile_rows // 128) * 128  # padded anyway: keep the tile lane-aligned
        vocab_tiles = -(-vocab_local // tile_rows)
    tiling = LossTiling(
        token_blocks, vocab_tiles, vocab_shards, tile_rows, batch_axes, vocab_axes,
        resolved is not None,
    )
    record = dict(
        token_blocks=token_blocks,
        vocab_tiles=vocab_tiles,
        tile_rows=tile_rows,
        vocab_shards=vocab_shards,
        tokens_per_device=tokens_local,
        # float32 bytes of the accumulators the rule's loops carry, a device
        hidden_carry_bytes=4 * hidden_size * tokens_local // token_blocks if vocab_tiles > 1 else 0,
        table_carry_bytes=4 * hidden_size * vocab_tiles * tile_rows if token_blocks > 1 else 0,
        accumulator_bytes_moved=4 * hidden_size * accumulator_elements(token_blocks),
    )
    return tiling, record


@jax.named_scope("ce_chunk")  # a scan body starts with no name of its own in a profile
def _chunk_ce_terms(
    h: jax.Array,
    table: jax.Array,
    y: jax.Array,
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    want_z: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One chunk's LM-head matmul + CE reduction, XLA reference lowering: ``(loss_sum,
    z_sum, num_tokens, lse)`` with ``lse`` the chunk's per-token log-sum-exp in float32.

    The chunk's ``[B, chunk, V]`` logits exist only inside this function.
    """
    return _ce_terms(_chunk_logits(h, table, logit_scale, compute_dtype), y, upcast, want_z, with_lse=True)


def _chunk_logits(h: jax.Array, table: jax.Array, logit_scale: float | None, compute_dtype) -> jax.Array:
    """One chunk's ``[B, chunk, V]`` logits, XLA reference lowering."""
    from ..parallel.sharding import logical_constraint

    # The table arrives pinned to its ACTIVATION layout (`fused_linear_cross_entropy`);
    # pinned again INSIDE the per-chunk body: without this boundary the partitioner has
    # propagated a ZeRO-3 table's fsdp-sharded vocabulary into the chunk's log_softmax,
    # where it collides with the batch-sharded logits constraint below — XLA then falls
    # back to "involuntary full rematerialization" (full replication) of the logits.
    table = logical_constraint(table, ("act_vocab", None))
    logits = jnp.dot(h.astype(compute_dtype), table.T)
    # keep the CE vocab-parallel ("act_vocab" -> tp) instead of all-gathering the table
    # per chunk. The chunk-local seq axis stays UNSHARDED (None, not "act_seq"): the
    # S -> (n_chunks, chunk) reshape already broke any sp sharding, and re-claiming
    # "act_seq" here forces an SPMD reshard of every chunk on sp>1 meshes.
    logits = logical_constraint(logits, ("act_batch", None, "act_vocab"))
    if logit_scale is not None:
        logits = logits * logit_scale
    return logits


@jax.named_scope("ce_chunk")
def _chunk_token_terms(
    h: jax.Array, table: jax.Array, y: jax.Array, logit_scale: float | None, upcast: bool, compute_dtype
) -> tuple[jax.Array, jax.Array]:
    """One chunk's PER-TOKEN ``(loss, lse)``, both ``[B, chunk]`` float32: the token's
    cross-entropy (0 on an IGNORE_INDEX row) and its log-sum-exp."""
    logits = _chunk_logits(h, table, logit_scale, compute_dtype)
    if upcast:
        logits = logits.astype(jnp.float32)
    mask = y != IGNORE_INDEX
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.where(mask, y, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(mask, lse - picked, 0.0).astype(jnp.float32), lse.astype(jnp.float32)


def _chunked_ce_forward(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z):
    """The forward scan over chunks: ``((loss_sum, z_sum, num_tokens), lse_c)`` with
    ``lse_c [n_chunks, B, chunk]`` the float32 log-sum-exp of every token — all the
    backward rule keeps of the logits."""
    from ..ops.pallas import use_pallas

    if use_pallas("fused_ce"):
        from ..ops.pallas.fused_ce import fused_ce_chunk

        def chunk_terms(h, y):
            return fused_ce_chunk(
                h, table, y, logit_scale=logit_scale, upcast=upcast,
                compute_dtype=compute_dtype, return_lse=True,
            )
    else:

        def chunk_terms(h, y):
            return _chunk_ce_terms(h, table, y, logit_scale, upcast, compute_dtype, want_z)

    def body(carry, xs):
        loss_sum, z_sum, num, lse = chunk_terms(*xs)
        return (carry[0] + loss_sum, carry[1] + z_sum, carry[2] + num), lse

    zero = jnp.zeros((), jnp.float32)
    with jax.named_scope("loss_chunks"):
        return jax.lax.scan(body, (zero, zero, zero), (hidden_c, labels_c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_ce_terms(
    hidden_c: jax.Array,  # [n_chunks, B, chunk, H]
    labels_c: jax.Array,  # [n_chunks, B, chunk]
    table: jax.Array,  # [V, H] in compute dtype
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    want_z: bool,
    tiling: LossTiling,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(loss_sum, z_sum, num_tokens) over all chunks; at most one chunk's logits live.

    Forward: a scan over sequence chunks (`_chunked_ce_forward`). The ``fused_ce`` kernel
    family dispatches there: on Pallas the per-chunk reduction runs
    `ops/pallas/fused_ce.fused_ce_chunk` (vocab-tiled online logsumexp — the chunk logits
    never leave VMEM); the XLA reference scans `_chunk_ce_terms`.

    Backward: ONE rule for both backends (`_chunked_ce_terms_bwd`), so gradients depend on
    the forward's backend only through the log-sum-exp it saved (1-2 float32 ulp apart).
    Residuals are ``(hidden, labels, table, lse)``: the inputs plus one float per token,
    nothing logits-sized. The rule recomputes the logits a tile at a time, forms
    ``softmax - onehot`` from the saved log-sum-exp (no second reduction over the
    vocabulary) and runs the two gradient matmuls. Which accumulator its loops carry is
    `tiling`'s choice (:func:`plan_loss_backward`): the inner scan runs over vocabulary
    tiles and carries the hidden states' gradient of one token block — each tile's table
    gradient leaves one matmul that contracts over the block's every token, accumulated in
    float32 inside the MXU as the unchunked reference's is, and is written once (the
    scan's `ys`); only where one block may not hold all tokens does an outer loop over
    token blocks carry the table's float32 gradient, once a block. (Before PR 25 the rule
    scanned the forward's chunks and carried the whole float32 ``[V, H]`` gradient through
    HBM once per `chunk` tokens: 1 GB a chunk at V 49152, H 2560.)
    """
    return _chunked_ce_forward(
        hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z
    )[0]


def _chunked_ce_terms_fwd(
    hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z, tiling
):
    terms, lse_c = _chunked_ce_forward(
        hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, want_z
    )
    # O(B*S*H + V*H + B*S): nothing logits-sized is saved
    return terms, (hidden_c, labels_c, table, lse_c)


@jax.named_scope("ce_tile")  # the backward scan's body: one vocabulary tile of one token block
def _tile_grads(
    h: jax.Array,  # [c, B, chunk, H] compute dtype: a token block (c forward chunks)
    y: jax.Array,  # [c, B, chunk]
    lse: jax.Array,  # [c, B, chunk] float32, saved by the forward
    softmax_coef: jax.Array,  # [c, B, chunk]: cotangent of a token's softmax row ...
    label_coef: jax.Array,  # ... and of its label's logit; both 0 on IGNORE_INDEX rows
    w: jax.Array,  # [shards, tile_rows, H] compute dtype: the tile's rows of every shard
    vocab_ids: jax.Array,  # [shards, tile_rows] int32; -1 on padded rows
    logit_scale: float | None,
    upcast: bool,
    constrain,
) -> tuple[jax.Array, jax.Array]:
    """One tile's ``(d hidden [shards, c, B, chunk, H]`` — a partial sum a vocabulary shard —
    ``, d tile [shards, tile_rows, H])``, both float32: the logits recomputed as the
    forward computed them, ``d logits = softmax_coef x softmax - label_coef x onehot`` in
    the precision `upcast` states, and the two gradient matmuls on compute-dtype operands
    with float32 accumulation."""
    with jax.named_scope("logits"):
        logits = jax.lax.dot_general(h, w, (((3,), (2,)), ((), ())))  # [c, B, chunk, g, v]
    logits = constrain(logits, "logits")
    if logit_scale is not None:
        logits = logits * logit_scale
    if upcast:
        logits = logits.astype(jnp.float32)
    col = (..., None, None)
    lse, softmax_coef, label_coef = (
        x.astype(logits.dtype)[col] for x in (lse, softmax_coef, label_coef)
    )
    dlogits = jnp.exp(logits - lse) * softmax_coef - jnp.where(vocab_ids == y[col], label_coef, 0)
    dlogits = jnp.where(vocab_ids >= 0, dlogits, 0).astype(h.dtype)
    if logit_scale is not None:
        dlogits = dlogits * logit_scale
    dlogits = constrain(dlogits, "logits")
    with jax.named_scope("grad_hidden"):
        # the vocabulary shard is a BATCH dimension: each shard keeps the partial sum over
        # its own rows, and the shards are summed once, after the scan — contracted here,
        # every tile would end in an all-reduce of the block's d hidden over tp
        dh = jax.lax.dot_general(
            dlogits, w, (((4,), (1,)), ((3,), (0,))), preferred_element_type=jnp.float32
        )  # [g, c, B, chunk, H]
    with jax.named_scope("grad_table"):
        dw = jax.lax.dot_general(
            dlogits, h, (((0, 1, 2), (0, 1, 2)), ((), ())), preferred_element_type=jnp.float32
        )
    return constrain(dh, "hidden"), constrain(dw, "tile")


def _chunked_ce_terms_bwd(logit_scale, upcast, compute_dtype, want_z, tiling, residuals, cts):
    def coefficients(valid, lse_c):
        # d(loss_sum)/d(logits) = softmax - onehot and d(z_sum)/d(logits) = 2 lse softmax on
        # valid rows (z_sum = sum lse^2); num_tokens has no gradient. Without `upcast` the
        # forward's terms are compute-dtype: the coefficients are cast where they are used
        ct_loss, ct_z = (ct.astype(jnp.float32) for ct in cts[:2])
        label_coef = jnp.where(valid, ct_loss, 0.0)
        softmax_coef = label_coef
        if want_z:
            softmax_coef = jnp.where(valid, ct_loss + 2.0 * ct_z * lse_c, 0.0)
        return softmax_coef, label_coef

    return _chunked_ce_grads(residuals, coefficients, logit_scale, upcast, tiling)


def _chunked_ce_grads(residuals, coefficients, logit_scale, upcast, tiling):
    """The backward rule's walk, for the summed terms and the per-token ones alike:
    ``(d hidden_c, None, d table)`` from the residuals and ``coefficients(valid, lse_c) ->
    (softmax_coef, label_coef)``, each ``[n_chunks, B, chunk]`` float32 and 0 on IGNORE_INDEX
    rows: ``d logits = softmax_coef x softmax - label_coef x onehot`` a token."""
    hidden_c, labels_c, table, lse_c = residuals
    n_chunks, _, _, hidden_size = hidden_c.shape
    blocks, tiles = tiling.token_blocks, tiling.vocab_tiles
    shards, rows = tiling.vocab_shards, tiling.tile_rows
    vocab_local = table.shape[0] // shards

    batch_axes, vocab_axes = tiling.batch_axes, tiling.vocab_axes
    specs = {
        "hidden": PartitionSpec(vocab_axes, None, batch_axes, None, None),
        "logits": PartitionSpec(None, batch_axes, None, vocab_axes, None),
        "tile": PartitionSpec(vocab_axes, None, None),
        "tiles": PartitionSpec(None, vocab_axes, None, None),
        "table": PartitionSpec(vocab_axes, None),
    }

    def constrain(x, what):
        return jax.lax.with_sharding_constraint(x, specs[what]) if tiling.constrain else x

    # the table as the forward pinned it, in its ACTIVATION layout (`fused_linear_cross_
    # entropy`: the gather is the forward's), as [tiles, shards, rows, H]: tile j holds
    # rows [j*rows, (j+1)*rows) of EVERY vocabulary shard, so a tile is cut inside each shard
    w = constrain(table, "table").reshape(shards, vocab_local, hidden_size)
    w = jnp.pad(w, ((0, 0), (0, tiles * rows - vocab_local), (0, 0)))
    w_tiles = constrain(jnp.moveaxis(w.reshape(shards, tiles, rows, hidden_size), 1, 0), "tiles")
    row = jnp.arange(rows, dtype=jnp.int32)
    shard_start = vocab_local * jnp.arange(shards, dtype=jnp.int32)[:, None]

    softmax_coef, label_coef = coefficients(labels_c != IGNORE_INDEX, lse_c)

    def block_grads(h, y, lse, s_coef, l_coef):
        """One token block: scan the vocabulary tiles, carrying the block's d hidden."""

        def body(dh_acc, xs):
            j, w_tile = xs
            local = j * rows + row  # row index inside a shard; >= vocab_local: padding
            vocab_ids = jnp.where(local < vocab_local, shard_start + local, -1)
            dh, dw = _tile_grads(
                h, y, lse, s_coef, l_coef, constrain(w_tile, "tile"), vocab_ids,
                logit_scale, upcast, constrain,
            )
            return dh_acc + dh, dw

        dh, dw_tiles = jax.lax.scan(
            body,
            constrain(jnp.zeros((shards, *h.shape), jnp.float32), "hidden"),
            (jnp.arange(tiles, dtype=jnp.int32), w_tiles),
        )
        return dh.sum(axis=0).astype(hidden_c.dtype), dw_tiles

    # a custom_vjp's backward rule is traced under the scopes of the call (the model's
    # `head_loss`) but not under those its forward opened: it opens the forward's own, so
    # that a profile tells the two scans apart by JAX's `transpose(...)` wrapper alone
    with jax.named_scope("loss_chunks"):
        per_block = [
            x.reshape(blocks, n_chunks // blocks, *x.shape[1:])
            for x in (hidden_c, labels_c, lse_c, softmax_coef, label_coef)
        ]
        if blocks == 1:
            dh, dw_tiles = block_grads(*(x[0] for x in per_block))
        else:
            # more tokens than one block may hold: the table's float32 gradient is carried,
            # once a block instead of once a chunk
            def outer(dw_acc, xs):
                dh, dw_tiles = block_grads(*xs)
                return dw_acc + dw_tiles, dh

            with jax.named_scope("token_blocks"):
                dw_tiles, dh = jax.lax.scan(
                    outer, constrain(jnp.zeros(w_tiles.shape, jnp.float32), "tiles"), per_block
                )
        dtable = jnp.moveaxis(dw_tiles, 0, 1).reshape(shards, tiles * rows, hidden_size)
        dtable = dtable[:, :vocab_local].reshape(table.shape).astype(table.dtype)
        return dh.reshape(hidden_c.shape), None, constrain(dtable, "table")


_chunked_ce_terms.defvjp(_chunked_ce_terms_fwd, _chunked_ce_terms_bwd)


def _chunked_ce_token_forward(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype):
    """The forward scan over chunks that keeps every token's terms apart: ``(loss_c, lse_c)``,
    both ``[n_chunks, B, chunk]`` float32."""
    from ..ops.pallas import use_pallas

    if use_pallas("fused_ce"):
        # the kernel's own per-row (log-sum-exp, label's logit): nothing of it to step aside
        from ..ops.pallas.fused_ce import fused_ce_rowwise

        def chunk_terms(h, y):
            lse, picked = fused_ce_rowwise(
                h.reshape(-1, h.shape[-1]), table, y.reshape(-1), logit_scale=logit_scale, compute_dtype=compute_dtype
            )
            return jnp.where(y != IGNORE_INDEX, (lse - picked).reshape(y.shape), 0.0), lse.reshape(y.shape)
    else:

        def chunk_terms(h, y):
            return _chunk_token_terms(h, table, y, logit_scale, upcast, compute_dtype)

    with jax.named_scope("loss_chunks"):
        return jax.lax.map(lambda xs: chunk_terms(*xs), (hidden_c, labels_c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_ce_token_terms(
    hidden_c: jax.Array,  # [n_chunks, B, chunk, H]
    labels_c: jax.Array,  # [n_chunks, B, chunk]
    table: jax.Array,  # [V, H] in compute dtype
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    tiling: LossTiling,
) -> tuple[jax.Array, jax.Array]:
    """`_chunked_ce_terms` with nothing summed over tokens: every token's cross-entropy and
    log-sum-exp, so that a caller may weigh tokens (and learn the weights: the weight's
    gradient is the token's own term, which autodiff of the caller's product gives). The same
    residuals, the same backward walk (`_chunked_ce_grads`); a token's cotangents take the
    place the summed rule's scalars had."""
    return _chunked_ce_token_terms_fwd(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, tiling)[0]


def _chunked_ce_token_terms_fwd(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, tiling):
    loss_c, lse_c = _chunked_ce_token_forward(hidden_c, labels_c, table, logit_scale, upcast, compute_dtype)
    # what is handed out is 0 on a row without a label, as its gradient is; the rule keeps the row's own
    return (loss_c, jnp.where(labels_c != IGNORE_INDEX, lse_c, 0.0)), (hidden_c, labels_c, table, lse_c)


def _chunked_ce_token_terms_bwd(logit_scale, upcast, compute_dtype, tiling, residuals, cts):
    def coefficients(valid, lse_c):
        # d(loss_i)/d(logits_i) = softmax - onehot, d(lse_i)/d(logits_i) = softmax
        ct_loss, ct_lse = (ct.astype(jnp.float32) for ct in cts)
        return jnp.where(valid, ct_loss + ct_lse, 0.0), jnp.where(valid, ct_loss, 0.0)

    return _chunked_ce_grads(residuals, coefficients, logit_scale, upcast, tiling)


_chunked_ce_token_terms.defvjp(_chunked_ce_token_terms_fwd, _chunked_ce_token_terms_bwd)


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
    weights: jax.Array | None = None,
) -> jax.Array:
    """LM-head matmul + CE without ever materializing the [B, S, V] logits.

    Forward: the sequence axis is cut into chunks of `chunk_size`; a `lax.scan` computes
    each chunk's logits ([B, chunk, V]), reduces them to (loss_sum, z_sum, count) and the
    tokens' log-sum-exp, and discards them. The whole reduction sits behind a `custom_vjp`
    whose residuals are (hidden, labels, table) and that one float per token.

    Backward: the rule recomputes logits under the same budget — `chunk_size` x V elements
    a batch row — but cuts them the other way where that is cheaper: all tokens (or a
    block of them) against a TILE of the vocabulary, so that the loop carries the hidden
    states' float32 gradient ([tokens, H]) instead of the table's ([V, H], read and
    written once an iteration). The tiling follows the shapes — tokens and vocabulary rows
    a device holds, `chunk_size` — through :func:`plan_loss_backward`; there is no knob.
    When a telemetry is installed the choice is written once as a ``loss_tiling`` event.

    Peak logits memory is O(chunk) in both directions and drops S/chunk_size-fold (at seq
    2048 / vocab 50k the full tensor is the single largest allocation in a train step).
    The reference has no counterpart (it materializes logits and calls F.cross_entropy,
    `model_wrapper/pretraining.py:89-127`); this is the TPU/HBM-side answer to that cost
    — the same move as Liger-kernel's chunked fused CE on GPU.

    With the ``fused_ce`` kernel family on Pallas the per-chunk forward reduction
    additionally runs as a vocab-tiled online-logsumexp kernel (`ops/pallas/fused_ce.py`)
    whose logits tiles never leave VMEM; the backward rule is the same for both (see
    `_chunked_ce_terms`).

    hidden: [B, S, H]; embedding: [V, H] (tied-embedding layout); labels: [B, S] with
    IGNORE_INDEX. Chunking is along sequence, so dp/fsdp/ep batch sharding is untouched.
    ``z_loss_coef`` adds ``coef * mean(logsumexp^2)`` exactly like `causal_lm_loss`.

    ``weights`` ([B, S], differentiable) weighs every token's terms: the loss is
    ``sum_i w_i (l_i + coef lse_i^2) / count of labels``, the gradients of the hidden states
    and of the table are scaled token by token by ``w_i``, and ``d loss / d w_i`` is the
    token's own term over the count (`fused_linear_token_cross_entropy`: the same chunks,
    the same backward walk, the tokens' terms kept apart). Without weights nothing of that
    is traced: the program is what it was (tests/ops/test_loss_token_weights.py holds its
    jaxpr's hash).
    """
    if weights is not None:
        token_loss, lse = fused_linear_token_cross_entropy(
            hidden, embedding, labels, chunk_size=chunk_size, upcast=upcast, logit_scale=logit_scale, compute_dtype=compute_dtype
        )
        if z_loss_coef != 0.0:
            token_loss = token_loss + z_loss_coef * jnp.square(lse)
        valid = labels != IGNORE_INDEX
        weighed = jnp.sum(jnp.where(valid, weights.astype(jnp.float32) * token_loss, 0.0))
        return weighed / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)

    hidden_c, labels_c, emb, tiling = _chunked_operands(hidden, embedding, labels, chunk_size, compute_dtype)
    loss_sum, z_sum, num_tokens = _chunked_ce_terms(
        hidden_c, labels_c, emb, logit_scale, upcast, compute_dtype, z_loss_coef != 0.0, tiling
    )
    denom = jnp.maximum(num_tokens, 1.0)
    loss = loss_sum / denom
    if z_loss_coef != 0.0:
        loss = loss + z_loss_coef * (z_sum / denom)
    return loss


def fused_linear_token_cross_entropy(
    hidden: jax.Array,
    embedding: jax.Array,
    labels: jax.Array,
    *,
    chunk_size: int = 256,
    upcast: bool = True,
    logit_scale: float | None = None,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array]:
    """`fused_linear_cross_entropy` before its sum over tokens: ``(loss, lse)``, both
    ``[B, S]`` float32 — every token's cross-entropy and its logits' log-sum-exp, both 0 (and
    without a gradient) where the token's label is IGNORE_INDEX — with the logits as short-lived
    as there, forward and backward. For a loss that weighs tokens by something that learns
    (a looped model's exit gate: `models/ouro.py`)."""
    S = hidden.shape[1]
    hidden_c, labels_c, emb, tiling = _chunked_operands(hidden, embedding, labels, chunk_size, compute_dtype)
    loss_c, lse_c = _chunked_ce_token_terms(hidden_c, labels_c, emb, logit_scale, upcast, compute_dtype, tiling)
    rows = lambda x: x.swapaxes(0, 1).reshape(x.shape[1], -1)[:, :S]  # noqa: E731
    return rows(loss_c), rows(lse_c)


def _chunked_operands(hidden, embedding, labels, chunk_size: int, compute_dtype):
    """``(hidden_c [n_chunks, B, chunk, H], labels_c [n_chunks, B, chunk], the table in its
    activation layout, the backward rule's tiling)`` of one call of the chunked loss."""
    from ..parallel.sharding import logical_constraint
    from ..utils.telemetry import get_telemetry

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

    # Pin the table to its ACTIVATION layout (vocab over tp only; replicated otherwise)
    # once, here: under ZeRO-3 the tied table arrives fsdp-sharded, and both scans and the
    # backward rule (whose residual this is: it gathers nothing again) compute with the
    # gathered one — ZeRO-3's gather/compute/scatter contract, the scatter being the
    # transpose of this constraint.
    emb = logical_constraint(embedding.astype(compute_dtype), ("act_vocab", None))
    tiling, record = plan_loss_backward(B, n_chunks, chunk_size, emb.shape[0], H)
    get_telemetry().event_once("loss_tiling", **record)
    return hidden_c, labels_c, emb, tiling


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
