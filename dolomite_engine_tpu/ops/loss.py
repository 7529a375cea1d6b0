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


def cross_entropy_terms(
    logits: jax.Array,
    labels: jax.Array,
    upcast: bool = True,
    want_z: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Token-level CE reduced to (loss_sum, z_sum, num_tokens).

    ``z_sum`` is the PaLM-style z-loss numerator — sum over valid tokens of
    ``logsumexp(logits)^2`` — computed only when `want_z` (an extra reduction over the
    vocab axis otherwise). The single formula shared by the unchunked loss and the chunk
    scan, so their parity is summation-order-only (1-2 float32 ulp).
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
        lse = jax.scipy.special.logsumexp(logits, axis=-1).astype(jnp.float32)
        z_sum = jnp.sum(jnp.where(mask, jnp.square(lse), 0.0))
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


class LossTiling(NamedTuple):
    """How the PER-TOKEN rule's backward (`_chunked_ce_token_terms`: the one rule that still
    recomputes) cuts the ``[tokens, vocab]`` logits it forms again (:func:`plan_loss_backward`).
    Static and hashable: it is resolved where the forward is traced — a `custom_vjp`'s
    backward rule is traced later, outside the model's logical-axis rules — and handed to
    the rule as a non-differentiable argument. The summed rule's plan is :class:`LossBlocks`."""

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


class LossBlocks(NamedTuple):
    """How the SUMMED rule's differentiated forward (`_chunked_ce_terms_fwd`) walks the
    tokens (:func:`plan_loss_blocks`): `token_blocks` blocks of whole forward chunks, each
    against the whole local vocabulary, its logits kept for the length of the block's body.
    Static and hashable, resolved where the forward is traced, as :class:`LossTiling` is."""

    token_blocks: int
    batch_axes: tuple | str | None  # as `LossTiling`'s
    vocab_axes: tuple | str | None
    constrain: bool

    def logits_block(self, batch: int, n_chunks: int, chunk: int, vocab: int) -> tuple[int, ...]:
        """Shape of the logits a block keeps: ``[chunks a block, batch, chunk, V]``."""
        return (n_chunks // self.token_blocks, batch, chunk, vocab)


# What one token block's kept logits may take of a device's memory, in bytes: one packed row
# of 4096 tokens against a 49152-row table in bfloat16 — the flagship's own head, the dense
# benchmark cells' whole step — 4096 x 49152 x 2 = 384 MiB. Keeping a block costs 6 bytes an
# element of HBM traffic (a write, two reads: 7 ps at a v5e's 819 GB/s); forming it again
# costs 2 x H flops an element (26 ps at H 2560, 42 ps at H 4096, at peak), and the dense
# cells read 11.8 of 15.75 GiB (PR 38's ledger lines). More tokens than the budget holds take
# more blocks, and only then is anything carried (the table's float32 gradient, once a block).
_KEPT_LOGITS_BYTES = 4096 * 49152 * 2


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _loss_shards(batch: int, vocab: int) -> tuple[tuple | str | None, tuple | str | None, int, int, bool]:
    """``(batch_axes, vocab_axes, batch_shards, vocab_shards, under a mesh)`` of the chunked
    loss's logits under the ambient mesh and rules: tokens are sharded as "act_batch", the
    vocabulary as "act_vocab"."""
    from ..parallel.sharding import logical_spec

    resolved = logical_spec((batch, vocab), ("act_batch", "act_vocab"))
    if resolved is None:
        return None, None, 1, 1, False
    mesh, (batch_axes, vocab_axes) = resolved

    def size(axes) -> int:
        names = () if axes is None else (axes,) if isinstance(axes, str) else axes
        return math.prod(mesh.shape[a] for a in names)

    return batch_axes, vocab_axes, size(batch_axes), size(vocab_axes), True


def plan_loss_backward(
    batch: int, n_chunks: int, chunk: int, vocab: int, hidden_size: int
) -> tuple[LossTiling, dict]:
    """Choose the PER-TOKEN rule's backward tiling from the shapes the loss sees, and price
    it (the summed rule recomputes nothing: :func:`plan_loss_blocks`).

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

    Returns the tiling and the record of it the telemetry event ``loss_tiling`` carries
    (``logits_products`` 2: the forward's and the rule's own).
    """
    batch_axes, vocab_axes, batch_shards, vocab_shards, constrain = _loss_shards(batch, vocab)
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
        token_blocks, vocab_tiles, vocab_shards, tile_rows, batch_axes, vocab_axes, constrain
    )
    record = dict(
        logits_products=2,
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


def plan_loss_blocks(
    batch: int, n_chunks: int, chunk: int, vocab: int, hidden_size: int, logits_itemsize: int
) -> tuple[LossBlocks, dict]:
    """Choose the SUMMED rule's token blocks from the shapes the loss sees, and price them.

    The differentiated forward keeps one block's logits — ``[tokens a block, V_local]`` in
    the compute dtype, `logits_itemsize` bytes an element, a device — from the product that
    forms them to the two gradient products that consume them, under `_KEPT_LOGITS_BYTES`.
    A single block carries nothing: both gradients leave their products once. More tokens
    than one block holds take the fewest blocks (a divisor of ``n_chunks``: a block is whole
    forward chunks, every batch row of each) that fit, and an outer loop carries the table's
    float32 gradient, read and written once a block: ``token_blocks x 2 x V_local x H x 4``
    bytes, so the fewest blocks also move the fewest accumulator bytes. The vocabulary is
    not tiled (one product contracts a device's whole share of it) and no hidden-state
    accumulator exists. Where even one chunk's logits pass the budget a block is one chunk:
    what the undifferentiated scan holds anyway. Local sizes follow the ambient mesh and
    rules as in :func:`plan_loss_backward`.

    Returns the plan and the record of it the telemetry event ``loss_tiling`` carries
    (``logits_products`` 1: nothing is formed again).
    """
    batch_axes, vocab_axes, batch_shards, vocab_shards, constrain = _loss_shards(batch, vocab)
    tokens_local = batch * n_chunks * chunk // batch_shards
    vocab_local = vocab // vocab_shards

    def kept_bytes(token_blocks: int) -> int:
        return tokens_local // token_blocks * vocab_local * logits_itemsize

    token_blocks = next((d for d in _divisors(n_chunks) if kept_bytes(d) <= _KEPT_LOGITS_BYTES), n_chunks)
    table_trips = 1 if token_blocks == 1 else 2 * token_blocks
    record = dict(
        logits_products=1,
        token_blocks=token_blocks,
        vocab_shards=vocab_shards,
        tokens_per_device=tokens_local,
        kept_logits_bytes=kept_bytes(token_blocks),
        # float32 bytes of the one accumulator the outer loop carries, a device
        table_carry_bytes=4 * hidden_size * vocab_local if token_blocks > 1 else 0,
        accumulator_bytes_moved=4 * hidden_size * (table_trips * vocab_local + tokens_local),
    )
    return LossBlocks(token_blocks, batch_axes, vocab_axes, constrain), record


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
    """One chunk's LM-head matmul + CE reduction, XLA reference lowering: ``(loss_sum,
    z_sum, num_tokens)``. The chunk's ``[B, chunk, V]`` logits exist only inside this
    function."""
    return cross_entropy_terms(_chunk_logits(h, table, logit_scale, compute_dtype), y, upcast, want_z)


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
    """The scan over chunks of the undifferentiated call: ``(loss_sum, z_sum, num_tokens)``."""
    from ..ops.pallas import use_pallas

    if use_pallas("fused_ce"):
        from ..ops.pallas.fused_ce import fused_ce_chunk

        def chunk_terms(h, y):
            return fused_ce_chunk(
                h, table, y, logit_scale=logit_scale, upcast=upcast, compute_dtype=compute_dtype
            )
    else:

        def chunk_terms(h, y):
            return _chunk_ce_terms(h, table, y, logit_scale, upcast, compute_dtype, want_z)

    def body(carry, xs):
        return tuple(a + t for a, t in zip(carry, chunk_terms(*xs))), None

    zero = jnp.zeros((), jnp.float32)
    with jax.named_scope("loss_chunks"):
        return jax.lax.scan(body, (zero, zero, zero), (hidden_c, labels_c))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_ce_terms(
    hidden_c: jax.Array,  # [n_chunks, B, chunk, H]
    labels_c: jax.Array,  # [n_chunks, B, chunk]
    table: jax.Array,  # [V, H] in compute dtype
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    z_loss_coef: float,
    blocks: LossBlocks,
) -> tuple[jax.Array, jax.Array]:
    """``(loss_sum + z_loss_coef x z_sum, num_tokens)`` over all tokens: the SUMMED rule. The
    coefficient is inside so that one gradient serves both terms; `num_tokens` has none.

    Undifferentiated (evaluation, `jax.eval_shape`, this primal): a scan over sequence
    chunks (`_chunked_ce_forward`), at most one chunk's logits live. The ``fused_ce`` kernel
    family dispatches there: on Pallas the per-chunk reduction runs
    `ops/pallas/fused_ce.fused_ce_chunk` (vocab-tiled online logsumexp — the chunk logits
    never leave VMEM); the XLA reference scans `_chunk_ce_terms`. It forms no gradient and
    needs no block of logits.

    Differentiated (`_chunked_ce_terms_fwd`): the head's logits are computed ONCE. The
    forward walks token blocks (`blocks`, :func:`plan_loss_blocks`); a block's
    ``[tokens, V]`` logits leave one product in the compute dtype and are kept for the
    length of the block's body — under `_KEPT_LOGITS_BYTES` a device — which reduces them
    to the block's terms (a token's log-sum-exp minus its label's logit, as the per-token
    forward has it: 1 ulp a token from the scan's log-softmax), forms ``d logits = softmax_coef x
    softmax - label_coef x onehot`` for a UNIT cotangent (`_softmax_minus_onehot`, the
    per-token rule's own expression) and runs the two gradient products with float32
    accumulation. Residuals are the gradients themselves: ``d hidden`` (the size and dtype
    of `hidden_c`, which is no residual any more) and ``d table`` (the table's dtype);
    nothing logits-sized, no log-sum-exp. One block carries nothing; several carry the
    table's float32 gradient once a block. The backward rule (`_chunked_ce_terms_bwd`) only
    scales both by the cotangent. (PR 25 to PR 38 kept the log-sum-exp alone and formed
    every logit again in the backward rule — a fourth product beside the three a head
    needs — because the budget for live logits was one chunk's; the per-token rule,
    `_chunked_ce_token_terms`, still does: its cotangents are a token's own and do not
    exist when the forward runs.)
    """
    loss_sum, z_sum, num_tokens = _chunked_ce_forward(
        hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, z_loss_coef != 0.0
    )
    return _objective(loss_sum, z_sum, z_loss_coef), num_tokens


def _objective(loss_sum: jax.Array, z_sum: jax.Array, z_loss_coef: float) -> jax.Array:
    return loss_sum + z_loss_coef * z_sum if z_loss_coef != 0.0 else loss_sum


def _softmax_minus_onehot(
    logits: jax.Array,  # [*tokens, *vocab]: scaled, in the dtype the product left them
    y: jax.Array,  # [*tokens]
    lse: jax.Array,  # [*tokens] float32
    softmax_coef: jax.Array,  # [*tokens]: cotangent of a token's softmax row ...
    label_coef: jax.Array,  # ... and of its label's logit; both 0 on IGNORE_INDEX rows
    vocab_ids: jax.Array,  # [*vocab] int32: the vocabulary row of a logit
    upcast: bool,
) -> jax.Array:
    """``softmax_coef x exp(logits - lse) - label_coef x onehot`` in the precision `upcast`
    states: the gradient of a token's terms with respect to its logits, for both rules."""
    if upcast:
        logits = logits.astype(jnp.float32)
    col = (..., *(None,) * vocab_ids.ndim)
    lse, softmax_coef, label_coef = (
        x.astype(logits.dtype)[col] for x in (lse, softmax_coef, label_coef)
    )
    return jnp.exp(logits - lse) * softmax_coef - jnp.where(vocab_ids == y[col], label_coef, 0)


def _chunked_ce_terms_fwd(
    hidden_c, labels_c, table, logit_scale, upcast, compute_dtype, z_loss_coef, blocks
):
    n_chunks = hidden_c.shape[0]
    want_z = z_loss_coef != 0.0
    specs = {
        "hidden": PartitionSpec(None, blocks.batch_axes, None, None),
        "logits": PartitionSpec(None, blocks.batch_axes, None, blocks.vocab_axes),
        "table": PartitionSpec(blocks.vocab_axes, None),
    }

    def constrain(x, what):
        return jax.lax.with_sharding_constraint(x, specs[what]) if blocks.constrain else x

    # the table as `fused_linear_cross_entropy` pinned it, in its ACTIVATION layout: the
    # gather (ZeRO-3) is done once, outside the walk
    w = constrain(table, "table")
    vocab_ids = jnp.arange(table.shape[0], dtype=jnp.int32)

    @jax.named_scope("ce_block")  # a scan body starts with no name of its own in a profile
    def block_terms_and_grads(h, y):
        """One token block ``[c, B, chunk]``: its terms, ``d hidden`` and the float32
        ``d table`` for a unit cotangent, from ONE product's logits."""
        h = h.astype(compute_dtype)
        with jax.named_scope("logits"):
            logits = jax.lax.dot_general(h, w, (((3,), (1,)), ((), ())))  # [c, B, chunk, V]
        # vocab-parallel ("act_vocab" -> tp) as the scan's chunks are: the reductions over
        # the vocabulary end in a psum, the table is not gathered
        logits = constrain(logits, "logits")
        if logit_scale is not None:
            logits = logits * logit_scale
        # the block's terms, a token's as `_chunk_token_terms` forms them (log-sum-exp minus
        # the label's logit) — with the label's logit gathered from the KEPT logits, before the
        # upcast: `cross_entropy_terms` gathers from the float32 log-softmax, which XLA then writes out
        # whole (768 MiB for this block's 384: the compiled head, PR 39); 1 ulp a token apart
        valid = y != IGNORE_INDEX
        picked = jnp.take_along_axis(logits, jnp.where(valid, y, 0)[..., None], axis=-1)[..., 0]
        reduced = logits.astype(jnp.float32) if upcast else logits
        lse = jax.scipy.special.logsumexp(reduced, axis=-1)
        loss_sum = jnp.sum(jnp.where(valid, lse - picked.astype(lse.dtype), 0.0).astype(jnp.float32))
        lse = lse.astype(jnp.float32)
        z_sum = jnp.sum(jnp.where(valid, jnp.square(lse), 0.0)) if want_z else jnp.zeros((), jnp.float32)
        num = jnp.sum(valid.astype(jnp.float32))
        # d(loss_sum)/d(logits) = softmax - onehot and d(z_sum)/d(logits) = 2 lse softmax on
        # valid rows (z_sum = sum lse^2): the coefficients of a unit cotangent
        label_coef = jnp.where(valid, 1.0, 0.0)
        softmax_coef = jnp.where(valid, 1.0 + 2.0 * z_loss_coef * lse, 0.0) if want_z else label_coef
        dlogits = _softmax_minus_onehot(logits, y, lse, softmax_coef, label_coef, vocab_ids, upcast)
        dlogits = dlogits.astype(h.dtype)
        if logit_scale is not None:
            dlogits = dlogits * logit_scale
        dlogits = constrain(dlogits, "logits")
        with jax.named_scope("grad_hidden"):
            # contracts a device's whole share of the vocabulary: across "act_vocab" shards
            # the partial sums are reduced once a block
            dh = jax.lax.dot_general(
                dlogits, w, (((3,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
        with jax.named_scope("grad_table"):
            dw = jax.lax.dot_general(
                dlogits, h, (((0, 1, 2), (0, 1, 2)), ((), ())), preferred_element_type=jnp.float32
            )
        return (loss_sum, z_sum, num), constrain(dh, "hidden").astype(hidden_c.dtype), constrain(dw, "table")

    with jax.named_scope("loss_chunks"):
        if blocks.token_blocks == 1:
            (loss_sum, z_sum, num_tokens), dh, dw = block_terms_and_grads(hidden_c, labels_c)
        else:
            # more tokens than one block may keep the logits of: the table's float32
            # gradient is carried, once a block
            def outer(carry, xs):
                terms, dh, dw = block_terms_and_grads(*xs)
                return (*(a + t for a, t in zip(carry[:3], terms)), carry[3] + dw), dh

            zero = jnp.zeros((), jnp.float32)
            per_block = [
                x.reshape(blocks.token_blocks, n_chunks // blocks.token_blocks, *x.shape[1:])
                for x in (hidden_c, labels_c)
            ]
            with jax.named_scope("token_blocks"):
                (loss_sum, z_sum, num_tokens, dw), dh = jax.lax.scan(
                    outer, (zero, zero, zero, constrain(jnp.zeros(table.shape, jnp.float32), "table")), per_block
                )
        dtable = constrain(dw.astype(table.dtype), "table")
    # O(B*S*H + V*H): the two gradients for a unit cotangent, nothing logits-sized
    return (_objective(loss_sum, z_sum, z_loss_coef), num_tokens), (dh.reshape(hidden_c.shape), dtable)


def _chunked_ce_terms_bwd(logit_scale, upcast, compute_dtype, z_loss_coef, blocks, residuals, cts):
    # two elementwise passes XLA fuses into their consumers; num_tokens has no gradient
    ct = cts[0].astype(jnp.float32)
    dh, dtable = ((g.astype(jnp.float32) * ct).astype(g.dtype) for g in residuals)
    return dh, None, dtable


_chunked_ce_terms.defvjp(_chunked_ce_terms_fwd, _chunked_ce_terms_bwd)


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
    dlogits = _softmax_minus_onehot(logits, y, lse, softmax_coef, label_coef, vocab_ids, upcast)
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


def _chunked_ce_grads(residuals, coefficients, logit_scale, upcast, tiling):
    """The per-token rule's backward walk, which forms the logits again a vocabulary tile at
    a time (`tiling`, :func:`plan_loss_backward`: the inner scan over tiles carries a token
    block's ``d hidden``, each tile's table gradient leaves one matmul and is written once,
    an outer scan over token blocks carries the table's float32 gradient only where one block
    may not hold all tokens): ``(d hidden_c, None, d table)`` from the residuals
    ``(hidden, labels, table, lse)`` and ``coefficients(valid, lse_c) ->
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
    """The PER-TOKEN rule: `_chunked_ce_terms` with nothing summed over tokens — every token's
    cross-entropy and log-sum-exp, so that a caller may weigh tokens (and learn the weights:
    the weight's gradient is the token's own term, which autodiff of the caller's product
    gives). Its cotangents are a token's own and arrive after the forward, so nothing can be
    formed there: the residuals are ``(hidden, labels, table)`` and the tokens' float32
    log-sum-exp — one float a token, nothing logits-sized — and the backward walk
    (`_chunked_ce_grads`) recomputes the logits."""
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
    """LM-head matmul + CE without the float32 [B, S, V] logits of the plain path.

    Undifferentiated (evaluation): the sequence axis is cut into chunks of `chunk_size`; a
    `lax.scan` computes each chunk's logits ([B, chunk, V]), reduces them to (loss_sum,
    z_sum, count) and discards them: peak logits memory is O(chunk).

    Differentiated, without `weights` (the SUMMED rule, `_chunked_ce_terms`: what every model
    but a looped one trains through): the logits are computed once. The forward walks token
    blocks of whole chunks; a block's logits ([chunks a block, B, chunk, V], compute dtype)
    are kept from the product that forms them to the two gradient products — d hidden and
    d table, float32 accumulation — that the same forward runs for a unit cotangent, then
    dropped. The `custom_vjp`'s residuals are those two gradients (the size of `hidden` and
    of the table) and its backward rule only scales them. What a block may keep is a
    constant a device, `_KEPT_LOGITS_BYTES`: one 4096-token row against a 49152-row table
    in bf16, 384 MiB — memory traded for the product that formed every logit a second
    time. The blocks follow the shapes — tokens and vocabulary rows a device holds,
    `chunk_size` — through :func:`plan_loss_blocks`; there is no knob. One block carries
    nothing; several carry the table's float32 gradient once a block.

    Differentiated with `weights`, or through `fused_linear_token_cross_entropy` (the
    PER-TOKEN rule, `_chunked_ce_token_terms`): the cotangents are a token's own and arrive
    after the forward, so the residuals are (hidden, labels, table) and one float a token,
    and the backward rule RECOMPUTES the logits under the scan's budget — `chunk_size` x V
    elements a batch row — all tokens (or a block of them) against a TILE of the vocabulary
    (:func:`plan_loss_backward`).

    When a telemetry is installed either choice is written once as a ``loss_tiling`` event
    (``logits_products`` 1 or 2, ``token_blocks``, the bytes kept and carried).

    The reference has no counterpart (it materializes logits and calls F.cross_entropy,
    `model_wrapper/pretraining.py:89-127`); this is the TPU/HBM-side answer to that cost
    — the same move as Liger-kernel's chunked fused CE on GPU, which also forms the
    gradients where it forms the logits.

    With the ``fused_ce`` kernel family on Pallas the per-chunk forward reduction of the
    undifferentiated call and of the per-token rule runs as a vocab-tiled online-logsumexp
    kernel (`ops/pallas/fused_ce.py`) whose logits tiles never leave VMEM; the summed rule's
    differentiated forward keeps its logits and is XLA's on every backend.

    hidden: [B, S, H]; embedding: [V, H] (tied-embedding layout); labels: [B, S] with
    IGNORE_INDEX. Chunking is along sequence, so dp/fsdp/ep batch sharding is untouched.
    ``z_loss_coef`` adds ``coef * mean(logsumexp^2)`` exactly like `causal_lm_loss`.

    ``weights`` ([B, S], differentiable) weighs every token's terms: the loss is
    ``sum_i w_i (l_i + coef lse_i^2) / count of labels``, the gradients of the hidden states
    and of the table are scaled token by token by ``w_i``, and ``d loss / d w_i`` is the
    token's own term over the count. Each rule's program is held by a hash of its jaxpr
    (tests/ops/test_loss_token_weights.py): a change to one must not move the other.
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

    hidden_c, labels_c, emb = _chunked_operands(hidden, embedding, labels, chunk_size, compute_dtype)
    blocks = _planned(plan_loss_blocks(*_loss_shapes(hidden_c, emb), jnp.dtype(compute_dtype).itemsize))
    objective, num_tokens = _chunked_ce_terms(
        hidden_c, labels_c, emb, logit_scale, upcast, compute_dtype, z_loss_coef, blocks
    )
    return objective / jnp.maximum(num_tokens, 1.0)


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
    hidden_c, labels_c, emb = _chunked_operands(hidden, embedding, labels, chunk_size, compute_dtype)
    tiling = _planned(plan_loss_backward(*_loss_shapes(hidden_c, emb)))
    loss_c, lse_c = _chunked_ce_token_terms(hidden_c, labels_c, emb, logit_scale, upcast, compute_dtype, tiling)
    rows = lambda x: x.swapaxes(0, 1).reshape(x.shape[1], -1)[:, :S]  # noqa: E731
    return rows(loss_c), rows(lse_c)


def _chunked_operands(hidden, embedding, labels, chunk_size: int, compute_dtype):
    """``(hidden_c [n_chunks, B, chunk, H], labels_c [n_chunks, B, chunk], the table in its
    activation layout)`` of one call of the chunked loss."""
    from ..parallel.sharding import logical_constraint

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
    # once, here: under ZeRO-3 the tied table arrives fsdp-sharded, and the scan, the
    # summed rule's block walk and the per-token backward rule (whose residual this is: it
    # gathers nothing again) compute with the gathered one — ZeRO-3's gather/compute/scatter
    # contract, the scatter being the transpose of this constraint.
    emb = logical_constraint(embedding.astype(compute_dtype), ("act_vocab", None))
    return hidden_c, labels_c, emb


def _loss_shapes(hidden_c: jax.Array, table: jax.Array) -> tuple[int, int, int, int, int]:
    """``(batch, n_chunks, chunk, vocab, hidden_size)``: what either plan is made from."""
    n_chunks, batch, chunk, hidden_size = hidden_c.shape
    return batch, n_chunks, chunk, table.shape[0], hidden_size


def _planned(plan_and_record):
    """A rule's plan, said once a trace as the ``loss_tiling`` event."""
    from ..utils.telemetry import get_telemetry

    plan, record = plan_and_record
    get_telemetry().event_once("loss_tiling", **record)
    return plan


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
