"""Fused LM-head cross-entropy chunk kernel: vocab-tiled online logsumexp.

One chunk of the chunked fused loss (`ops/loss.fused_linear_cross_entropy`) needs, per
token row, exactly two scalars from the ``[rows, V]`` logits: ``logsumexp(logits)`` and
``logits[label]``. The XLA reference materializes the chunk's logits in HBM to get them;
this kernel tiles the vocabulary instead — each grid step computes one
``[block_rows, block_v]`` logits tile on the MXU and folds it into running
``(max, sum_exp, label_logit)`` scratch (the flash-attention recurrence applied to the
softmax normalizer), so no logits tile ever leaves VMEM. That is the Liger-kernel
chunked-CE move expressed as a TPU kernel.

Numerics: the tile matmul accumulates fp32 (``preferred_element_type``); a non-fp32
``compute_dtype`` is round-tripped through that dtype after the dot so the tile sees the
same quantized logits as the XLA reference's ``compute_dtype`` matmul. The online
max/sum recurrence reassociates the reduction, so parity vs the reference is 1-2 float32
ulp (asserted in tier-1), not bitwise. Gradients never run this kernel. The per-token
rule's backward (`ops/loss._chunked_ce_token_terms`) is one for both forward backends and
takes from the forward only the per-token log-sum-exp this kernel also returns; the summed
rule (`ops/loss._chunked_ce_terms`) runs the kernel in its undifferentiated call alone —
differentiated, its forward keeps a token block's logits to form the gradients over them
(PR 39), which is the opposite of this kernel's point, and is XLA's on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# only imported behind the `config.use_pallas` capability gate
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rmsnorm import _interpret_default

_DEFAULT_BLOCK_ROWS = 256
_DEFAULT_BLOCK_V = 512


def _pick_block(n: int, preferred: int) -> int:
    for block in (preferred, 256, 128, 64, 32, 16, 8):
        if block <= preferred and n >= block:
            return block
    return max(n, 1)


def _fused_ce_kernel(
    h_ref,
    t_ref,
    y_ref,
    lse_ref,
    lab_ref,
    m_scr,
    s_scr,
    lab_scr,
    *,
    block_v: int,
    vocab: int,
    logit_scale: float | None,
    compute_dtype,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        s_scr[:] = jnp.zeros_like(s_scr)
        lab_scr[:] = jnp.zeros_like(lab_scr)

    logits = jax.lax.dot_general(
        h_ref[:],
        t_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if compute_dtype != jnp.float32:
        # the XLA reference's matmul emits compute_dtype logits; round-trip so the
        # online reduction sees identically quantized values
        logits = logits.astype(compute_dtype)
    if logit_scale is not None:
        logits = logits * jnp.asarray(logit_scale, logits.dtype)
    logits = logits.astype(jnp.float32)

    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    in_vocab = cols < vocab  # the padded table tail must not enter max/sum
    masked = jnp.where(in_vocab, logits, -jnp.inf)

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(masked, axis=1, keepdims=True))
    s_scr[:] = s_scr[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(masked - m_new), axis=1, keepdims=True
    )
    m_scr[:] = m_new

    hit = (cols == y_ref[:]) & in_vocab
    lab_scr[:] = lab_scr[:] + jnp.sum(
        jnp.where(hit, logits, 0.0), axis=1, keepdims=True
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse_ref[:] = m_scr[:] + jnp.log(s_scr[:])
        lab_ref[:] = lab_scr[:]


def fused_ce_rowwise(
    hidden: jax.Array,
    table: jax.Array,
    labels: jax.Array,
    *,
    logit_scale: float | None = None,
    compute_dtype=jnp.float32,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-row ``(logsumexp, label_logit)`` of ``hidden @ table.T`` without HBM logits.

    hidden: [rows, H]; table: [V, H]; labels: [rows] int (IGNORE_INDEX rows return a
    garbage label logit that the caller masks). Rows and vocab are padded up to tile
    multiples; padded vocab columns are excluded from the reduction in-kernel.
    """
    interpret = _interpret_default(interpret)
    rows, hdim = hidden.shape
    vocab = table.shape[0]
    block_rows = _pick_block(rows, _DEFAULT_BLOCK_ROWS)
    block_v = _pick_block(vocab, _DEFAULT_BLOCK_V)

    padded_rows = -(-rows // block_rows) * block_rows
    padded_v = -(-vocab // block_v) * block_v
    h = hidden.astype(compute_dtype)
    t = table.astype(compute_dtype)
    if padded_rows != rows:
        h = jnp.pad(h, ((0, padded_rows - rows), (0, 0)))
    if padded_v != vocab:
        t = jnp.pad(t, ((0, padded_v - vocab), (0, 0)))
    y2d = jnp.pad(labels.astype(jnp.int32), (0, padded_rows - rows), constant_values=-1)
    y2d = y2d.reshape(padded_rows, 1)

    grid = (padded_rows // block_rows, padded_v // block_v)
    row_spec = pl.BlockSpec((block_rows, hdim), lambda i, j: (i, 0))
    scalar_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))

    with jax.named_scope("pallas_fused_ce"):
        lse, lab = pl.pallas_call(
            functools.partial(
                _fused_ce_kernel,
                block_v=block_v,
                vocab=vocab,
                logit_scale=logit_scale,
                compute_dtype=compute_dtype,
            ),
            grid=grid,
            in_specs=[
                row_spec,
                pl.BlockSpec((block_v, hdim), lambda i, j: (j, 0)),
                scalar_spec,
            ],
            out_specs=(scalar_spec, scalar_spec),
            out_shape=(
                jax.ShapeDtypeStruct((padded_rows, 1), jnp.float32),
                jax.ShapeDtypeStruct((padded_rows, 1), jnp.float32),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_rows, 1), jnp.float32),
                pltpu.VMEM((block_rows, 1), jnp.float32),
                pltpu.VMEM((block_rows, 1), jnp.float32),
            ],
            interpret=interpret,
        )(h, t, y2d)
    return lse[:rows, 0], lab[:rows, 0]


def fused_ce_chunk(
    h: jax.Array,
    table: jax.Array,
    y: jax.Array,
    *,
    logit_scale: float | None,
    upcast: bool,
    compute_dtype,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk's (loss_sum, z_sum, num_tokens) via the vocab-tiled kernel.

    Drop-in for `ops/loss._chunk_ce_terms` on the forward pass: h [B, chunk, H],
    table [V, H], y [B, chunk]. The kernel always reduces in fp32, which matches the
    reference exactly under ``upcast=True`` and at compute-dtype tolerance otherwise
    (the reference then runs its whole softmax in compute_dtype).
    """
    from ..loss import IGNORE_INDEX

    del upcast  # fp32 reduction always; see docstring
    rows = h.shape[0] * h.shape[1]
    lse, lab = fused_ce_rowwise(
        h.reshape(rows, h.shape[-1]),
        table,
        y.reshape(rows),
        logit_scale=logit_scale,
        compute_dtype=compute_dtype,
        interpret=interpret,
    )
    mask = y.reshape(rows) != IGNORE_INDEX
    loss_sum = jnp.sum(jnp.where(mask, lse - lab, 0.0))
    z_sum = jnp.sum(jnp.where(mask, jnp.square(lse), 0.0))
    num = jnp.sum(mask.astype(jnp.float32))
    return loss_sum, z_sum, num
