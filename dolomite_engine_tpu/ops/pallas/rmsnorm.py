"""Fused RMSNorm(+residual add) Pallas kernel.

The XLA lowering of the pre-norm block does ``s = residual + branch_out`` and
``y = rmsnorm(s) * w`` as separate HLOs; on GPU the reference engine bought this fusion
with its in-repo Triton RMSNorm (PAPER.md layer map). Here one kernel reads the branch
output and the residual stream once, produces both the normalized activations AND the
new residual stream, and keeps the fp32 statistics on-chip — one HBM round-trip instead
of three for the bandwidth-bound norm.

Numerics mirror `ops/normalization.rmsnorm` exactly (fp32 accumulation, the same
cast-then-scale order), so fp32 parity is bitwise and bf16 parity is at cast granularity.
Training works: the pair is wrapped in `jax.custom_vjp` with a plain-XLA backward (the
standard RMSNorm gradient), so the kernel only has to be a forward kernel.

Rows are tiled `(block_rows, hidden)`; the row count is padded up to the tile so any
``[B, S, d]`` activation shape lowers to one program shape per ``d``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_DEFAULT_BLOCK_ROWS = 256
# the most bytes a block of rows may hold. The kernel with a residual double-buffers two
# inputs and two outputs and keeps float32 copies of a block: at 2 MiB a block (256 rows of
# 4096 in bfloat16) that is 17.8 MiB, over the 16 MiB of scoped VMEM a kernel gets, and it
# compiled only where XLA chose to keep both outputs in VMEM itself — which any change to
# the operations around it can undo (PR 31 did). 1.5 MiB leaves 256 rows to widths up to 3072.
_MAX_BLOCK_BYTES = 3 * 2**19


def _pick_block_rows(rows: int, row_bytes: int = 0) -> int:
    for block in (_DEFAULT_BLOCK_ROWS, 128, 64, 32, 16, 8):
        if rows >= block and block * row_bytes <= _MAX_BLOCK_BYTES:
            return block
    return max(min(rows, 8), 1)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(variance + eps)
    o_ref[:] = normed.astype(o_ref.dtype) * w_ref[0].astype(o_ref.dtype)


def _rmsnorm_residual_kernel(x_ref, r_ref, w_ref, o_ref, s_ref, *, eps: float):
    s = x_ref[:] + r_ref[:]  # residual add fused in, in the activation dtype
    s_ref[:] = s
    s32 = s.astype(jnp.float32)
    variance = jnp.mean(jnp.square(s32), axis=-1, keepdims=True)
    normed = s32 * jax.lax.rsqrt(variance + eps)
    o_ref[:] = normed.astype(o_ref.dtype) * w_ref[0].astype(o_ref.dtype)


def _flatten_rows(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    shape = x.shape
    return x.reshape(-1, shape[-1]), shape


def _interpret_default(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    from ...utils.packages import pallas_interpret_mode

    return pallas_interpret_mode()


def _rmsnorm_fwd_call(x, weight, eps: float, residual, interpret: bool | None, sharding):
    """The forward kernel, once per shard of `sharding`'s mesh (`fused_rmsnorm` resolves
    it; None = one device)."""
    from ...parallel.sharding import shard_kernel

    if residual is None:
        (normed,) = shard_kernel(
            lambda x, w: _rmsnorm_local(x, w, eps, None, interpret)[:1], sharding
        )(x, weight)
        return normed, None
    return shard_kernel(
        lambda x, r, w: _rmsnorm_local(x, w, eps, r, interpret), sharding
    )(x, residual, weight)


def _rmsnorm_local(x, weight, eps: float, residual, interpret: bool | None):
    from jax.experimental import pallas as pl

    interpret = _interpret_default(interpret)
    rows2d, shape = _flatten_rows(x)
    rows, dim = rows2d.shape
    block_rows = _pick_block_rows(rows, dim * rows2d.dtype.itemsize)
    padded = -(-rows // block_rows) * block_rows
    if padded != rows:
        rows2d = jnp.pad(rows2d, ((0, padded - rows), (0, 0)))
    grid = (padded // block_rows,)
    w2d = weight.reshape(1, dim)
    row_spec = pl.BlockSpec((block_rows, dim), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, dim), lambda i: (0, 0))

    # the scope names the custom call in compiled HLO (utils/program_signature
    # `hlo_tpu_kernels` counts kernels by it)
    if residual is None:
        with jax.named_scope("pallas_rmsnorm"):
            out = pl.pallas_call(
                functools.partial(_rmsnorm_kernel, eps=eps),
                grid=grid,
                in_specs=[row_spec, w_spec],
                out_specs=row_spec,
                out_shape=jax.ShapeDtypeStruct((padded, dim), x.dtype),
                interpret=interpret,
            )(rows2d, w2d)
        return out[:rows].reshape(shape), None

    res2d, _ = _flatten_rows(residual)
    if padded != rows:
        res2d = jnp.pad(res2d, ((0, padded - rows), (0, 0)))
    with jax.named_scope("pallas_rmsnorm"):
        out, stream = pl.pallas_call(
            functools.partial(_rmsnorm_residual_kernel, eps=eps),
            grid=grid,
            in_specs=[row_spec, row_spec, w_spec],
            out_specs=(row_spec, row_spec),
            out_shape=(
                jax.ShapeDtypeStruct((padded, dim), x.dtype),
                jax.ShapeDtypeStruct((padded, dim), x.dtype),
            ),
            interpret=interpret,
        )(rows2d, res2d, w2d)
    return out[:rows].reshape(shape), stream[:rows].reshape(shape)


# ---------------------------------------------------------------------------- custom vjp
# Backward stays plain XLA: the RMSNorm gradient is a handful of fused elementwise ops
# and two reductions, which XLA already lowers well; only the forward is the hot
# inference/serving path that justifies a kernel.


# a backward rule is traced outside the scopes its forward opened: it opens the forward's
@jax.named_scope("pallas_rmsnorm")
def _rmsnorm_grads(s, weight, eps: float, dy):
    s32 = s.astype(jnp.float32)
    variance = jnp.mean(jnp.square(s32), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(variance + eps)
    xhat = s32 * inv
    dy32 = dy.astype(jnp.float32)
    # y = cast(xhat) * w: grads flow through the cast as identity
    dxhat = dy32 * weight.astype(jnp.float32)
    dw = jnp.sum(dy32 * xhat, axis=tuple(range(s.ndim - 1))).astype(weight.dtype)
    ds = inv * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    return ds.astype(s.dtype), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_rmsnorm(x, weight, eps: float, interpret: bool | None, sharding):
    out, _ = _rmsnorm_fwd_call(x, weight, eps, None, interpret, sharding)
    return out


def _fused_rmsnorm_fwd(x, weight, eps, interpret, sharding):
    return _fused_rmsnorm(x, weight, eps, interpret, sharding), (x, weight)


def _fused_rmsnorm_bwd(eps, interpret, sharding, residuals, dy):
    x, weight = residuals
    dx, dw = _rmsnorm_grads(x, weight, eps, dy)
    return dx, dw


_fused_rmsnorm.defvjp(_fused_rmsnorm_fwd, _fused_rmsnorm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_rmsnorm_residual(x, residual, weight, eps: float, interpret: bool | None, sharding):
    return _rmsnorm_fwd_call(x, weight, eps, residual, interpret, sharding)


def _fused_rmsnorm_residual_fwd(x, residual, weight, eps, interpret, sharding):
    out, stream = _rmsnorm_fwd_call(x, weight, eps, residual, interpret, sharding)
    return (out, stream), (stream, weight)


def _fused_rmsnorm_residual_bwd(eps, interpret, sharding, residuals, cotangents):
    stream, weight = residuals
    dy, dstream = cotangents
    ds, dw = _rmsnorm_grads(stream, weight, eps, dy)
    ds = ds + dstream.astype(ds.dtype)  # the returned stream feeds the next residual add
    return ds, ds, dw


_fused_rmsnorm_residual.defvjp(_fused_rmsnorm_residual_fwd, _fused_rmsnorm_residual_bwd)


def fused_rmsnorm(
    x: jax.Array,
    weight: jax.Array,
    eps: float,
    residual: jax.Array | None = None,
    interpret: bool | None = None,
):
    """``rmsnorm(x + residual) * weight`` in one kernel.

    Without `residual`: returns the normalized activations (drop-in for
    `ops/normalization.rmsnorm` with a non-None weight). With `residual`: returns
    ``(normed, x + residual)`` — the block threads the second output on as its new
    residual stream, so the add never materializes separately."""
    assert weight is not None and weight.shape == x.shape[-1:], (
        f"weight {None if weight is None else weight.shape} must match hidden dim "
        f"{x.shape[-1:]}"
    )
    from ...parallel.sharding import kernel_sharding

    eps = float(np.float32(eps))  # hashable static for custom_vjp nondiff  # dolint: disable=tracer-python-cast,tracer-numpy-call
    # rows are independent: under a mesh batch and sequence shard the way the block's
    # residual stream does and only the hidden dim stays whole. Resolved HERE, while the
    # model's rules and mesh are live, and passed down as a static
    rows = (x.shape, ("act_batch", "act_seq", None) if x.ndim == 3 else (None,) * x.ndim)
    scale = (weight.shape, (None,))
    if residual is None:
        sharding = kernel_sharding((rows, scale), (rows,))
        return _fused_rmsnorm(x, weight, eps, interpret, sharding)
    assert residual.shape == x.shape and residual.dtype == x.dtype, (
        residual.shape,
        x.shape,
    )
    sharding = kernel_sharding((rows, rows, scale), (rows, rows))
    return _fused_rmsnorm_residual(x, residual, weight, eps, interpret, sharding)
