"""Fused QKV-split + rotary-embedding kernel.

The XLA lowering of the attention entry (`ops/rope.split_qkv_apply_rope`) splits the
fused ``[B, S, (Hq + 2*Hkv) * D]`` projection output, then runs the rotate-half chain
(`mul + roll + negate + mul + add`) over Q and K as separate elementwise HLOs — the
projection output makes three HBM round-trips before attention sees it. This kernel
reads each head's slice once, applies the rotation in VMEM, and writes the rotated
tensor once; V head blocks pass through untouched, so the output is the same flat QKV
layout and the caller's split/reshape is free.

One program per (row block, lane block). A head of 64 or 80 lanes is not a legal TPU
block (the last block dim must be a multiple of 128 or the whole array dim), so the
fused dim is walked in blocks of ``lcm(head_dim, 128)`` lanes — whole heads AND whole
lane tiles (640 = 8 heads of 80, 128 = 2 heads of 64) — or in one whole-row block when
that does not divide the fused width. Inside a block rotate-half is two lane rotations
(`pltpu.roll`, by +/- head_dim/2) and a select on the lane's position inside its head;
lanes at or past the K/V boundary copy through, so MHA/GQA/MQA all lower to one program
shape per head_dim. cos/sin arrive per row (`get_cos_sin` output broadcast over batch),
already carrying any YaRN interpolation/mscale, and are tiled to one lane block outside
the kernel; the lane-block axis is the inner grid axis, so each row block fetches them
once.

Numerics mirror `ops/rope.apply_rotary_pos_emb`: the same
``x * cos + rotate_half(x) * sin`` with cos/sin in the activation dtype; the arithmetic
runs in fp32 (the TPU rotates 32-bit lanes only, and a v5e has no bf16 VPU anyway) and
rounds once on the way out — fp32 parity is 1-2 ulp (the two lowerings contract the
multiply-add chain differently), asserted in tier-1.
Rope is on the training hot path, but no custom backward is needed: the rotation is its
own transpose up to sign, and the `jax.custom_vjp` below reuses the kernel with negated
``sin`` for the cotangent — the backward is one more fused kernel call, not an XLA
fallback chain.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# only imported behind the `config.use_pallas` capability gate
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rmsnorm import _interpret_default, _pick_block_rows


def _rope_qkv_kernel(rope_lanes_ref, x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int):
    x = x_ref[:].astype(jnp.float32)
    width = x.shape[-1]
    half = head_dim // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    # rotate_half per head: lanes in a head's first half take -x[lane + half], the rest
    # x[lane - half]; a block holds whole heads, so the rolls' wrap-around lanes are
    # never the ones selected
    rotated = jnp.where(
        lane % head_dim < half, -pltpu.roll(x, width - half, 1), pltpu.roll(x, half, 1)
    )
    roped = x * cos_ref[:].astype(jnp.float32) + rotated * sin_ref[:].astype(jnp.float32)
    # the first rope_lanes lanes are the Q and K slices of the fused layout; the V tail
    # copies (a scalar, not a constant: under tensor parallelism it differs per shard)
    in_rope = pl.program_id(1) * width + lane < rope_lanes_ref[0]
    o_ref[:] = jnp.where(in_rope, roped, x).astype(o_ref.dtype)


def fused_rope_qkv(
    qkv: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Rotate the Q and K head blocks of a fused QKV tensor in one kernel.

    qkv: [B, S, (num_heads + 2*num_kv_heads) * head_dim]; cos/sin: broadcastable to
    [B, S, head_dim]. Returns the same-shaped tensor with rope applied to the Q/K
    blocks — the caller's `jnp.split` + reshape then yields roped q/k and untouched v.
    """
    from ...parallel.sharding import kernel_sharding

    batch, seq, total_dim = qkv.shape
    total_heads = num_heads + 2 * num_kv_heads
    assert total_dim == total_heads * head_dim, (qkv.shape, total_heads, head_dim)
    # under a mesh the fused dim is tensor-parallel in whole heads: the flat ``act_heads``
    # sharding of the projection output, spelled over a heads dim so that a shard can
    # never cut a head. Resolved HERE, while the model's rules and mesh are live, and
    # passed down as a static (the backward is traced after both are gone)
    heads = ((batch, seq, total_heads, head_dim), ("act_batch", "act_seq_inner", "act_heads", None))
    table = ((batch, seq, head_dim), ("act_batch", "act_seq_inner", None))
    sharding = kernel_sharding((heads, table, table), (heads,))
    return _fused_rope_qkv(
        qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_rope_qkv(qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding):
    return _rope_qkv_call(
        qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding
    )


def _rope_qkv_call(qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding):
    """The kernel, once per shard of `sharding`'s mesh (None = one device): each shard
    works out how many of ITS heads lie before the K/V boundary."""
    from ...parallel.sharding import shard_kernel

    batch, seq, total_dim = qkv.shape
    head_axes = None if sharding is None else sharding[1][0][2]

    def local(qkv, cos, sin):
        heads = qkv.shape[2]
        first_head = 0 if head_axes is None else jax.lax.axis_index(head_axes) * heads
        rope_heads = jnp.clip(num_heads + num_kv_heads - first_head, 0, heads)
        out = _rope_qkv_local(
            qkv.reshape(*qkv.shape[:2], heads * head_dim), cos, sin, rope_heads * head_dim,
            head_dim, interpret,
        )
        return (out.reshape(qkv.shape),)

    (out,) = shard_kernel(local, sharding)(
        qkv.reshape(batch, seq, total_dim // head_dim, head_dim),
        jnp.broadcast_to(cos.astype(qkv.dtype), (batch, seq, head_dim)),
        jnp.broadcast_to(sin.astype(qkv.dtype), (batch, seq, head_dim)),
    )
    return out.reshape(batch, seq, total_dim)


def _rope_qkv_local(qkv, cos, sin, rope_lanes, head_dim, interpret):
    """One device's [B, S, heads * head_dim] block; `rope_lanes` (a traced scalar) is
    how many of its leading lanes are Q/K."""
    interpret = _interpret_default(interpret)
    batch, seq, total_dim = qkv.shape
    rows = batch * seq
    # lane-block width: whole heads and whole 128-lane tiles, else the whole fused row
    # (a block dim equal to the array dim is always legal)
    width = math.lcm(head_dim, 128)
    if total_dim % width:
        width = total_dim
    x2d = qkv.reshape(rows, total_dim)
    cos2d = jnp.tile(cos.reshape(rows, head_dim), (1, width // head_dim))
    sin2d = jnp.tile(sin.reshape(rows, head_dim), (1, width // head_dim))

    block_rows = _pick_block_rows(rows)
    padded = -(-rows // block_rows) * block_rows
    if padded != rows:
        x2d = jnp.pad(x2d, ((0, padded - rows), (0, 0)))
        cos2d = jnp.pad(cos2d, ((0, padded - rows), (0, 0)))
        sin2d = jnp.pad(sin2d, ((0, padded - rows), (0, 0)))

    qkv_spec = pl.BlockSpec((block_rows, width), lambda i, j, rope_lanes: (i, j))
    cs_spec = pl.BlockSpec((block_rows, width), lambda i, j, rope_lanes: (i, 0))
    with jax.named_scope("pallas_fused_rope_qkv"):
        out = pl.pallas_call(
            functools.partial(_rope_qkv_kernel, head_dim=head_dim),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(padded // block_rows, total_dim // width),
                in_specs=[qkv_spec, cs_spec, cs_spec],
                out_specs=qkv_spec,
            ),
            out_shape=jax.ShapeDtypeStruct((padded, total_dim), qkv.dtype),
            interpret=interpret,
        )(jnp.asarray(rope_lanes, jnp.int32).reshape(1), x2d, cos2d, sin2d)
    return out[:rows].reshape(batch, seq, total_dim)


def _fused_rope_qkv_fwd(qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding):
    out = _rope_qkv_call(
        qkv, cos, sin, num_heads, num_kv_heads, head_dim, interpret, sharding
    )
    return out, (cos, sin)


def _fused_rope_qkv_bwd(num_heads, num_kv_heads, head_dim, interpret, sharding, residuals, g):
    # R(x) = x*cos + rot(x)*sin with rot^T = -rot, so R^T(g) = g*cos + rot(g)*(-sin):
    # the backward is the SAME kernel with sin negated (V blocks pass g through).
    cos, sin = residuals
    dqkv = _rope_qkv_call(
        g, cos, -sin, num_heads, num_kv_heads, head_dim, interpret, sharding
    )
    return dqkv, None, None


_fused_rope_qkv.defvjp(_fused_rope_qkv_fwd, _fused_rope_qkv_bwd)
