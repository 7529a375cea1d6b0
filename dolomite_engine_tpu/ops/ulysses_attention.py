"""Ulysses-style context parallelism: all_to_all head<->sequence resharding.

The reference has no context parallelism (SURVEY §2.6); this is the second CP scheme next
to `ops/ring_attention.py`, picked with `attention_implementation: ulysses`. Instead of
rotating K/V blocks around the ring, two `all_to_all`s over the "sp" axis reshard
activations from sequence-sharded to head-sharded and back, so the attention itself runs
over the FULL sequence locally — which on TPU means the Pallas flash/splash kernels apply
unchanged, where the ring's online-softmax accumulation is plain XLA ops.

Tradeoffs (scaling-book terms):
  - ulysses: 2 all_to_alls of O(B*S_loc*H*D) bytes; attention rides the MXU kernels; sp is
    capped by the per-device head count (sp | Hq/tp required).
  - ring: sp scales past the head count and moves only K/V (GQA: only the kv heads), but
    every hop recomputes masked scores without a fused kernel.
GQA K/V with fewer heads than sp are repeated by the minimal factor that makes the head
split even — strictly less HBM than the full `_repeat_kv` blow-up whenever gcd(Hkv, sp)>1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..enums import AttentionImplementation


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    softmax_scale: float | None = None,
    segment_ids_q: jax.Array | None = None,
    implementation: AttentionImplementation = AttentionImplementation.flash_attention_2,
) -> jax.Array:
    """all_to_all CP body (call under shard_map). q [B, S_loc, Hq_loc, D]; k, v
    [B, S_loc, Hkv_loc, D]; returns [B, S_loc, Hq_loc, D]. Requires sp | Hq_loc."""
    from .attention import attention as _attention

    sp = jax.lax.axis_size(axis_name)
    h_loc, kv_loc = q.shape[2], k.shape[2]
    if h_loc % sp != 0:
        raise ValueError(f"ulysses attention needs sp ({sp}) to divide the local query head count ({h_loc})")

    if kv_loc % sp != 0:
        # minimal grouped repeat keeping the q-head -> kv-head mapping consistent: each kv
        # head appears r consecutive times, so group size g = Hq/Hkv becomes g/r and
        # chunk j's q heads still map to chunk j's kv heads after the split
        r = sp // math.gcd(kv_loc, sp)
        group = h_loc // kv_loc
        if group % r != 0:  # r must divide the group for the mapping to stay aligned
            r = group
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)

    # seq-sharded -> head-sharded: split heads (axis 2), gather sequence (axis 1)
    q_f = jax.lax.all_to_all(q, axis_name, 2, 1, tiled=True)
    k_f = jax.lax.all_to_all(k, axis_name, 2, 1, tiled=True)
    v_f = jax.lax.all_to_all(v, axis_name, 2, 1, tiled=True)
    seg_full = (
        None
        if segment_ids_q is None
        else jax.lax.all_gather(segment_ids_q, axis_name, axis=1, tiled=True)
    )

    out = _attention(
        q_f,
        k_f,
        v_f,
        implementation=implementation,
        causal=causal,
        softmax_scale=softmax_scale,
        segment_ids=seg_full,
    )
    # head-sharded -> seq-sharded: split sequence (axis 1), gather heads (axis 2)
    return jax.lax.all_to_all(out, axis_name, 1, 2, tiled=True)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    softmax_scale: float | None = None,
    segment_ids: jax.Array | None = None,
    seq_axis: str = "sp",
    # same pruning rationale as ring_attention_sharded: activations on an ep>1 mesh are
    # batch-sharded over (dp, fsdp, ep)
    batch_axes: tuple[str, ...] = ("dp", "fsdp", "ep"),
    head_axis: str = "tp",
) -> jax.Array:
    """GSPMD-callable wrapper: shard_map `ulysses_attention` with batch over `batch_axes`,
    sequence over `seq_axis`, heads over `head_axis` (TP composes: the a2a only redistributes
    each tp shard's local heads)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in batch_axes if sizes.get(a, 1) > 1)
    while batch_axes and q.shape[0] % math.prod(sizes[a] for a in batch_axes):
        batch_axes = batch_axes[:-1]

    tp = sizes.get(head_axis, 1)
    shard_heads = tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
    h_ax = head_axis if shard_heads else None

    qkv_spec = P(batch_axes or None, seq_axis, h_ax, None)
    seg_spec = P(batch_axes or None, seq_axis)

    operands = (q, k, v) + (() if segment_ids is None else (segment_ids,))
    in_specs = (qkv_spec, qkv_spec, qkv_spec) + (() if segment_ids is None else (seg_spec,))

    def body(q, k, v, *seg):
        return ulysses_attention(
            q, k, v, seq_axis, causal, softmax_scale,
            segment_ids_q=seg[0] if seg else None,
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )(*operands)
