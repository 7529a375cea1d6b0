"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has NO context parallelism (SURVEY §2.6: no ring/Ulysses/blockwise code; its
long-context story is YaRN + packing + Megatron-SP). This is the TPU-native long-context
path the north star asks for: shard the sequence over the "sp" mesh axis, keep Q local, and
rotate K/V blocks around the ring with `ppermute` while accumulating the softmax online
(flash-attention style log-sum-exp merging). Compute and communication overlap: each step's
block matmul hides the next block's ICI transfer.

Used inside `shard_map` (manual collectives) — `ring_attention_sharded` wraps the plain
`ring_attention` body for callers living in GSPMD-traced code.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_attend(q, k, v, bias_mask, softmax_scale, o, m, l):
    """One online-softmax accumulation step against a K/V block.

    q [B,Sq,Hkv,G,D] (query heads grouped per kv head — G = Hq/Hkv, no repeated K/V);
    k,v [B,Sk,Hkv,D]; bias_mask [B,1,1,Sq,Sk] bool (True = attend);
    o [B,Sq,Hkv,G,D] f32 accumulator; m, l [B,Hkv,G,Sq] running max / sum.
    """
    scores = (
        jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
        * softmax_scale
    )
    scores = jnp.where(bias_mask, scores, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(bias_mask, p, 0.0)

    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * jnp.moveaxis(correction, 3, 1)[..., None] + jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, v, preferred_element_type=jnp.float32
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    softmax_scale: float | None = None,
    segment_ids_q: jax.Array | None = None,
    query_chunk_size: int | None = None,
) -> jax.Array:
    """Exact attention over sequence blocks distributed on `axis_name` (call under shard_map).

    q: local block [B, S_loc, Hq, D]; k, v: local blocks [B, S_loc, Hkv, D] — GQA K/V stay
    UN-repeated, so each ring hop moves Hkv (not Hq) heads over ICI; the group dimension is
    handled by grouped einsums locally. segment_ids_q: local [B, S_loc] document ids
    (0 = padding) for packed sequences. Returns the local output block [B, S_loc, Hq, D].

    query_chunk_size bounds each hop's score buffer at [B,Hkv,G,chunk,S_loc] f32 by scanning
    Q chunks sequentially (flash-style, with rematerialized backward) — without it the hop
    materializes [B,Hkv,G,S_loc,S_loc], which at the long contexts CP exists for is the
    dominant allocation. Default: auto-chunk at 1024 once S_loc > 2048 (chunking smaller
    blocks just adds scan overhead). The actual chunk is the largest divisor of S_loc <=
    the requested size; if that falls below request/4 (near-prime S_loc), chunking is
    skipped rather than degrading to a per-query scan.
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5

    axis_size = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, s_loc, num_heads, dim = q.shape
    num_kv = k.shape[2]
    group = num_heads // num_kv
    q = q.reshape(batch, s_loc, num_kv, group, dim)

    if query_chunk_size is None and s_loc > 2048:
        query_chunk_size = 1024
    chunk = None
    if query_chunk_size:
        # honor the bound for ANY S_loc: largest divisor <= the requested size (not just an
        # exact divide — seq 40960 / sp 16 gives S_loc 2560, where 1024 doesn't divide but
        # 512 does). Chunks below request/4 are refused (near-prime S_loc would otherwise
        # degrade toward chunk=1, an S_loc-iteration scan) — chunking is skipped instead.
        floor = max(1, query_chunk_size // 4)
        chunk = next(
            (c for c in range(min(query_chunk_size, s_loc), floor - 1, -1) if s_loc % c == 0),
            None,
        )

    # accumulators must be device-varying to be a legal loop value under shard_map; deriving
    # the zeros from q inherits its varying axes without naming them explicitly
    o = (q * 0).astype(jnp.float32)
    zeros = jnp.moveaxis(jnp.sum(q, axis=-1) * 0, 1, 3).astype(jnp.float32)  # [B, Hkv, G, S]
    m = zeros + _NEG_INF
    l = zeros

    q_pos = my_index * s_loc + jnp.arange(s_loc)  # global query positions

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    k_blk, v_blk, seg_blk = k, v, segment_ids_q

    def hop(q, o, m, l, q_pos, seg_q, k_blk, v_blk, seg_blk, k_pos):
        """Attend one (possibly chunked) query slab against the K/V block held this step."""
        mask = jnp.ones((1, 1, 1, q.shape[1], s_loc), bool)
        if causal:
            mask = mask & (k_pos[None, None, None, None, :] <= q_pos[None, None, None, :, None])
        if seg_blk is not None:
            same = seg_q[:, None, None, :, None] == seg_blk[:, None, None, None, :]
            nonpad = (seg_blk != 0)[:, None, None, None, :]
            mask = mask & same & nonpad
        return _block_attend(q, k_blk, v_blk, mask, softmax_scale, o, m, l)

    # static unroll over the (small) ring; the last step skips the rotate whose result
    # nobody consumes, saving one full K/V block transfer per call
    for step_idx in range(axis_size):
        src = (my_index - step_idx) % axis_size  # whose block we hold this step
        k_pos = src * s_loc + jnp.arange(s_loc)

        if chunk is None:
            o, m, l = hop(q, o, m, l, q_pos, segment_ids_q, k_blk, v_blk, seg_blk, k_pos)
        else:
            n_chunks = s_loc // chunk

            def to_chunks_seq(x):  # [B, S, ...] -> [C, B, chunk, ...]
                return jnp.moveaxis(x.reshape((batch, n_chunks, chunk) + x.shape[2:]), 1, 0)

            def to_chunks_ml(x):  # [B, Hkv, G, S] -> [C, B, Hkv, G, chunk]
                return jnp.moveaxis(x.reshape(batch, num_kv, group, n_chunks, chunk), 3, 0)

            xs = (
                to_chunks_seq(q),
                to_chunks_seq(o),
                to_chunks_ml(m),
                to_chunks_ml(l),
                q_pos.reshape(n_chunks, chunk),
                None if segment_ids_q is None else to_chunks_seq(segment_ids_q),
            )

            @jax.checkpoint
            def chunk_body(args):
                q_c, o_c, m_c, l_c, qpos_c, segq_c = args
                return hop(q_c, o_c, m_c, l_c, qpos_c, segq_c, k_blk, v_blk, seg_blk, k_pos)

            o_c, m_c, l_c = jax.lax.map(chunk_body, xs)
            o = jnp.moveaxis(o_c, 0, 1).reshape(batch, s_loc, num_kv, group, dim)
            m = jnp.moveaxis(m_c, 0, 3).reshape(batch, num_kv, group, s_loc)
            l = jnp.moveaxis(l_c, 0, 3).reshape(batch, num_kv, group, s_loc)

        if step_idx < axis_size - 1:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            if seg_blk is not None:
                seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)

    l = jnp.maximum(l, 1e-30)  # fully-masked rows (padding) produce zeros, not NaN
    out = o / jnp.transpose(l, (0, 3, 1, 2))[..., None]  # [B, Hkv, G, S] -> [B, S, Hkv, G]
    return out.reshape(batch, s_loc, num_heads, dim).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    softmax_scale: float | None = None,
    segment_ids: jax.Array | None = None,
    seq_axis: str = "sp",
    # "ep" included: on an ep>1 mesh activations are batch-sharded over (dp, fsdp, ep)
    # (parallel/sharding.py act_batch rule) — omitting it would silently all-gather the batch
    # over "ep" at every attention call when sp>1 and ep>1 compose
    batch_axes: tuple[str, ...] = ("dp", "fsdp", "ep"),
    head_axis: str = "tp",
    query_chunk_size: int | None = None,
) -> jax.Array:
    """GSPMD-callable wrapper: shard_map `ring_attention` with batch over `batch_axes`,
    sequence over `seq_axis`, heads over `head_axis` (TP composes: each tp device rings only
    its local heads), head_dim replicated."""
    # axes that don't divide their dimension (e.g. the batch-1 dummy init, or MQA kv heads)
    # are dropped — layout-only change, identical numerics
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
        return ring_attention(
            q, k, v, seq_axis, causal, softmax_scale,
            segment_ids_q=seg[0] if seg else None,
            query_chunk_size=query_chunk_size,
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )(*operands)
