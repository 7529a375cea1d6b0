"""Chunked-prefill flash-attention kernel through the page table.

The XLA chunked-prefill path (`models/modeling_utils._update_paged_kv_cache`, reached
from the engine's per-chunk jits) gathers every row's full page list into a contiguous
``[B, max_pages * page_size, H, D]`` view and masks the invalid tail — every prefill
chunk moves the whole worst-case cache through HBM even when the resident prefix is a
handful of pages. This kernel computes the chunk's ``[chunk, D]`` query block against the
resident prefix K/V **through the page table**, exactly like the decode kernel
(`paged_attention.py`) but sized for wide query windows:

- one program per (row, query block): walks only the pages below the block's causal
  frontier (``cdiv(start + block_end, page_size)``), DMAs each page from HBM once;
- **online softmax** over the page walk (running max / denominator / fp32 accumulator —
  the flash recurrence), because a chunk-wide score matrix over the whole view would not
  fit VMEM at real chunk widths;
- the causal frontier for query row ``j`` of the chunk is ``start + j`` — the same
  per-row frontier `make_attention_mask(query_offset=start)` builds. The scatter that
  precedes the kernel (shared with the XLA path, so pool state is bit-identical) has
  already written the chunk's K/V at ``[start, start + chunk)``, so row ``j`` sees the
  committed prefix plus this chunk's rows ``<= j``, exactly what the masked reference
  attends. Right-pad tail rows of the chunk attend whatever the walked pages hold —
  finite garbage; their outputs (and their trash-page K/V writes) are never read.
- quantized pools (`serving/kv_cache` ``kv_dtype="int8"|"fp8"``): pass the per-page
  ``[num_pages, H]`` scale pools and each DMA'd page is dequantized in VMEM before the
  matmuls — the whole-view dequantized gather is never materialized.

Numerics: fp32 scores/softmax/accumulator (the eager-reference discipline); the online
recurrence is mathematically the one-shot softmax and agrees to ~ulp at fp32.
Prefill-only: no VJP (nothing differentiates through a serving step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# only imported behind the `config.use_pallas` capability gate
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _interpret_default(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    from ...utils.packages import pallas_interpret_mode

    return pallas_interpret_mode()


def _pick_block_q(width: int) -> int:
    """Largest power-of-two query block (<= 256) dividing the chunk width; chunk widths
    are multiples of 8 (prefill_bucket_multiple), so 8 always divides — a non-multiple
    width (direct kernel calls in tests) falls back to one whole-width block."""
    for block in (256, 128, 64, 32, 16, 8):
        if width % block == 0:
            return block
    return width


def _prefill_kernel(
    # scalar prefetch
    start_ref,  # [B] int32 per-row write frontier (== cache_index)
    table_ref,  # [B, max_pages] int32
    # inputs (+ optional scale pools), then output, then scratch
    *refs,
    softmax_scale: float,
    page_size: int,
    quantized: bool,
):
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, kpage_ref, vpage_ref, sems = refs
    else:
        q_ref, k_ref, v_ref, o_ref, kpage_ref, vpage_ref, sems = refs
        ks_ref = vs_ref = None

    row = pl.program_id(0)
    block = pl.program_id(1)
    block_q, num_q_heads, head_dim = q_ref.shape[1:]
    num_kv_heads = kpage_ref.shape[1]
    group = num_q_heads // num_kv_heads
    max_pages = table_ref.shape[1]

    start = start_ref[row]
    row0 = block * block_q
    # the ragged frontier: pages at or past this index are unmapped (trash) for this
    # block's causal window and are neither copied nor scored
    pages_needed = jnp.minimum(
        (start + row0 + block_q + page_size - 1) // page_size, max_pages
    )

    q = q_ref[0].reshape(block_q, num_kv_heads, group, head_dim).astype(jnp.float32)
    shape = (block_q, num_kv_heads, group, page_size)
    q_pos = start + row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_off = jax.lax.broadcasted_iota(jnp.int32, shape, 3)

    def page_step(p, carry):
        m, l, acc = carry
        page = table_ref[row, p]
        k_copy = pltpu.make_async_copy(k_ref.at[page], kpage_ref, sems.at[0])
        k_copy.start()
        k_copy.wait()
        kp = kpage_ref[:].astype(jnp.float32)
        if quantized:
            kp = kp * ks_ref[page][None, :, None]
        s = (
            jnp.einsum("wkgd,pkd->wkgp", q, kp, preferred_element_type=jnp.float32)
            * softmax_scale
        )
        s = jnp.where(p * page_size + k_off <= q_pos, s, _NEG_INF)
        # flash recurrence: renormalize the running sum/accumulator to the new max.
        # Page 0 always holds an unmasked key for every row (position 0 <= start + j),
        # so m leaves -inf on the first step and alpha/probs stay finite throughout.
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(probs, axis=-1)
        v_copy = pltpu.make_async_copy(v_ref.at[page], vpage_ref, sems.at[1])
        v_copy.start()
        v_copy.wait()
        vp = vpage_ref[:].astype(jnp.float32)
        if quantized:
            vp = vp * vs_ref[page][None, :, None]
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "wkgp,pkd->wkgd", probs, vp, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, num_kv_heads, group), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, num_kv_heads, group), jnp.float32)
    acc0 = jnp.zeros((block_q, num_kv_heads, group, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, pages_needed, page_step, (m0, l0, acc0))
    out = acc / l[..., None]
    o_ref[0] = out.reshape(block_q, num_q_heads, head_dim).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    start: jax.Array,
    softmax_scale: float,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked-prefill attention straight off the page table.

    ``q`` is ``[B, chunk, Hq, D]`` (the engine's chunk jits run B=1); ``start`` is the
    per-row ``[B]`` chunk write offset. Returns what `eager_attention` over the
    `paged_gather_kv` view with the per-row causal frontier mask produces for the chunk's
    real rows, without ever materializing the view. Pass ``k_scales``/``v_scales``
    (``[num_pages, Hkv]`` fp32) for quantized pools — pages are dequantized per-DMA."""
    num_rows, width, num_q_heads, head_dim = q.shape
    page_size, num_kv_heads = k_pages.shape[1], k_pages.shape[2]
    assert num_q_heads % num_kv_heads == 0, (num_q_heads, num_kv_heads)
    quantized = k_scales is not None
    assert (v_scales is not None) == quantized, "k_scales and v_scales come as a pair"

    block_q = _pick_block_q(width)
    grid = (num_rows, width // block_q)

    def q_index(row, block, starts, table):
        return (row, block, 0, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, num_q_heads, head_dim), q_index),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        # scale pools ride whole in VMEM: [num_pages, H] fp32 is a few hundred KB even
        # for large pools, and the kernel indexes rows dynamically per walked page
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, num_q_heads, head_dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((page_size, num_kv_heads, head_dim), k_pages.dtype),
            pltpu.VMEM((page_size, num_kv_heads, head_dim), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        softmax_scale=float(softmax_scale),  # dolint: disable=tracer-python-cast (static kernel param)
        page_size=page_size,
        quantized=quantized,
    )
    with jax.named_scope("pallas_prefill_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=_interpret_default(interpret),
        )(start.astype(jnp.int32), page_table.astype(jnp.int32), *operands)
