"""Ragged paged-attention decode kernel.

The XLA paged decode path (`models/modeling_utils._update_paged_kv_cache`) GATHERS every
row's full page list into a contiguous ``[B, max_pages * page_size, H, D]`` view and
masks the invalid tail — every decode step moves the whole worst-case cache through HBM
even when a row holds 10 resident tokens. This kernel reads K/V **through the page
table**: one program per slot row walks only the pages below that row's frontier
(``cdiv(length + W, page_size)`` of them — the ragged part), DMAs each page from HBM
once, and never touches unmapped/trash table entries past the frontier. Traffic scales
with *resident* tokens per row, exactly the quantity the paged pool already bills by.

Shapes (the serving engine's one-compile decode/verify step):
  q            [S, W, Hq, D]   W = 1 (decode) or draft_k + 1 (speculative verify)
  k/v pages    [num_pages, page_size, Hkv, D]  (the shared pool, page 0 = trash)
  page_table   [S, max_pages]  int32
  lengths      [S]             per-row pre-write frontier (== cache_index)

Query j of row b attends key positions ``pos <= lengths[b] + j`` — the same per-row
causal frontier `make_attention_mask(query_offset=lengths)` builds, covering both the
committed prefix and the in-flight verify window written by this step's scatter. GQA is
native: K/V keep their Hkv heads and query head h reads kv head ``h // (Hq // Hkv)``
(no `_repeat_kv` HBM blowup). Numerics mirror `ops/attention.eager_attention`: scores
accumulated in fp32, the `_NEG_INF` mask constant, fp32 softmax, probs cast back to the
activation dtype before the PV matmul.

Decode-only: no VJP (nothing differentiates through a serving step).
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


def _paged_decode_kernel(
    # scalar prefetch
    lengths_ref,  # [S] int32
    table_ref,  # [S, max_pages] int32
    # inputs: q [1, W, Hq, D] VMEM block, k/v pages [num_pages, page_size, Hkv, D] in
    # ANY/HBM (+ optional [num_pages, Hkv] fp32 scale pools in VMEM for quantized
    # pools), then the [1, W, Hq, D] output block and scratch (scores, landing buffer,
    # DMA semaphore)
    *refs,
    softmax_scale: float,
    page_size: int,
    quantized: bool,
):
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, scores_ref, page_ref, sem = refs
    else:
        q_ref, k_ref, v_ref, o_ref, scores_ref, page_ref, sem = refs
        ks_ref = vs_ref = None

    row = pl.program_id(0)
    width, num_q_heads, head_dim = q_ref.shape[1:]
    num_kv_heads = page_ref.shape[1]
    group = num_q_heads // num_kv_heads
    max_kv = scores_ref.shape[-1]
    max_pages = max_kv // page_size

    length = lengths_ref[row]
    # the ragged frontier: pages at or past this index are unmapped (trash) for this row
    # and are neither copied nor scored — where the gather path's traffic goes to die
    pages_needed = jnp.minimum((length + width + page_size - 1) // page_size, max_pages)

    scores_ref[:] = jnp.full_like(scores_ref, _NEG_INF)
    q = q_ref[0].reshape(width, num_kv_heads, group, head_dim)

    def qk_page(p, _):
        page = table_ref[row, p]
        copy = pltpu.make_async_copy(k_ref.at[page], page_ref, sem)
        copy.start()
        copy.wait()
        kp = page_ref[:]
        if quantized:
            # dequantize in VMEM with the page's per-head scale, back to the activation
            # dtype — the same cast discipline as the XLA fallback's dequantizing gather
            kp = (kp.astype(jnp.float32) * ks_ref[page][None, :, None]).astype(q.dtype)
        s = jnp.einsum("wkgd,pkd->wkgp", q, kp, preferred_element_type=jnp.float32)
        scores_ref[:, :, :, pl.dslice(p * page_size, page_size)] = s * softmax_scale
        return 0

    jax.lax.fori_loop(0, pages_needed, qk_page, 0)

    key_pos = jax.lax.broadcasted_iota(jnp.int32, (width, num_kv_heads, group, max_kv), 3)
    query_pos = length + jax.lax.broadcasted_iota(
        jnp.int32, (width, num_kv_heads, group, max_kv), 0
    )
    probs = jax.nn.softmax(
        jnp.where(key_pos <= query_pos, scores_ref[:], _NEG_INF), axis=-1
    ).astype(o_ref.dtype)

    def pv_page(p, acc):
        page = table_ref[row, p]
        copy = pltpu.make_async_copy(v_ref.at[page], page_ref, sem)
        copy.start()
        copy.wait()
        vp = page_ref[:]
        if quantized:
            vp = (vp.astype(jnp.float32) * vs_ref[page][None, :, None]).astype(
                probs.dtype
            )
        page_probs = jax.lax.dynamic_slice(
            probs, (0, 0, 0, p * page_size), (width, num_kv_heads, group, page_size)
        )
        return acc + jnp.einsum(
            "wkgp,pkd->wkgd", page_probs, vp, preferred_element_type=jnp.float32
        )

    out = jax.lax.fori_loop(
        0,
        pages_needed,
        pv_page,
        jnp.zeros((width, num_kv_heads, group, head_dim), jnp.float32),
    )
    o_ref[0] = out.reshape(width, num_q_heads, head_dim).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    softmax_scale: float,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Attention for the paged decode/verify step, straight off the page table.

    Returns ``[S, W, Hq, D]`` — what `eager_attention` over the
    `paged_gather_kv` view with the per-row causal frontier mask produces, without ever
    materializing the view. For quantized pools pass the per-page ``[num_pages, Hkv]``
    fp32 scale pools: each DMA'd page is dequantized in VMEM, so the full dequantized
    view is never built either."""
    num_slots, width, num_q_heads, head_dim = q.shape
    page_size, num_kv_heads = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    assert num_q_heads % num_kv_heads == 0, (num_q_heads, num_kv_heads)
    group = num_q_heads // num_kv_heads
    quantized = k_scales is not None
    assert (v_scales is not None) == quantized, "k_scales and v_scales come as a pair"

    def q_index(b, lens, table):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, width, num_q_heads, head_dim), q_index),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, k_pages, v_pages]
    if quantized:
        # scale pools ride whole in VMEM ([num_pages, Hkv] fp32 is small) and the
        # kernel indexes rows dynamically per walked page
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, width, num_q_heads, head_dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((width, num_kv_heads, group, max_pages * page_size), jnp.float32),
            pltpu.VMEM((page_size, num_kv_heads, head_dim), k_pages.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel,
        softmax_scale=float(softmax_scale),  # dolint: disable=tracer-python-cast (static kernel param)
        page_size=page_size,
        quantized=quantized,
    )
    with jax.named_scope("pallas_paged_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=_interpret_default(interpret),
        )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), *operands)
