"""Unified attention op: eager / XLA-fused (sdpa) / Pallas flash with segment ids.

Parity: reference `hf_models/modeling_utils/attention/` implements four paths — eager fp32-softmax
matmul (`base.py:234-259`), SDPA (`sdpa.py:11-86`), FlashAttention2 with unpad/pad
(`flash.py:16-140`), and PaddingFreeAttention over packed `cu_seqlens` tensors
(`padding_free.py:14-77`). The TPU design collapses FlashAttention2 + PaddingFree into ONE path:
packed sequences with **segment ids** (the TPU-native replacement for varlen cu_seqlens) running a
Pallas flash kernel; "sdpa" maps to XLA's fused `jax.nn.dot_product_attention`; "eager" is the
fp32-softmax debug/parity path. GQA/MQA head broadcast replaces `repeat_key_value`
(`attention/utils.py:5-118`).

All shapes are batch-first: q [B, Sq, Hq, D]; k [B, Skv, Hkv, D]; v [B, Skv, Hkv, Dv] (Dv is
D everywhere but in latent attention, whose values are narrower than its scores). Packed (padding-free) input
is [B, S] tokens + segment_ids [B, S] (0 = padding, 1.. = documents); this also implements
`reset_attention_mask` document isolation (reference `model_wrapper/pretraining.py:129-160`).

How documents reach the splash kernel: twice. Inside a block the kernel compares the segment
ids of its queries and keys, as it always did. Which blocks it runs at all it reads from block
tables in scalar memory, and those are built each step from the rows' segment ids
(`document_block_pairs`, `_document_block_tables`): a (query block, key block) pair under the
diagonal that no document spans is neither fetched nor computed, in the forward pass, in dkv and
in dq. A call without segment ids runs jax's static causal tables, as before.

A window (`window`: query i sees key j iff ``0 <= i - j < window``, itself and the ``window - 1``
before it, inside its document) is one more term everywhere the causal term stands: in the
dense mask of `make_attention_mask` (eager, sdpa), in `document_block_pairs` (a key block
further back than the window reaches is not needed) and in the splash kernel's in-block mask
function (jax's `LocalMask` in place of its `CausalMask`). None is no window, and the programs
of before. Ring / ulysses and the legacy flash kernel refuse a window rather than ignore it.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..enums import AttentionImplementation

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def make_attention_mask(
    batch_size: int,
    query_length: int,
    key_length: int,
    causal: bool = True,
    attention_mask: jax.Array | None = None,
    segment_ids_q: jax.Array | None = None,
    segment_ids_kv: jax.Array | None = None,
    query_offset: jax.Array | int = 0,
    window: int | None = None,
) -> jax.Array | None:
    """Boolean [B, 1, Sq, Skv] mask (True = attend), or None when fully visible.

    `window` (with `causal`): a query sees itself and the ``window - 1`` keys before it.

    `query_offset` may be a per-row [B] vector (continuous batching: every slot
    continues at its own cache position), producing a per-row causal frontier. This
    composes with Sq > 1: the speculative verify step scores K+1 positions per slot in
    one call, and query i of row b may attend keys up to ``query_offset[b] + i`` — draft
    token i sees the in-flight K/V of drafts 0..i-1 written in the same call, exactly
    what sequential decode would have resident."""
    mask = None

    if causal:
        if getattr(query_offset, "ndim", 0) == 1:
            q_pos = jnp.arange(query_length)[None, :, None] + query_offset[:, None, None]
            k_pos = jnp.arange(key_length)[None, None, :]
            mask = _causal_pairs(q_pos, k_pos, window)[:, None]  # [B, 1, Sq, Skv]
        else:
            q_pos = jnp.arange(query_length)[:, None] + query_offset
            k_pos = jnp.arange(key_length)[None, :]
            mask = _causal_pairs(q_pos, k_pos, window)[None, None]
    elif window is not None:
        raise NotImplementedError("a window without the causal mask is not built")

    if attention_mask is not None:
        pad = attention_mask.astype(bool)[:, None, None, :]  # [B, 1, 1, Skv]
        mask = pad if mask is None else jnp.logical_and(mask, pad)

    if segment_ids_q is not None or segment_ids_kv is not None:
        if segment_ids_kv is None:
            segment_ids_kv = segment_ids_q
        if segment_ids_q is None:
            segment_ids_q = segment_ids_kv
        seg = segment_ids_q[:, None, :, None] == segment_ids_kv[:, None, None, :]
        nonpad = (segment_ids_kv != 0)[:, None, None, :]
        seg = jnp.logical_and(seg, nonpad)
        mask = seg if mask is None else jnp.logical_and(mask, seg)

    return mask


def _causal_pairs(q_pos: jax.Array, k_pos: jax.Array, window: int | None) -> jax.Array:
    """``k <= q``, and with a window ``q - k < window``."""
    pairs = k_pos <= q_pos
    return pairs if window is None else pairs & (q_pos - k_pos < window)


def paged_scatter_kv(
    pages: jax.Array,
    new: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    write_valid: jax.Array | None = None,
) -> jax.Array:
    """Scatter new K (or V) tokens into a paged pool.

    ``pages`` is the shared pool ``[num_pages, page_size, H, D]``; ``page_table`` maps each
    row's logical page slots to physical pages ``[B, max_pages]``; ``positions`` are the
    absolute token positions being written ``[B, S]``. Page 0 is the TRASH page by
    convention: rows whose table entries are 0 (idle decode slots) and writes with
    ``write_valid == False`` (right-pad tail of a prefill chunk) land at flat index 0,
    where collisions are harmless because trash content is never attended unmasked.
    """
    from ..parallel.sharding import logical_constraint

    num_pages, page_size = pages.shape[:2]
    batch, seq = positions.shape
    page_ids = jnp.take_along_axis(page_table, positions // page_size, axis=1)  # [B, S]
    flat_index = page_ids * page_size + positions % page_size
    if write_valid is not None:
        flat_index = jnp.where(write_valid, flat_index, 0)
    flat_pages = pages.reshape((num_pages * page_size,) + pages.shape[2:])
    flat_pages = flat_pages.at[flat_index.reshape(-1)].set(
        # explicit cast: a low-bit pool (kv_dtype="bf16" under an fp32 model) stores
        # rounded tokens; a matching dtype is a no-op
        new.reshape((batch * seq,) + new.shape[2:]).astype(pages.dtype)
    )
    # keep the pool kv-head-sharded through the scatter (serving/kv_cache.shard_kv_caches
    # places it that way): without the pin GSPMD may emit a replicated output, which both
    # materializes the whole pool per device and flips the donated decode-step input
    # sharding on the next call (a recompile, breaking decode_compiles == 1)
    return logical_constraint(
        flat_pages.reshape(pages.shape), (None, None, "act_kv_heads", None)
    )


def paged_scatter_kv_quantized(
    pages: jax.Array,
    scales: jax.Array,
    new: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    write_valid: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Quantize-on-scatter into a low-bit paged pool (``serving/kv_cache``
    ``kv_dtype="int8"|"fp8"``).

    ``pages`` holds quantized values ``[num_pages, page_size, H, D]`` with per-(page,
    head) fp32 ``scales`` ``[num_pages, H]``. Each row's write window ``positions``
    (always contiguous: ``cache_index + arange(S)``) touches a bounded run of logical
    pages; those pages are gathered, dequantized, the new tokens inserted, and the whole
    page re-encoded (`ops/kv_quant.quantize_pages`) with a fresh absmax over its VALID
    tokens — committed prefix plus this call's actual writes, never the stale garbage
    beyond the frontier. While a page's scale is unchanged the re-encode is exact (see
    `ops/kv_quant`), so repeated decode writes do not drift committed tokens.

    Trash-page discipline matches `paged_scatter_kv`: invalid writes (pad tails,
    overhang) are dropped from the insert, and window pages with no actual write —
    including the one-page look-ahead pad of the window bound — are redirected to page
    0, so a mapped page is only ever rewritten by the row that owns it (writable pages
    are refcount-1 private by the pool contract; shared prefix pages are never inside a
    write window).
    """
    from ..parallel.sharding import logical_constraint
    from .kv_quant import kv_qmax, quantize_pages

    num_pages, page_size = pages.shape[:2]
    heads, head_dim = pages.shape[2:]
    batch, seq = positions.shape
    max_pages = page_table.shape[1]
    # a contiguous S-token window spans at most this many logical pages (the +1 pads
    # the bound when the window straddles a page boundary)
    span = (seq - 1) // page_size + 2
    base = positions[:, 0] // page_size  # [B] — positions[:, 0] is the row's frontier
    logical = base[:, None] + jnp.arange(span, dtype=positions.dtype)[None, :]
    in_table = logical < max_pages
    phys = jnp.where(
        in_table,
        jnp.take_along_axis(page_table, jnp.clip(logical, 0, max_pages - 1), axis=1),
        0,
    )  # [B, span]

    # dequantize the touched window, insert the new tokens (invalid writes dropped)
    window = pages[phys].astype(jnp.float32) * scales[phys][:, :, None, :, None]
    window = window.reshape(batch, span * page_size, heads, head_dim)
    local = positions - base[:, None] * page_size
    local = jnp.where(write_valid, local, span * page_size)  # out of bounds -> dropped
    rows = jnp.arange(batch)[:, None]
    window = window.at[rows, local].set(new.astype(jnp.float32), mode="drop")
    written = jnp.zeros((batch, span * page_size), bool).at[rows, local].set(
        True, mode="drop"
    )
    grid = base[:, None] * page_size + jnp.arange(span * page_size)
    valid = (grid < positions[:, :1]) | written  # committed prefix + this call's writes

    q, new_scales = quantize_pages(
        window.reshape(batch * span, page_size, heads, head_dim),
        valid.reshape(batch * span, page_size),
        kv_qmax(pages.dtype),
        pages.dtype,
    )
    # only pages that actually received a write go back (untouched window pad -> trash,
    # where colliding garbage is harmless by the trash-page contract)
    page_written = written.reshape(batch, span, page_size).any(-1)
    dst = jnp.where(page_written & in_table, phys, 0).reshape(-1)
    pages = pages.at[dst].set(q)
    scales = scales.at[dst].set(new_scales)
    # same sharding pins as paged_scatter_kv: keep pool and scale pool kv-head-sharded
    # through the scatter so the donated decode buffers keep a stable sharding
    pages = logical_constraint(pages, (None, None, "act_kv_heads", None))
    scales = logical_constraint(scales, (None, "act_kv_heads"))
    return pages, scales


def paged_gather_kv_dequant(
    pages: jax.Array, scales: jax.Array, page_table: jax.Array, dtype
) -> jax.Array:
    """Dequantizing variant of `paged_gather_kv` for the XLA reference attention paths:
    gather each row's quantized pages into a contiguous ``[B, max_pages * page_size, H,
    D]`` view in ``dtype``, applying each page's per-head scale. Positions past a row's
    validity frontier decode stale-but-finite garbage the attention mask zeroes, exactly
    like the unquantized gather."""
    from ..parallel.sharding import logical_constraint

    num_pages, page_size = pages.shape[:2]
    batch, max_pages = page_table.shape
    flat_pages = pages.reshape((num_pages * page_size,) + pages.shape[2:])
    index = (
        page_table[:, :, None] * page_size + jnp.arange(page_size, dtype=page_table.dtype)
    ).reshape(batch, max_pages * page_size)
    values = flat_pages[index].astype(jnp.float32)
    page_scales = jnp.repeat(scales[page_table], page_size, axis=1)  # [B, view, H]
    out = (values * page_scales[..., None]).astype(dtype)
    return logical_constraint(out, (None, None, "act_kv_heads", None))


def gather_kv_pages(caches: list, page_index: jax.Array) -> list:
    """Snapshot whole physical pages out of a paged pool: ``page_index`` is a fixed-width
    ``[W]`` vector of physical page ids (padded with the trash page so one program serves
    any request), and every per-layer array is page-major (pages at dim 0), so a
    quantized pool's per-(page, head) scale rows ride out with their page bytes. This is
    the swap-OUT half of paged-KV preemption (serving/engine.py ``preemption="swap"``):
    the result is fetched to a host-memory pool and the device pages are freed."""
    return [{name: array[page_index] for name, array in cache.items()} for cache in caches]


def scatter_kv_pages(caches: list, payload: list, page_index: jax.Array) -> list:
    """Swap-IN half of paged-KV preemption: write `payload` (the `gather_kv_pages`
    snapshot, one ``[W, ...]`` leading-dim chunk per per-layer array) back onto the
    physical pages in ``page_index``. Pad lanes map trash->trash (page 0 on both sides),
    where duplicate writes are harmless by the trash-page contract — the same shape as
    the KVHandoff page copy, so the pair compiles once per pool geometry and restores
    page bytes (and quantized scale rows) exactly."""
    return [
        {name: cache[name].at[page_index].set(chunk[name]) for name in cache}
        for cache, chunk in zip(caches, payload)
    ]


def paged_gather_kv(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Gather each row's pages into a contiguous ``[B, max_pages * page_size, H, D]`` view.

    Positions past a row's validity frontier read whatever the mapped page holds (stale
    K/V, trash) — finite garbage the attention mask reduces to exactly-zero probability,
    so downstream attention is bitwise identical to a dense cache with the same frontier.
    """
    from ..parallel.sharding import logical_constraint

    num_pages, page_size = pages.shape[:2]
    batch, max_pages = page_table.shape
    flat_pages = pages.reshape((num_pages * page_size,) + pages.shape[2:])
    index = (
        page_table[:, :, None] * page_size + jnp.arange(page_size, dtype=page_table.dtype)
    ).reshape(batch, max_pages * page_size)
    # the gathered per-row view feeds attention with kv heads tp-sharded (the gather
    # indexes only the unsharded pages dim, so each device gathers its local head
    # shard). The slot-batch dim stays unconstrained: batch parallelism in the serving
    # tier is done with whole replicas (serving/cluster/router.py), and pinning it to
    # the data axes would force a reshard on meshes where fsdp > num_slots.
    return logical_constraint(flat_pages[index], (None, None, "act_kv_heads", None))


def _repeat_kv(k: jax.Array, num_query_heads: int) -> jax.Array:
    """Expand KV heads to match query heads (reference `attention/utils.py` repeat_key_value)."""
    num_kv = k.shape[2]
    if num_kv == num_query_heads:
        return k
    return jnp.repeat(k, num_query_heads // num_kv, axis=2)


def eager_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
    bias: jax.Array | None,
    softmax_scale: float,
    softmax_in_fp32: bool = True,
    dropout: float = 0.0,
    dropout_rng: jax.Array | None = None,
) -> jax.Array:
    """Explicit QK^T -> softmax -> V (reference `attention/base.py:234-259`): scores scaled by
    softmax_scale, optional additive bias (alibi), softmax upcast to fp32."""
    input_dtype = q.dtype
    k = _repeat_kv(k, q.shape[2])
    v = _repeat_kv(v, q.shape[2])

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)

    if softmax_in_fp32:
        scores = scores.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(input_dtype)

    if dropout > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)

    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def sdpa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
    bias: jax.Array | None,
    softmax_scale: float,
) -> jax.Array:
    """XLA fused attention; GQA/MQA handled natively by `jax.nn.dot_product_attention`.
    It refuses values narrower than the scores' head (latent attention): those take the
    explicit products of `eager_attention`, which XLA fuses as it can."""
    if v.shape[-1] != q.shape[-1]:
        return eager_attention(q, k, v, mask, bias, softmax_scale)
    return jax.nn.dot_product_attention(
        q, k, v, bias=bias, mask=mask, scale=softmax_scale, implementation="xla"
    )


def _use_splash_kernel() -> bool:
    """Whether causal flash attention lowers through the splash kernel (the production
    MaxText kernel: GQA without KV-head repetition, fused bwd option) or the legacy
    flash kernel. Numerics are pinned by tests in interpret mode; splash against legacy
    flash on hardware: not measured. Selection lives in the central KernelConfig
    (`ops/pallas/config.py` — ``kernel_args`` block / ``DOLOMITE_KERNELS``)."""
    from .pallas import use_pallas

    return use_pallas("splash_attention")


def splash_expected(implementation: AttentionImplementation | None) -> bool:
    """Whether a model's causal self-attention is expected to lower through the splash
    kernel: asked for as ``flash_attention_2``, on a TPU, the family on Pallas. `attention`
    may still drop the kernel for a call and says why (dropout, a mask that is no padding
    mask, a kv cache, a length off 128, alibi); what a trace did is in its ``remat_plan``."""
    return (
        implementation == AttentionImplementation.flash_attention_2
        and jax.default_backend() == "tpu"
        and _use_splash_kernel()
    )


def _tpu_splash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array | None,
    softmax_scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """GQA-native Pallas splash attention: K/V keep their kv-head count (no `_repeat_kv`
    HBM blowup); the kernel maps q head h to kv head h // (Hq // Hkv). Causal-only (alibi
    needs an additive bias splash's mask objects don't express).

    Under a mesh the kernel runs per shard (`parallel.sharding.shard_kernel`): batch over
    the data axes, heads over tp when tp divides both head counts (whole GQA groups stay
    together), the sequence whole."""
    from ..parallel.sharding import kernel_sharding, logical_spec, shard_kernel

    q_heads = logical_spec(q.shape, (None, None, "act_heads", None))
    kv_heads = logical_spec(k.shape, (None, None, "act_kv_heads", None))
    heads_divide = q_heads is not None and None not in (q_heads[1][2], kv_heads[1][2])
    q_side = (q.shape, ("act_batch", None, "act_heads" if heads_divide else None, None))
    kv_side = (k.shape, ("act_batch", None, "act_kv_heads" if heads_divide else None, None))
    segments = () if segment_ids is None else (segment_ids,)
    sharding = kernel_sharding(
        (q_side, kv_side, kv_side, *((s.shape, ("act_batch", None)) for s in segments)),
        (q_side,),
    )

    def local(q, k, v, *seg):
        return (_splash_attention_local(q, k, v, seg[0] if seg else None, softmax_scale, interpret, window),)

    (out,) = shard_kernel(local, sharding)(q, k, v, *segments)
    return out


# lists that `watch_kernel_residuals` opened, innermost last
_RESIDUAL_WATCHERS: list[list[int]] = []


@contextmanager
def watch_kernel_residuals():
    """What the attention kernel tagged for the remat policy in the trace inside: one entry a
    kernel call, the bytes a batch row (on a device) of its output and log-sum-exp. Empty
    where attention lowered through XLA — its products are dots there. The model reads it for
    its ``remat_plan`` event (`models/gpt_dolomite.remat_plan`)."""
    seen: list[int] = []
    _RESIDUAL_WATCHERS.append(seen)
    try:
        yield seen
    finally:
        _RESIDUAL_WATCHERS.pop()


# what a step's forward pass counts of the kernel's block tables (`splash_block_counters`)
SPLASH_COUNTERS = ("splash_blocks_visited", "splash_blocks_causal")
# ... of a model whose layers differ by mask (`models/afmoe.py`): the tables of the window
# layers and of the full layers, each summed over the layers of its kind, and the blocks
# under the diagonal summed over all of them
SPLASH_COUNTERS_BY_KIND = ("splash_blocks_visited_window", "splash_blocks_visited_full", "splash_blocks_causal")


def window_block_reach(window: int, block: int) -> int:
    """How many key blocks back of its own a query block can reach under `window`: the nearest
    pair of query block i and key block j < i lies ``(i - j - 1) * block + 1`` apart, and a
    window lets through distances up to ``window - 1``."""
    return (window - 2) // block + 1


def _block_band(n: int, window: int, block: int) -> np.ndarray:
    """bool ``[n, n]``: key block j on or under the diagonal of query block i and within the
    window's reach."""
    return np.triu(np.tril(np.ones((n, n), bool)), -window_block_reach(window, block))


def document_block_pairs(segment_ids: jax.Array, block: int, window: int | None = None) -> jax.Array:
    """bool ``[B, n, n]`` (``n = S // block``): whether (query block i, key block j) of a row
    holds a pair the causal, per-document mask lets through — j on or under the diagonal and
    the two blocks' ids meet. The test is each block's ``[min, max]`` of the ids: ranges that
    do not meet share no id, whatever the order of the ids, so a needed pair is never dropped;
    on ids that do not decrease along the row it is exact. Padding (id 0, at a row's tail)
    is given the largest id first, so that it too is in order. With a `window`, one more
    term: a key block further back than the window reaches (`window_block_reach`) is not
    needed, whatever its ids."""
    batch, seq = segment_ids.shape
    n = seq // block
    ids = segment_ids.astype(jnp.int32)
    ids = jnp.where(ids == 0, jnp.iinfo(jnp.int32).max, ids).reshape(batch, n, block)
    low, high = ids.min(-1), ids.max(-1)  # [B, n]
    meet = (low[:, :, None] <= high[:, None, :]) & (low[:, None, :] <= high[:, :, None])
    if window is None:
        return meet & jnp.tril(jnp.ones((n, n), bool))
    return meet & _block_band(n, window, block)


def _document_block_tables(needed: jax.Array):
    """The tables jax's splash launches read by scalar prefetch, from `document_block_pairs`,
    for the rows laid end to end as one sequence of ``B * n`` blocks (a row's blocks only ever
    name blocks of the same row, so rows never meet). ``block_mask`` says whether a grid step
    runs; ``data_next`` names the block its index maps fetch — its own where it runs, the next
    one that runs where it does not, so a skipped step moves nothing the next does not need.

    Forward and dq (grid: heads, query blocks, key slots) get ``[1, B * n, n]``: the grid is
    as wide as a row, and slot j of query block (b, i) is key block (b, j). dkv (grid: key
    blocks, heads, query slots) gets ``[1, n, B * n]``: slot i of key block (b, j) is query
    block (b, i). That is the shape jax's own shrunk tables have (`_shrink_mask_info`)."""
    batch, n, _ = needed.shape
    first = jnp.arange(batch, dtype=jnp.int32)[:, None, None] * n  # a row's first block
    slot = jnp.arange(n, dtype=jnp.int32)

    # forward, dq: the next needed (row, slot) in grid order. The last slot of the last row
    # is a diagonal block, so there always is one; a query block's run ends at its diagonal
    # and goes on with the first needed block of the next query block.
    positions = jnp.arange(batch * n * n, dtype=jnp.int32)
    following = jax.lax.cummin(jnp.where(needed.reshape(-1), positions, batch * n * n), reverse=True)
    data_next = following // (n * n) * n + following % n  # its row's first block + its slot
    forward = (needed.astype(jnp.int32).reshape(1, batch * n, n), data_next.reshape(1, batch * n, n))

    # dkv: the next needed query block of the same key block, else its diagonal — where the
    # next head starts
    following = jax.lax.cummin(jnp.where(needed, slot[None, :, None], n), axis=1, reverse=True)
    following = jnp.where(following == n, slot[None, None, :], following) + first
    key_major = lambda t: jnp.swapaxes(t, 0, 1).reshape(1, n, batch * n)
    return forward, (key_major(needed.astype(jnp.int32)), key_major(following))


def _banded_block_tables(needed: jax.Array, width: int):
    """`_document_block_tables` for a layer under a window that reaches ``width - 1`` key blocks
    back of a query block's own, fewer than a row has: the same steps run in the same order,
    and a launch's grid walks `width` slots a block, not a row's ``n`` (the kernels read the
    grid's width off the tables, take a key's position from ``data_next`` and not from the slot,
    and open and close a block's accumulation at the first and last slot whether or not those
    run).

    Forward and dq get ``[1, B * n, width]``: slot s of query block (b, i) is key block
    (b, i - (width - 1) + s), the last slot the diagonal; a slot before the row's first block
    does not run. dkv gets ``[1, width, B * n]``: slot s of key block (b, j) is query block
    (b, j + s), slot 0 the diagonal; a slot past the row's last block does not run. A step that
    does not run names the next block of its own query (key) block that does, else the diagonal
    — always a block of the same row: the index maps fetch what ``data_next`` names, and an
    index outside ``[0, B * n)`` is a DMA out of bounds on the chip, not an exception. The
    diagonal always runs, so in the forward's grid order that is the next step that runs."""
    batch, n, _ = needed.shape
    place = jnp.arange(batch * n, dtype=jnp.int32).reshape(batch, n, 1)  # a block's, among the rows end to end
    block, slot = jnp.arange(n, dtype=jnp.int32), jnp.arange(width, dtype=jnp.int32)

    def band(pairs, partner, diagonal):
        # pairs[b, i, partner[i, s]], nothing where the partner lies outside the row. Read out
        # by comparing against the constant partner table: a gather of so few elements compiles
        # to several times the program code on the chip, in every layer that builds tables
        runs = (pairs[:, :, None, :] & (block == partner[:, :, None])).any(-1)  # [B, n, width]
        following = jax.lax.cummin(jnp.where(runs, slot, width), axis=2, reverse=True)
        return runs.astype(jnp.int32), place + jnp.where(following == width, diagonal, following) - diagonal

    forward = band(needed, block[:, None] - (width - 1) + slot, width - 1)
    dkv = band(jnp.swapaxes(needed, 1, 2), block[:, None] + slot, 0)
    key_major = lambda t: jnp.swapaxes(t.reshape(batch * n, width), 0, 1)[None]
    return tuple(t.reshape(1, batch * n, width) for t in forward), tuple(key_major(t) for t in dkv)


def splash_block_counters(batch: int, seq: int, segment_ids: jax.Array | None = None, window: int | None = None) -> dict:
    """`SPLASH_COUNTERS` of one attention layer over these rows: the (query block, key block)
    pairs the kernel's tables make it run, and those under the diagonal. Their ratio is what
    the documents (and the layer's `window`, where it has one) left of the work; 1.0 says the
    tables skipped nothing (no segment ids, no window). Layers of one mask share the ids'
    tables; a model whose layers differ by mask asks once a kind
    (`splash_block_counters_by_kind`). Zeros where the kernel does not take the length."""
    if seq % 128 != 0:
        return dict.fromkeys(SPLASH_COUNTERS, jnp.zeros((), jnp.int32))
    block = _pick_block(seq)
    n = seq // block
    causal = jnp.asarray(batch * n * (n + 1) // 2, jnp.int32)
    if segment_ids is not None:
        visited = document_block_pairs(segment_ids, block, window).sum(dtype=jnp.int32)
    elif window is not None:
        visited = jnp.asarray(batch * _block_band(n, window, block).sum(), jnp.int32)
    else:
        visited = causal
    return {"splash_blocks_visited": visited, "splash_blocks_causal": causal}


def splash_block_counters_by_kind(
    batch: int, seq: int, segment_ids: jax.Array | None, window: int, window_layers: int, full_layers: int
) -> dict:
    """`SPLASH_COUNTERS_BY_KIND` of a step whose `window_layers` attention layers run under
    `window` and whose `full_layers` run without: each kind's tables counted once and
    multiplied by its layers, the blocks under the diagonal by all of them."""
    windowed = splash_block_counters(batch, seq, segment_ids, window)
    full = splash_block_counters(batch, seq, segment_ids)
    return {
        "splash_blocks_visited_window": window_layers * windowed["splash_blocks_visited"],
        "splash_blocks_visited_full": full_layers * full["splash_blocks_visited"],
        "splash_blocks_causal": (window_layers + full_layers) * full["splash_blocks_causal"],
    }


def _rows_end_to_end(x: jax.Array) -> jax.Array:
    """``[B, S, H, D] -> [H, B * S, D]``: each head's rows one after the other."""
    return jnp.transpose(x, (2, 0, 1, 3)).reshape(x.shape[2], x.shape[0] * x.shape[1], x.shape[3])


def _splash_attention_local(q, k, v, segment_ids, softmax_scale: float, interpret: bool, window: int | None = None):
    """jax's splash kernel over one device's rows, causal (under a `window`, where the layer
    has one), blocks of `_pick_block`.

    Without segment ids: the static causal kernel (`make_splash_mha_single_device`), whose
    tables know the diagonal, under `jax.vmap` over the rows. With segment ids: the same
    kernel functions on tables built from the ids (`_document_block_tables`), so the blocks
    no document spans are not run; the rows go in end to end as one sequence (Pallas would
    batch a per-row scalar-prefetch operand with a loop of slices and copies over the rows).
    Those tables are a row wide, and a launch's grid walks a row's key slots for every query
    block; under a `window` that reaches fewer they are as wide as the reach
    (`_banded_block_tables`: 5 of 32 slots at a window of 2048 on rows of 16384 in blocks of
    512), and the same blocks run in the same order.
    The mask inside a block — causal on positions (jax's `CausalMask` function; with a
    window its `LocalMask` function: ``q - (window - 1) <= k <= q``), equality on segment ids —
    is the same on both paths, and a skipped block is one whose every entry it masked."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    from ..models.modeling_utils import ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME
    from ..utils.telemetry import get_telemetry

    batch, sq, num_q_heads, _ = q.shape
    skv = k.shape[1]

    bq, bkv = _pick_block(sq), _pick_block(skv)
    block_sizes = _sk.BlockSizes(
        block_q=bq,
        block_kv=bkv,
        block_kv_compute=bkv,
        block_q_dkv=bq,
        block_kv_dkv=bkv,
        block_kv_dkv_compute=bkv,
        block_q_dq=bq,
        block_kv_dq=bkv,
    )
    for seen in _RESIDUAL_WATCHERS:
        # the output is as wide as the values (latent attention scores over a wider head)
        seen.append(num_q_heads * sq * (v.shape[3] * q.dtype.itemsize + 4))
    plan = dict(
        block_q=bq,
        block_kv=bkv,
        rows=batch,
        # a launch's grid: heads x query blocks x key slots (dkv: key blocks x heads x query slots)
        grid=(num_q_heads, batch * (sq // bq), skv // bkv),
        launches_per_call=1 if segment_ids is not None else batch,
        tables="segment_ids" if segment_ids is not None else "static",
        why_static=None if segment_ids is not None else "the call has no segment ids",
        # a layer under a window, and the key blocks (its own among them) a query block can reach
        **({} if window is None else {"window": window, "window_key_blocks": window_block_reach(window, bkv) + 1}),
    )
    # the ids' tables of a layer whose window reaches fewer key blocks than a row has are that
    # narrow, and so is the launches' grid; every other call's plan and tables are untouched
    key_slots = None
    if segment_ids is not None and window is not None and plan["window_key_blocks"] < skv // bkv:
        key_slots = plan["window_key_blocks"]
        plan.update(grid=(num_q_heads, batch * (sq // bq), key_slots), key_slots=key_slots)
    get_telemetry().event_once("splash_block_plan", **plan)

    # jax's static causal kernel of one row: its tables know the diagonal. The name makes it
    # tag its output and log-sum-exp with `checkpoint_name`, so a remat policy can keep them
    # (save_dots does) and the backward pass need not run the forward kernel again; outside
    # a remat and under a policy without the name the tag is an identity that does not
    # reach the HLO
    if window is None:
        head_mask = _sm.CausalMask((sq, skv))
    else:
        head_mask = _sm.LocalMask((sq, skv), window_size=(window - 1, 0), offset=0)
    mask = _sm.MultiHeadMask([head_mask for _ in range(num_q_heads)])
    kernel = _sk.make_splash_mha_single_device(
        mask,
        block_sizes=block_sizes,
        residual_checkpoint_name=ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME,
        interpret=interpret,
    )
    if segment_ids is None:
        qt = jnp.swapaxes(q, 1, 2)  # [B, Hq, S, D]
        kt = jnp.swapaxes(k, 1, 2)  # [B, Hkv, S, D]
        vt = jnp.swapaxes(v, 1, 2)
        qs = qt * softmax_scale  # splash has no sm_scale argument
        out = jax.vmap(lambda a, b, c: kernel(a, b, c))(qs, kt, vt)
        return jnp.swapaxes(out, 1, 2)

    # the same kernel (its functions, mask value, in-block causal function) on the documents'
    # tables; the positions are those of the rows laid end to end, where a row's causal
    # order is what it was
    needed = document_block_pairs(segment_ids, bq, window)
    (block_mask, data_next), (block_mask_dkv, data_next_dkv) = (
        _document_block_tables(needed) if key_slots is None else _banded_block_tables(needed, key_slots)
    )
    tables = kernel.fwd_mask_info._replace(
        data_next=data_next, block_mask=block_mask, q_sequence=jnp.arange(batch * sq, dtype=jnp.int32)
    )
    kernel = _sk.SplashAttentionKernel(
        tables,
        tables,  # dq: the forward's block sizes, so the forward's tables
        tables._replace(data_next=data_next_dkv, block_mask=block_mask_dkv),
        **kernel.kwargs,
    )
    ids = segment_ids.astype(jnp.int32).reshape(-1)
    out = kernel(
        _rows_end_to_end(q) * softmax_scale,  # splash has no sm_scale argument
        _rows_end_to_end(k),
        _rows_end_to_end(v),
        segment_ids=_sk.SegmentIds(q=ids, kv=ids),
    )
    return jnp.transpose(out.reshape(num_q_heads, batch, sq, v.shape[3]), (1, 2, 0, 3))


def _tpu_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array | None,
    segment_ids: jax.Array | None,
    causal: bool,
    softmax_scale: float,
) -> jax.Array:
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    # kernel expects [B, H, S, D] with equal head counts
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(_repeat_kv(k, q.shape[2]), 1, 2)
    vt = jnp.swapaxes(_repeat_kv(v, q.shape[2]), 1, 2)

    seg = None
    if segment_ids is not None:
        seg_i = segment_ids.astype(jnp.int32)
        seg = _fa.SegmentIds(q=seg_i, kv=seg_i)

    ab = None
    if bias is not None:
        ab = jnp.broadcast_to(bias, (q.shape[0], q.shape[2], q.shape[1], k.shape[1])).astype(
            jnp.float32
        )

    out = _fa.flash_attention(
        qt,
        kt,
        vt,
        ab=ab,
        segment_ids=seg,
        causal=causal,
        sm_scale=softmax_scale,
        block_sizes=_flash_block_sizes(q.shape[1], k.shape[1]),
    )
    return jnp.swapaxes(out, 1, 2)


def _pick_block(length: int) -> int:
    """Largest of 512/256/128 dividing `length` (both Pallas kernels assert block | seq).
    Shared by the legacy flash and splash paths so block-size tuning can't silently
    diverge between the two sides of the A/B."""
    for block in (512, 256, 128):
        if length % block == 0:
            return block
    return min(128, length)


def _flash_block_sizes(q_len: int, kv_len: int):
    """Explicit kernel tiling for the legacy flash kernel (see _pick_block)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa

    bq = _pick_block(q_len)
    bk = _pick_block(kv_len)
    return _fa.BlockSizes(
        block_q=bq,
        block_k_major=bk,
        block_k=bk,
        block_b=1,
        block_q_major_dkv=bq,
        block_k_major_dkv=bk,
        block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk,
        block_k_dq=bk,
        block_q_dq=bq,
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    implementation: AttentionImplementation = AttentionImplementation.sdpa,
    causal: bool = True,
    softmax_scale: float | None = None,
    attention_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    alibi_bias: jax.Array | None = None,
    softmax_in_fp32: bool = True,
    dropout: float = 0.0,
    dropout_rng: jax.Array | None = None,
    query_offset: jax.Array | int = 0,
    window: int | None = None,
) -> jax.Array:
    """Dispatch to the configured implementation; returns [B, Sq, Hq, D]. `window`: a query
    sees itself and the ``window - 1`` keys before it (causal attention only)."""
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a window of {window} keys on {'causal' if causal else 'non-causal'} attention")

    if implementation in (AttentionImplementation.ring, AttentionImplementation.ulysses):
        if window is not None:
            raise NotImplementedError(
                f"{implementation.value} attention under a window of {window}: the exchange of key/value "
                "shards knows no window (a shard further back than the window would still travel); not built"
            )
        from ..parallel.mesh import MeshManager
        from .ring_attention import ring_attention_sharded
        from .ulysses_attention import ulysses_attention_sharded

        cp_name = implementation.value
        sp = MeshManager.axis_size("sp") if MeshManager.is_initialized() else 1
        tp = MeshManager.axis_size("tp") if MeshManager.is_initialized() else 1
        use_cp = (
            sp > 1
            and q.shape[1] == k.shape[1]  # no decode-with-cache over CP
            and q.shape[1] % sp == 0
            and attention_mask is None  # padded batches: use packed segment_ids instead
            and alibi_bias is None
            and dropout == 0.0
            and causal
        )
        if implementation == AttentionImplementation.ulysses:
            # the head all_to_all needs sp | local q-head count. Mirror the wrapper's
            # shard_heads decision (ulysses_attention_sharded): when q or kv heads don't
            # divide tp it runs with heads UNsharded, so the requirement is sp | Hq, not
            # sp | Hq/tp — gating on the per-tp-shard count here would wrongly drop legal
            # configs to sdpa and silently lose CP.
            shard_heads = tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
            local_heads = q.shape[2] // tp if shard_heads else q.shape[2]
            use_cp = use_cp and local_heads % sp == 0
        if use_cp:
            cp_fn = (
                ring_attention_sharded
                if implementation == AttentionImplementation.ring
                else ulysses_attention_sharded
            )
            # K/V stay un-repeated: GQA grouping happens inside the CP body so ICI moves
            # only kv heads (ring) / the minimal grouped repeat (ulysses)
            return cp_fn(
                q,
                k,
                v,
                MeshManager.get_mesh(),
                causal=True,
                softmax_scale=softmax_scale,
                segment_ids=segment_ids,
            )
        if sp > 1:
            # the mesh HAS sequence sharding but this call can't ride CP — say so
            # once per trace so the user knows the CP savings aren't happening here
            import logging

            from ..utils import log_rank_0

            log_rank_0(
                logging.WARNING,
                f"{cp_name} attention fell back to sdpa (requires: no kv cache, no "
                "attention_mask — use packed segment_ids, no alibi, no dropout, causal, "
                f"seq divisible by sp={sp}"
                + (", sp | local q heads" if implementation == AttentionImplementation.ulysses else "")
                + ")",
            )
        implementation = AttentionImplementation.sdpa

    if (
        implementation == AttentionImplementation.flash_attention_2
        and attention_mask is not None
        and attention_mask.ndim == 2  # key-side [B, S] padding mask
        and segment_ids is None
        and causal
        and q.shape[1] == k.shape[1]
        and isinstance(query_offset, int)
        and query_offset == 0
    ):
        # key-side padding mask -> segment ids (pad = 0, real = 1): the padding-free packed
        # representation the flash kernel already understands, so left-padded batches
        # (finetuning, generation prefill) ride the Pallas kernel instead of masked sdpa.
        # Pad queries attend only among themselves (segment 0); their outputs are never read.
        segment_ids = attention_mask.astype(jnp.int32)
        attention_mask = None

    use_flash = False
    if implementation == AttentionImplementation.flash_attention_2 and jax.default_backend() == "tpu":
        # off-TPU the request quietly means sdpa (the CPU tests rely on it); on a TPU a
        # dropped kernel is said once per reason — a run that believes it measures the
        # flash kernel must be able to see that it does not
        dropped = [
            reason
            for reason, hit in (
                ("attention dropout is on", dropout != 0.0),
                ("an attention_mask that is not a plain key-side padding mask", attention_mask is not None),
                ("query and key lengths differ (kv cache)", q.shape[1] != k.shape[1]),
                ("sequence length is not a multiple of 128", q.shape[1] % 128 != 0),
            )
            if hit
        ]
        use_flash = not dropped
        if dropped:
            from ..utils import warn_rank_0

            warn_rank_0("flash_attention_2 lowers as sdpa here: " + "; ".join(dropped))
    if use_flash:
        if causal and alibi_bias is None and _use_splash_kernel():
            return _tpu_splash_attention(q, k, v, segment_ids, softmax_scale, window=window)
        if window is not None:
            raise NotImplementedError(
                f"the legacy flash kernel under a window of {window}: its causal mask knows no window; "
                "run the splash kernel (kernel_args splash_attention) or sdpa"
            )
        return _tpu_flash_attention(q, k, v, alibi_bias, segment_ids, causal, softmax_scale)

    if segment_ids is not None and q.shape[1] != k.shape[1]:
        raise NotImplementedError("packed segment attention with KV cache is not supported")

    mask = make_attention_mask(
        q.shape[0],
        q.shape[1],
        k.shape[1],
        causal=causal,
        attention_mask=attention_mask,
        segment_ids_q=segment_ids,
        query_offset=query_offset,
        window=window,
    )

    if implementation == AttentionImplementation.eager or dropout > 0.0:
        return eager_attention(
            q,
            k,
            v,
            mask,
            alibi_bias,
            softmax_scale,
            softmax_in_fp32=softmax_in_fp32,
            dropout=dropout,
            dropout_rng=dropout_rng,
        )

    return sdpa_attention(q, k, v, mask, alibi_bias, softmax_scale)
