"""Grouped-GEMM MoE dispatch Pallas kernels (scattermoe/megablocks technique).

The dense "eager" path in `ops/moe.py` runs EVERY expert over EVERY token
(``num_experts / top_k`` wasted FLOPs); the XLA "scatter" path fixes the FLOPs with
`jax.lax.ragged_dot` but still lowers through a generic einsum. This module is the
hand-written tier: token-expert assignments are stable-sorted by expert, each expert's
rows are **padded to GEMM-block boundaries** (the scattermoe ``padded_block_indices``
trick — at most one wasted ``block_rows`` tile per expert), and a Pallas kernel walks the
block list with a scalar-prefetched block->expert map, so each grid step runs one dense
``[block_rows, in] x [in, out]`` MXU tile against exactly the right expert bank — no
dynamic shapes, no capacity-factor token dropping.

Three kernels:
- ``_gmm_kernel``: forward grouped GEMM (block-diagonal lhs x per-expert rhs);
- the same kernel with the transposed banks computes dL/dx in the backward;
- ``_tgmm_kernel``: per-expert ``x^T dy`` accumulation for dL/dw (consecutive grid steps
  share an expert's output block, the standard Pallas revisit-accumulate pattern).

`grouped_mlp` composes fc -> activation -> proj over one padded layout; `experts_grouped`
is the drop-in for `ops/moe.experts_eager`/`experts_ragged` (same signature family, same
dropless semantics). Gates, biases, and the activation stay in plain jnp between the
GEMMs — they are elementwise and XLA fuses them; only the GEMMs need the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# this module is only imported behind the `config.use_pallas` capability gate, so the
# Pallas import is unconditional here
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_default(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    from ...utils.packages import pallas_interpret_mode

    return pallas_interpret_mode()


def _pick_block_rows(assignments: int) -> int:
    for block in (128, 64, 32, 16, 8):
        if assignments >= block:
            return block
    return max(assignments, 1)


# ------------------------------------------------------------------------------ kernels


def _gmm_kernel(block_expert_ref, x_ref, w_ref, o_ref):
    o_ref[:] = jnp.dot(
        x_ref[:], w_ref[0], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _tgmm_kernel(block_expert_ref, x_ref, dy_ref, o_ref):
    b = pl.program_id(0)
    first = jnp.logical_or(
        b == 0, block_expert_ref[b] != block_expert_ref[jnp.maximum(b - 1, 0)]
    )

    @pl.when(first)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] += jnp.dot(
        x_ref[:].T, dy_ref[:], preferred_element_type=jnp.float32
    )[None]


def _gmm_call(xp, w, block_expert, block_rows: int, interpret: bool):
    """Forward grouped GEMM over the padded layout: ``out[b] = xp[b] @ w[expert(b)]``."""
    num_blocks = block_expert.shape[0]
    _, k_dim = xp.shape
    n_dim = w.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, k_dim), lambda b, ge: (b, 0)),
            pl.BlockSpec((1, k_dim, n_dim), lambda b, ge: (ge[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n_dim), lambda b, ge: (b, 0)),
    )
    with jax.named_scope("pallas_moe_dispatch"):
        return pl.pallas_call(
            _gmm_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_blocks * block_rows, n_dim), xp.dtype),
            interpret=interpret,
        )(block_expert, xp, w)


def _tgmm_call(xp, dy, w_shape, block_expert, block_rows: int, interpret: bool):
    """dL/dw: accumulate ``xp_block^T @ dy_block`` into each block's expert bank. Every
    expert owns >= 1 block (empty groups get a zero-row block), so every output block is
    written; revisits are consecutive because blocks are expert-sorted."""
    num_blocks = block_expert.shape[0]
    _, k_dim = xp.shape
    n_dim = dy.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, k_dim), lambda b, ge: (b, 0)),
            pl.BlockSpec((block_rows, n_dim), lambda b, ge: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, k_dim, n_dim), lambda b, ge: (ge[b], 0, 0)),
    )
    with jax.named_scope("pallas_moe_dispatch"):
        return pl.pallas_call(
            _tgmm_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(w_shape, jnp.float32),
            interpret=interpret,
        )(block_expert, xp, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_gemm(xp, w, block_expert, block_rows: int, interpret: bool):
    """``out[b*bm:(b+1)*bm] = xp[b*bm:(b+1)*bm] @ w[block_expert[b]]`` (padded layout)."""
    return _gmm_call(xp, w, block_expert, block_rows, interpret)


def _grouped_gemm_fwd(xp, w, block_expert, block_rows, interpret):
    return _gmm_call(xp, w, block_expert, block_rows, interpret), (xp, w, block_expert)


def _grouped_gemm_bwd(block_rows, interpret, residuals, dy):
    xp, w, block_expert = residuals
    dx = _gmm_call(
        dy, jnp.swapaxes(w, 1, 2), block_expert, block_rows, interpret
    ).astype(xp.dtype)
    dw = _tgmm_call(xp, dy, w.shape, block_expert, block_rows, interpret).astype(w.dtype)
    dge = np.zeros(block_expert.shape, jax.dtypes.float0)  # int input: symbolic zero
    return dx, dw, dge


grouped_gemm.defvjp(_grouped_gemm_fwd, _grouped_gemm_bwd)


# ------------------------------------------------------------------------ padded layout


def _padded_layout(group_sizes: jax.Array, assignments: int, block_rows: int):
    """Static-shape block plan for expert-sorted rows.

    Returns ``(block_expert [NB], group_block_start [E], group_start [E], NB)`` with
    ``NB = cdiv(A, bm) + E``: every expert rounds up to whole blocks AND gets at least
    one block (the tgmm zero-init depends on every bank being visited). Trailing entries
    of ``block_expert`` repeat the last expert over all-zero rows — wasted-but-harmless
    tiles, never gathered back."""
    num_experts = group_sizes.shape[0]
    num_blocks = -(-assignments // block_rows) + num_experts  # static upper bound
    blocks_per_group = jnp.maximum(-(-group_sizes // block_rows), 1)
    block_expert = jnp.repeat(
        jnp.arange(num_experts, dtype=jnp.int32),
        blocks_per_group,
        total_repeat_length=num_blocks,
    )
    group_block_start = (jnp.cumsum(blocks_per_group) - blocks_per_group) * block_rows
    group_start = jnp.cumsum(group_sizes) - group_sizes
    return block_expert, group_block_start, group_start, num_blocks


def grouped_mlp(
    xs: jax.Array,
    sorted_experts: jax.Array,
    group_sizes: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """fc -> act -> proj for rows already sorted by expert id; returns rows in the same
    sorted order. ``group_sizes`` must cover every expert bank row of ``w_fc``."""
    assignments = xs.shape[0]
    num_experts = w_fc.shape[0]
    assert group_sizes.shape == (num_experts,), (group_sizes.shape, num_experts)
    block_rows = block_rows or _pick_block_rows(assignments)
    interpret = _interpret_default(interpret)

    block_expert, group_block_start, group_start, num_blocks = _padded_layout(
        group_sizes, assignments, block_rows
    )
    dest = (
        jnp.take(group_block_start, sorted_experts)
        + jnp.arange(assignments, dtype=jnp.int32)
        - jnp.take(group_start, sorted_experts)
    )
    padded_rows = num_blocks * block_rows
    xp = jnp.zeros((padded_rows, xs.shape[1]), xs.dtype).at[dest].set(xs)
    row_expert = jnp.repeat(block_expert, block_rows, total_repeat_length=padded_rows)

    h = grouped_gemm(xp, w_fc, block_expert, block_rows, interpret)
    if b_fc is not None:
        h = h + jnp.take(b_fc, row_expert, axis=0)
    h = act(h)
    y = grouped_gemm(h, w_proj, block_expert, block_rows, interpret)
    if b_proj is not None:
        y = y + jnp.take(b_proj, row_expert, axis=0)
    return jnp.take(y, dest, axis=0)


def experts_grouped(
    x: jax.Array,
    router_weights: jax.Array,
    selected_experts: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act,
    num_experts: int,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Dropless grouped-GEMM expert compute on the Pallas tier — the kernel-backed
    equivalent of `ops/moe.experts_ragged` (same sort/scatter framing, kernels instead of
    `ragged_dot`). x: [T, d]; router_weights/selected_experts: [T, k]; weight banks are
    the `[E, d, f]` / `[E, f, d]` layout `ops/moe.py` asserts."""
    tokens, hidden = x.shape
    top_k = selected_experts.shape[-1]

    flat_experts = selected_experts.reshape(-1)
    order = jnp.argsort(flat_experts, stable=True)
    sorted_experts = jnp.take(flat_experts, order)
    group_sizes = jnp.bincount(flat_experts, length=num_experts)
    token_index = order // top_k

    ys = grouped_mlp(
        jnp.take(x, token_index, axis=0),
        sorted_experts,
        group_sizes,
        w_fc,
        b_fc,
        w_proj,
        b_proj,
        act,
        block_rows=block_rows,
        interpret=interpret,
    )
    gates = jnp.take(router_weights.reshape(-1), order).astype(ys.dtype)
    out = jnp.zeros((tokens, hidden), dtype=ys.dtype)
    return out.at[token_index].add(ys * gates[:, None])


# ---------------------------------------------------------- a share's grouped product


def held_grouped_product(
    rows: jax.Array, bank: jax.Array, group_sizes: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """``rows[i] @ bank[g]`` for the rows of group ``g``: jax's megablox grouped matmul
    (`jax.experimental.pallas.ops.tpu.megablox`, with its own backward: the same kernel on
    the transposed bank for the rows' gradient, a transposed grouped product for the
    bank's). The drop-in for `jax.lax.ragged_dot` in `ops/moe.experts_held_ragged`: the
    grid walks the tiles of the groups that have rows, so rows past ``sum(group_sizes)``
    cost nothing — and are left as they were: the caller masks them.

    rows ``[m, a]``, bank ``[groups, a, b]``. One device's kernel: under a multi-device mesh
    it would have to go through `parallel.sharding.shard_kernel`, and the share's layout
    across chips (experts over ``ep``) is not built — the caller takes `jax.lax.ragged_dot`
    there (`ops/moe._share_grouped_product`)."""
    import math

    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, a = rows.shape
    b = bank.shape[-1]
    tiling = (math.gcd(m, 512), min(a, 1024), min(b, 1024))
    with jax.named_scope("pallas_moe_grouped_product"):
        return gmm(
            rows,
            bank,
            group_sizes,
            preferred_element_type=rows.dtype,
            tiling=tiling,
            interpret=_interpret_default(interpret),
        )


# ------------------------------------------------- the held experts' activation, routed rows only

# what a launch of `routed_row_blocks` may hold in VMEM: two copies of every side's block and
# the float32 values between them (`ops/moe._KERNEL_BLOCK_BYTES` sizes the blocks); a v5e
# has 128 MiB, and a kernel that does not ask gets 16
_ROW_BLOCKS_VMEM_BYTES = 64 * 2**20


def routed_row_blocks(
    of_block, count: jax.Array, sides: tuple, width: int, block_rows: int, *, interpret: bool | None = None
) -> jax.Array:
    """``of_block(*blocks)`` for the blocks of `block_rows` rows of `sides` (``[rows, w]``
    each) that hold one of the first `count` rows, in a ``[rows, width]`` array of their
    dtype: one elementwise launch over all the blocks, `count` handed to it before the grid
    runs. A grid step past the last routed row computes nothing and moves nothing — its
    block indices are the last routed block's, which VMEM already holds — so the rows past
    that block are what the buffer held: undefined. `of_block` gets and gives float32
    (Mosaic has no bfloat16 comparison or broadcast on a v5e), rounded once on the way out.
    The drop-in for `ops/moe._loop_row_blocks`, on one device."""
    rows = sides[0].shape[0]

    def at(index, count_ref):
        return jnp.minimum(index, jnp.maximum(pl.cdiv(count_ref[0], block_rows) - 1, 0)), 0

    def kernel(count_ref, *refs):
        @pl.when(pl.program_id(0) * block_rows < count_ref[0])
        def _():
            here = of_block(*(ref[...].astype(jnp.float32) for ref in refs[:-1]))
            refs[-1][...] = here.astype(refs[-1].dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block_rows),),
            in_specs=[pl.BlockSpec((block_rows, side.shape[1]), at) for side in sides],
            out_specs=pl.BlockSpec((block_rows, width), at),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, width), sides[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_ROW_BLOCKS_VMEM_BYTES
        ),
        interpret=_interpret_default(interpret),
        name="moe_routed_row_blocks",  # the launch's name in a trace and in the compiled step
    )(jnp.reshape(count, (1,)).astype(jnp.int32), *sides)
