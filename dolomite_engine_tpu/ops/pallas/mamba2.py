"""The Mamba-2 chunked selective scan as two Pallas TPU kernels, forward and backward.

`ops/mamba2.mamba2_chunked` is the contract (same mathematics, same precision): inside a
chunk of ``L`` tokens the output is ``(decay o C B^T o dt) X``, each chunk leaves a state,
and the state is carried from chunk to chunk. Differentiated by JAX, that form writes
``cb``, ``span``, ``decay`` and ``scores`` — ``[B, chunks, heads, L, L]`` each — and a
cotangent of each to HBM, a layer and a pass: some thirty passes over half a gigabyte
where the scan's inputs and outputs are a third of one. Here they live in VMEM.

**Channels-major.** The kernels read and write ``[B, channels, T]``: x and y as blocks
``[R*P, L]`` (the ``R`` heads of a group under one another, time along the lanes), B and C
as ``[N, L]``. That is the layout XLA itself gives the mixer's activations on a TPU (time
minor: the convolution shifts along it, the gated norm reduces over channel groups), so the
transposes around the kernel are changes of the logical shape and no copies — the first
version took ``[B, T, channels]`` blocks and the step gained 20 ms of layout copies around
it (PERF.md section 6, PR 29). It is also the friendlier orientation for the kernel:
every product with the state streams ``R*P`` rows past one ``[N, L]`` operand, a head's
rows are a sublane slice, a sum over a head's width is a sum over sublanes, and a
token's per-head numbers broadcast as rows.

One launch a pass. The grid is (row, group, chunk) with the chunk axis sequential; a grid
step holds one chunk of one group and the group's running state ``[R*P, N]`` float32 in
VMEM scratch. The forward does the in-chunk product (one head at a time: only ``scores``
differs between the heads of a group), the entering state's part ``S C`` and the state's
update in that step. The backward walks the chunks in reverse with the state's cotangent
in scratch and builds the in-chunk tensors again.

What depends on a token and a head but not on the head's width — ``dt``, the cumulative
logarithm of the decay, and the decays that touch the state (``reach``: entering state to
token, ``te``: token to the state the chunk leaves, times ``dt``; a state's decay across
the whole chunk is ``reach`` at the chunk's last token) — is computed outside, head-major
``[B, H, T]`` float32 (4 MB a tensor at the benchmark's shapes), by the formulas of the
`jnp` form, and JAX differentiates that part: the kernel's rule returns the cotangents of
``dt``, ``cum``, ``reach`` and ``te`` as its inputs had them, so the sums over 16384 tokens
behind the gradients of ``A_log``, ``D`` and ``dt_bias`` are float32 sums. Document
boundaries live in those decays and in the in-chunk pair mask, as in the `jnp` form.

The backward rule keeps its inputs and the state entering every chunk
(``[B, chunks, H*P, N]`` float32, written by the forward launch of the rule; the primal
launch writes none): nothing ``[L, L]``.

The pair ``(s, l)`` of an in-chunk tensor has the earlier token ``s`` on the sublanes, so a
head's ``cum`` and ``dt`` are needed as columns too: they come in as rows and one
``[128, L]`` transpose a grid step gives the columns; ``dt``'s cotangent, which arises as
a column, goes back through one transpose the other way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def tiles(batch: int, length: int, heads: int, width: int, groups: int, state: int, chunk: int) -> bool:
    """Whether the kernels tile these shapes (the scan's, in `kept_bytes`' order; the number
    of rows does not matter): blocks whose last dimension is a multiple of
    128 lanes, a head's rows and a group's head rows in whole sublane tiles, every
    ``[L, L]`` tensor small enough to stay near the registers."""
    if groups <= 0 or heads % groups:
        return False
    per_group = heads // groups
    return (
        chunk in (128, 256)
        and length % chunk == 0
        and state % LANES == 0
        and (per_group * width) % LANES == 0
        and LANES % width == 0
        and width % 16 == 0
        and per_group % 8 == 0
        and 2 * per_group + 8 <= LANES
    )


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _columns(*rows):
    """``[k, L]`` float32 arrays, stacked and transposed: ``[L, 128]`` whose lane ``i`` is
    row ``i`` of the stack."""
    used = sum(r.shape[0] for r in rows)
    length = rows[0].shape[1]
    stack = jnp.concatenate([*rows, jnp.zeros((LANES - used, length), _F32)], axis=0)
    return stack.T


def _pair_mask(seg_col, seg_row):
    """``[S, L]``: the earlier token ``s`` (sublanes) reaches the later ``l`` (lanes)."""
    length = seg_col.shape[0]
    earlier = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    later = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    return (earlier <= later) & (seg_col == seg_row)


def _over_width(values, heads, width: int, lanes=slice(None)):
    """A 128-row tile's heads' rows of `values` ``[R, L]`` (or of its `lanes`), each repeated
    over its head's ``P`` sublanes: ``[128, L]``."""
    blocks = [jnp.broadcast_to(values[h : h + 1, lanes], (width, values[:, lanes].shape[1])) for h in heads]
    return jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]


def _skip_column(d_ref, heads, width: int):
    """``D`` of a tile's heads (scalars in SMEM, indexed over all heads) as a column ``[128, 1]``."""
    first = pl.program_id(1) * (d_ref.shape[0] // pl.num_programs(1))
    blocks = [jnp.full((width, 1), d_ref[first + h], _F32) for h in heads]
    return jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]


def _set_row(rows, head: int, row):
    """`rows` ``[R, L]`` with row `head` replaced by `row` ``[1, L]``."""
    sublane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(sublane == head, row, rows)


def _chunk(b_ref, c_ref, dt_ref, cum_ref, seg_ref, per_group: int):
    """What both kernels build of a chunk before they walk its heads: B and C ``[N, L]``,
    ``cb`` ``[S, L]`` (``B_s . C_l``), the pair mask, and `decay_and_dt(head)`: a head's
    ``decay`` ``[S, L]`` (float32) and its ``dt`` as a column over ``s``."""
    bm, cm, cum = b_ref[...], c_ref[...], cum_ref[...]
    seg_row = seg_ref[...]  # [1, L]
    cols = _columns(cum, dt_ref[...], jnp.broadcast_to(seg_row, (8, seg_row.shape[1])))
    pair = _pair_mask(cols[:, 2 * per_group : 2 * per_group + 1], seg_row)

    def decay_and_dt(head: int):
        span = cum[head : head + 1, :] - cols[:, head : head + 1]  # cum_l - cum_s
        return jnp.exp(jnp.where(pair, span, -jnp.inf)), cols[:, per_group + head : per_group + head + 1]

    return bm, cm, _dot(bm, cm, _TN), decay_and_dt


# Both kernels walk a group's ``R*P`` rows a 128-row tile (``128 / P`` heads) at a time and
# finish everything of a tile before the next, so that what is live is a few ``[128, L]``
# float32 values and not ``[R*P, L]`` ones, each four times the register file.


def _forward_kernel(
    x_ref, b_ref, c_ref, dt_ref, cum_ref, reach_ref, te_ref, seg_ref, d_ref,
    y_ref, *rest, per_group: int, width: int,
):  # fmt: skip
    entering_ref, state = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    dtype = x_ref.dtype
    reach, te = reach_ref[...], te_ref[...]  # [R, L]
    length = te.shape[1]
    bm, cm, cb, decay_and_dt = _chunk(b_ref, c_ref, dt_ref, cum_ref, seg_ref, per_group)
    in_tile = LANES // width
    for tile in range(per_group * width // LANES):
        rows = slice(tile * LANES, (tile + 1) * LANES)
        heads = range(tile * in_tile, (tile + 1) * in_tile)
        x = x_ref[rows, :]  # [128, S]
        entering = state[rows, :]  # [128, N]
        if entering_ref is not None:
            entering_ref[rows, :] = entering
        # inside the chunk, a head at a time: only `scores` differs between a group's heads
        y = []
        for j, head in enumerate(heads):
            decay, dt_col = decay_and_dt(head)
            scores = (decay * cb * dt_col).astype(dtype)
            y.append(_dot(x[j * width : (j + 1) * width, :], scores))
        y = jnp.concatenate(y, axis=0) if in_tile > 1 else y[0]
        # the entering state's part, and the skip
        y = y + _dot(entering.astype(dtype), cm) * _over_width(reach, heads, width)
        y_ref[rows, :] = (y + x.astype(_F32) * _skip_column(d_ref, heads, width)).astype(dtype)
        # what the chunk leaves; the state's decay across the chunk is reach at its last token
        weighted = x * _over_width(te, heads, width).astype(dtype)
        through = _over_width(reach, heads, width, slice(length - 1, length))
        state[rows, :] = entering * through + _dot(weighted, bm, _NT)


def _backward_kernel(
    x_ref, b_ref, c_ref, dt_ref, cum_ref, reach_ref, te_ref, seg_ref, d_ref, entering_ref, dy_ref,
    dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dreach_ref, dte_ref, dd_ref,
    dstate, *, per_group: int, width: int,
):  # fmt: skip
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk: the grid walks them in reverse
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    dt, reach, te = dt_ref[...], reach_ref[...], te_ref[...]
    length = te.shape[1]
    bm, cm, cb, decay_and_dt = _chunk(b_ref, c_ref, dt_ref, cum_ref, seg_ref, per_group)
    in_tile = LANES // width
    lane = jax.lax.broadcasted_iota(jnp.int32, (length, LANES), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, length), 1) == length - 1
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    cb_ct = jnp.zeros((length, length), _F32)
    dcum = dreach = dte = dd = jnp.zeros((per_group, length), _F32)
    dt_ct_cols = jnp.zeros((length, LANES), _F32)  # lane h: head h's dt cotangent, as a column over s
    for tile in range(per_group * width // LANES):
        rows = slice(tile * LANES, (tile + 1) * LANES)
        heads = range(tile * in_tile, (tile + 1) * in_tile)
        x, dy = x_ref[rows, :], dy_ref[rows, :]
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        entering = entering_ref[rows, :]  # [128, N] float32
        leaving_ct = dstate[rows, :]  # the cotangent of the state this chunk leaves
        te_wide = _over_width(te, heads, width).astype(dtype)
        through = _over_width(reach, heads, width, slice(length - 1, length))

        # y's part from the entering state: y += reach o (S C)
        entering_lo = entering.astype(dtype)
        dy_reach = (dy32 * _over_width(reach, heads, width)).astype(dtype)
        dc = dc + _dot(entering_lo, dy_reach, _TN)  # [N, L]
        reach_ct = dy32 * _dot(entering_lo, cm)

        # the state the chunk leaves: through o S + (x o te) B^T
        leaving_ct_lo = leaving_ct.astype(dtype)
        weighted_ct = _dot(leaving_ct_lo, bm)  # [128, S]
        db = db + _dot(leaving_ct_lo, x * te_wide, _TN)  # [N, S]
        dx = weighted_ct * te_wide.astype(_F32) + dy32 * _skip_column(d_ref, heads, width)
        te_ct = weighted_ct * x32
        through_ct = leaving_ct * entering
        skip_ct = dy32 * x32
        dstate[rows, :] = leaving_ct * through + _dot(dy_reach, cm, _NT)

        dx_in = []
        for j, head in enumerate(heads):
            mine = slice(j * width, (j + 1) * width)
            head_sum = lambda v: jnp.sum(v[mine, :], axis=0, keepdims=True)  # noqa: E731
            # through is reach at the chunk's last token: its cotangent goes there
            to_last = jnp.where(last, jnp.sum(head_sum(through_ct), axis=1, keepdims=True), 0.0)
            dreach = _set_row(dreach, head, head_sum(reach_ct) + to_last)
            dte = _set_row(dte, head, head_sum(te_ct))
            dd = _set_row(dd, head, head_sum(skip_ct))
            # inside the chunk
            decay, dt_col = decay_and_dt(head)
            scores_ct = _dot(x[mine, :], dy[mine, :], _TN) * decay  # [S, L], before cb and dt
            cb_ct = cb_ct + scores_ct * dt_col
            span_ct = scores_ct * cb  # the cotangent of dt's factor; times dt, of span's
            dt_ct_cols = jnp.where(lane == head, jnp.sum(span_ct, axis=1, keepdims=True), dt_ct_cols)
            dcum = _set_row(dcum, head, jnp.sum(span_ct * dt_col, axis=0, keepdims=True))
            dx_in.append(_dot(dy[mine, :], (decay * cb * dt_col).astype(dtype), _NT))  # [P, S]
        dx_ref[rows, :] = (dx + (jnp.concatenate(dx_in, axis=0) if in_tile > 1 else dx_in[0])).astype(dx_ref.dtype)

    cb_ct = cb_ct.astype(dtype)
    db_ref[...] = (db + _dot(cm, cb_ct, _NT)).astype(db_ref.dtype)
    dc_ref[...] = (dc + _dot(bm, cb_ct)).astype(dc_ref.dtype)
    ddt = dt_ct_cols.T[:per_group]  # [R, S]
    ddt_ref[...] = ddt
    dcum_ref[...] = dcum - ddt * dt  # as l: + the sums over s; as s: - the sums over l
    dreach_ref[...] = dreach
    dte_ref[...] = dte
    dd_ref[...] += dd


def _specs(batch, length, heads, width, groups, state, chunk, reverse: bool):
    """Block specs of the operands both kernels share, and of the entering states."""
    per_group = heads // groups
    chunks = length // chunk
    at = (lambda ch: chunks - 1 - ch) if reverse else (lambda ch: ch)
    channels = lambda rows: pl.BlockSpec((None, rows, chunk), lambda b, g, ch: (b, g, at(ch)))  # noqa: E731
    shared = [
        channels(per_group * width),  # x
        channels(state),  # B
        channels(state),  # C
        *[channels(per_group)] * 4,  # dt, cum, reach, te
        pl.BlockSpec((None, 1, chunk), lambda b, g, ch: (b, 0, at(ch))),  # segments
        pl.BlockSpec(memory_space=pltpu.SMEM),  # D
    ]
    entering = pl.BlockSpec((None, None, per_group * width, state), lambda b, g, ch: (b, at(ch), g, 0))
    return shared, entering


def _launch(kernel, name, statics, in_specs, out_specs, out_shape, interpret, operands):
    batch, length, heads, width, groups, state, chunk = statics
    per_group = heads // groups
    return pl.pallas_call(
        functools.partial(kernel, per_group=per_group, width=width),
        grid=(batch, groups, length // chunk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((per_group * width, state), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20,
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def _forward(statics, interpret, keep_states, operands):
    batch, length, heads, width, groups, state, chunk = statics
    shared, entering = _specs(*statics, reverse=False)
    x = operands[0]
    out_specs = [shared[0]]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep_states:
        out_specs.append(entering)
        out_shape.append(jax.ShapeDtypeStruct((batch, length // chunk, heads * width, state), _F32))
    return _launch(_forward_kernel, "mamba2_scan_fwd", statics, shared, out_specs, out_shape, interpret, operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(statics, interpret, x, b, c, dt, cum, reach, te, seg, d):
    return _forward(statics, interpret, False, (x, b, c, dt, cum, reach, te, seg, d))[0]


def _scan_fwd(statics, interpret, *operands):
    y, entering = _forward(statics, interpret, True, operands)
    return y, (operands, entering)


def _scan_bwd(statics, interpret, residuals, dy):
    batch, length, heads, width, groups, state, chunk = statics
    operands, entering = residuals
    x, b, c, dt, _, _, _, seg, _ = operands
    shared, entering_spec = _specs(*statics, reverse=True)
    per_group = heads // groups
    out_specs = [
        *shared[:7],
        pl.BlockSpec((None, per_group, chunk), lambda b_, g, ch: (b_, g, 0)),  # D's: summed over chunks
    ]
    out_shape = [
        *(jax.ShapeDtypeStruct(v.shape, v.dtype) for v in (x, b, c)),
        *(jax.ShapeDtypeStruct(dt.shape, _F32) for _ in range(4)),
        jax.ShapeDtypeStruct((batch, heads, chunk), _F32),
    ]
    *cotangents, dd = _launch(
        _backward_kernel,
        "mamba2_scan_bwd",
        statics,
        [*shared, entering_spec, shared[0]],
        out_specs,
        out_shape,
        interpret,
        (*operands, entering, dy),
    )
    return *cotangents, jnp.zeros_like(seg), jnp.sum(dd, axis=(0, 2))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kept_bytes(batch, length, heads, width, groups, state, chunk, itemsize: int) -> dict:
    """What the backward rule keeps of one layer's scan, in bytes: its inputs (x, B, C; dt,
    cum, reach, te, the segments; D) and the state entering every chunk (float32)."""
    tokens, chunks = batch * length, batch * (length // chunk)
    return {
        "inputs": tokens * (heads * width + 2 * groups * state) * itemsize + (tokens * (4 * heads + 1) + heads) * 4,
        "entering_states": chunks * state * heads * width * 4,
    }


def mamba2_chunked_kernel(
    x: jax.Array,
    dt: jax.Array,
    a_log_decay: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    segment_ids: jax.Array | None = None,
    chunk_size: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """`ops/mamba2.mamba2_chunked` through the kernels: the same arguments, the same result.
    The shapes must tile (`tiles`)."""
    batch, length, heads, width = x.shape
    groups, state = b.shape[-2:]
    if not tiles(batch, length, heads, width, groups, state, chunk_size):
        raise ValueError(
            f"the Mamba-2 scan kernel does not tile x {x.shape}, B {b.shape}, chunk {chunk_size}"
        )
    if interpret is None:
        from ...utils.packages import pallas_interpret_mode

        interpret = pallas_interpret_mode()
    chunks = length // chunk_size

    # a number a (row, head, token), float32, by the `jnp` form's formulas
    dt = jnp.swapaxes(dt.astype(_F32), 1, 2).reshape(batch, heads, chunks, chunk_size)
    cum = jnp.cumsum(dt * a_log_decay.astype(_F32)[:, None, None], axis=-1)
    if segment_ids is None:
        seg = jnp.zeros((batch, 1, chunks, chunk_size), jnp.int32)
    else:
        seg = segment_ids.reshape(batch, 1, chunks, chunk_size)
    last_seg = seg[..., -1:]
    entering_seg = jnp.pad(last_seg, ((0, 0), (0, 0), (1, 0), (0, 0)), constant_values=-1)[:, :, :chunks]
    reach = jnp.where(seg == entering_seg, jnp.exp(cum), 0.0)
    te = jnp.where(seg == last_seg, jnp.exp(cum[..., -1:] - cum), 0.0) * dt
    head_major = lambda v: v.reshape(batch, heads, length)  # noqa: E731
    channels_major = lambda v, n: jnp.swapaxes(v.reshape(batch, length, n), 1, 2)  # noqa: E731

    y = _scan(
        (batch, length, heads, width, groups, state, chunk_size),
        interpret,
        channels_major(x, heads * width),
        channels_major(b, groups * state),
        channels_major(c, groups * state),
        head_major(dt),
        head_major(cum),
        head_major(reach),
        head_major(te),
        seg.reshape(batch, 1, length).astype(_F32),
        d.astype(_F32),
    )
    return jnp.swapaxes(y, 1, 2).reshape(batch, length, heads, width)
