"""MoE ops: routing, load-balancing loss, and grouped expert compute.

Parity: reference `hf_models/models/moe_dolomite/moe/`:
  - routing (`base.py:124-135`): gate linear -> top-k logits -> softmax over the SELECTED
    logits in fp32 (not softmax-then-gather) -> cast back to input dtype.
  - eager expert compute (`base.py:137-178`): sort tokens by expert, per-expert matmul.
  - ScatterMoE (`moe/scatter.py:56-141`): external Triton `parallel_linear` kernels over
    flatten_and_sort / padded_block_indices. The TPU-native equivalent here is
    `jax.lax.ragged_dot` (grouped GEMM over contiguous expert groups) after a stable sort of
    token-expert assignments — same dropless semantics, MXU-friendly, no capacity factor.
  - load-balancing aux loss (`moe_dolomite/base.py:24-43`) delegates to HF mixtral
    `load_balancing_loss_func`; the exact formula is reimplemented in
    `load_balancing_loss` below (concat layers -> softmax -> top-k mask -> E * sum(frac * prob)).

The "eager" path below intentionally runs every expert on every token (dense einsum over the
expert axis). That costs num_experts/top_k extra FLOPs but is fully static, shards cleanly over
the "ep" mesh axis (einsum contraction -> psum inserted by GSPMD), and is the numerical
reference for the ragged path.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp



def route(
    router_logits: jax.Array, top_k: int
) -> tuple[jax.Array, jax.Array]:
    """Top-k routing (reference `moe/base.py:124-135, 176-183`).

    Returns (router_weights [T, k] in input dtype, selected_experts [T, k] int32).
    Softmax is computed over the selected top-k logits in fp32.
    """
    top_logits, selected = jax.lax.top_k(router_logits, top_k)
    weights = jax.nn.softmax(top_logits.astype(jnp.float32), axis=-1)
    return weights.astype(router_logits.dtype), selected.astype(jnp.int32)


def route_sigmoid_bias(
    router_logits: jax.Array,
    top_k: int,
    correction_bias: jax.Array,
    scaling_factor: float = 1.0,
    normalize: bool = True,
    epsilon: float = 1e-20,
) -> tuple[jax.Array, jax.Array]:
    """The routing rule of the families routed by sigmoid scores (DeepSeek-V3's): chosen with
    a bias and weighed without it. ``s = sigmoid(logits)`` in float32 over all experts, the
    top-k of ``s + correction_bias`` are chosen, and the weights are the chosen experts'
    ``s``, divided by their sum + `epsilon` where `normalize` (the family's own: 1e-20 in
    `nemotron_h` and `joyai_llm_flash`'s public code, 1e-6 in `lfm2_moe`'s), times
    `scaling_factor`. The bias is a buffer: it chooses, and no gradient reaches it.

    Returns (router_weights [T, k] float32, selected_experts [T, k] int32)."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    biased = scores + jax.lax.stop_gradient(correction_bias.astype(jnp.float32))
    _, selected = jax.lax.top_k(biased, top_k)
    weights = jnp.take_along_axis(scores, selected, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + epsilon)
    return weights * scaling_factor, selected.astype(jnp.int32)


def load_balancing_loss(
    router_logits: jax.Array, num_experts: int, top_k: int, token_mask: jax.Array | None = None
) -> jax.Array:
    """Switch/Mixtral auxiliary load-balancing loss.

    `router_logits`: [num_layers * tokens, num_experts] (all layers concatenated, matching
    HF `load_balancing_loss_func` as called at reference `moe_dolomite/base.py:39`).
    `token_mask`: optional [num_layers * tokens] validity mask — the reference calls the HF
    func WITHOUT a mask (pad tokens pollute router statistics); masking here matches what
    HF's `attention_mask` argument does and is strictly more correct for padded batches.
    """
    routing_weights = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _, selected_experts = jax.lax.top_k(routing_weights, top_k)
    expert_mask = jax.nn.one_hot(selected_experts, num_experts, dtype=jnp.float32)  # [T, k, E]
    if token_mask is None:
        tokens_per_expert = jnp.mean(expert_mask, axis=0)  # [k, E]
        router_prob_per_expert = jnp.mean(routing_weights, axis=0)  # [E]
    else:
        m = token_mask.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(m), 1.0)
        tokens_per_expert = jnp.sum(expert_mask * m[:, None, None], axis=0) / denom
        router_prob_per_expert = jnp.sum(routing_weights * m[:, None], axis=0) / denom
    return jnp.sum(tokens_per_expert * router_prob_per_expert[None, :]) * num_experts


def experts_eager(
    x: jax.Array,
    combine: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act: Callable,
) -> jax.Array:
    """Dense all-experts compute: every expert runs on every token, weighted by `combine`.

    x: [T, d]; combine: [T, E] (zero for unselected experts); w_fc: [E, d, f];
    w_proj: [E, f, d]. This path is NOT expert-parallel: on an ep > 1 mesh the einsums
    all-gather every expert bank onto every device (distributed experts go through
    `experts_ep_a2a`, dispatched by models/moe_dolomite.py). It is the single-device
    numerical reference the ragged / grouped-GEMM (`ops/pallas/moe.py`) paths are pinned
    against.

    The `[E, d, f]` / `[E, f, d]` bank layout below is a contract shared with the
    Pallas grouped-GEMM kernel (its BlockSpecs index expert banks on axis 0 and contract
    axis 1 against the incoming rows) — asserted here so a transposed import surfaces at
    the reference path too, not just inside the kernel.
    """
    assert w_fc.ndim == 3 and w_proj.ndim == 3, (w_fc.shape, w_proj.shape)
    num_experts, hidden, fc_out = w_fc.shape
    intermediate = w_proj.shape[1]
    # GLU activations emit [up | gate], so c_fc's output axis is f or 2f
    assert w_proj.shape == (num_experts, intermediate, hidden) and fc_out in (
        intermediate,
        2 * intermediate,
    ), (
        f"w_proj {w_proj.shape} must be the [E, f, d] partner of w_fc {w_fc.shape} "
        f"([E, d, f] or [E, d, 2f] for GLU)"
    )
    assert x.shape[-1] == hidden and combine.shape == (x.shape[0], num_experts), (
        x.shape,
        combine.shape,
        w_fc.shape,
    )
    assert x.dtype == w_fc.dtype == w_proj.dtype, (x.dtype, w_fc.dtype, w_proj.dtype)
    h = jnp.einsum("td,edf->etf", x, w_fc)
    if b_fc is not None:
        h = h + b_fc[:, None, :]
    h = act(h)
    y = jnp.einsum("etf,efd->etd", h, w_proj)
    if b_proj is not None:
        y = y + b_proj[:, None, :]
    return jnp.einsum("etd,te->td", y, combine.astype(y.dtype))


def experts_ragged(
    x: jax.Array,
    router_weights: jax.Array,
    selected_experts: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act: Callable,
    num_experts: int,
) -> jax.Array:
    """Dropless grouped-GEMM expert compute (ScatterMoE equivalent, `moe/scatter.py:56-141`).

    Stable-sort the (token, expert) assignments by expert id so each expert's tokens are
    contiguous, then two `jax.lax.ragged_dot` grouped matmuls, then scatter-add back with the
    routing gates (reference `_compute_experts` base.py:137-156).
    """
    tokens, hidden = x.shape
    top_k = selected_experts.shape[-1]

    flat_experts = selected_experts.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_experts, stable=True)  # [T*k]
    token_index = order // top_k  # source token of each sorted slot
    group_sizes = jnp.bincount(flat_experts, length=num_experts)

    xs = jnp.take(x, token_index, axis=0)  # [T*k, d]
    h = jax.lax.ragged_dot(xs, w_fc, group_sizes.astype(jnp.int32))
    if b_fc is not None:
        h = h + jnp.take(b_fc, jnp.take(flat_experts, order), axis=0)
    h = act(h)
    y = jax.lax.ragged_dot(h, w_proj, group_sizes.astype(jnp.int32))
    if b_proj is not None:
        y = y + jnp.take(b_proj, jnp.take(flat_experts, order), axis=0)

    gates = jnp.take(router_weights.reshape(-1), order).astype(y.dtype)  # [T*k]
    out = jnp.zeros((tokens, hidden), dtype=y.dtype)
    return out.at[token_index].add(y * gates[:, None])


def _one_tpu(rows: jax.Array) -> bool:
    """Whether a Mosaic kernel can be launched on `rows` as they are: on a TPU, in a trace
    with no multi-device mesh (under one the kernel would have to go through
    `parallel.sharding.shard_kernel` with the share's layout across chips, experts over
    ``ep``, which is not built; off the TPU it would run interpreted)."""
    from ..parallel.sharding import kernel_sharding

    layout = ((rows.shape, (None, None)),)
    return jax.default_backend() == "tpu" and kernel_sharding(layout, layout) is None


def _share_grouped_product(rows: jax.Array) -> Callable:
    """``product(rows, bank, group_sizes)``: ``rows[i] @ bank[g]`` for the rows of group ``g``
    (``group_sizes`` in order; rows past their sum belong to no group and come out
    undefined). On one TPU (`_one_tpu`) jax's megablox grouped matmul
    (`ops/pallas/moe.held_grouped_product`), else `jax.lax.ragged_dot`. Nothing a user sets:
    on a v5e the TPU compiler's own `ragged_dot` kernel ran these shapes at 23-33 TFLOP/s and
    megablox at 50-90 (PERF.md, PR 26)."""
    if _one_tpu(rows):
        from .pallas.moe import held_grouped_product

        return held_grouped_product
    return jax.lax.ragged_dot


class _Walk(NamedTuple):
    """What a walk over sorted slots knows before the step: the rows on the tokens' side, the
    slots a token has, and the slots a loop step moves."""

    tokens: int
    top_k: int
    block_rows: int


# a loop step moves about this many bytes of rows (8 MiB: 2048 rows of 2048 bfloat16)
_WALK_BLOCK_BYTES = 8 * 2**20


def _walk_block_rows(capacity: int, hidden: int, itemsize: int, block_bytes: int | None = None) -> int:
    """The largest divisor of `capacity` whose rows are at most `block_bytes`
    (`_WALK_BLOCK_BYTES`)."""
    most = max(1, min(capacity, (block_bytes or _WALK_BLOCK_BYTES) // (hidden * itemsize)))
    return next(rows for rows in range(most, 0, -1) if capacity % rows == 0)


def _take_rows(
    walk: _Walk,
    source: jax.Array,
    slot: jax.Array,
    count: jax.Array,
    gates: jax.Array | None = None,
    partner: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """``rows[i] = source[slot[i] // top_k]`` (times ``gates[slot[i]]``) for the slots ``i <
    count``, block by block, stopping after the block that holds the last of them; the rows
    past `count` are zero and no row of `source` is read for them. With `partner`
    (``[len(slot), d]``, read up to `count` only) also the gates' gradient, float32 ``[tokens
    * top_k]``: ``sum_i <partner[i], source[slot[i] // top_k]>`` added at ``slot[i]``."""
    block = walk.block_rows
    rows = jnp.zeros((slot.shape[0], source.shape[1]), source.dtype)
    dgates = None if partner is None else jnp.zeros((walk.tokens * walk.top_k,), jnp.float32)

    def step(index, carry):
        rows, dgates = carry
        at = index * block
        slot_here = jax.lax.dynamic_slice_in_dim(slot, at, block)
        live = at + jnp.arange(block) < count
        got = jnp.take(source, slot_here // walk.top_k, axis=0)
        if partner is not None:
            beside = jax.lax.dynamic_slice_in_dim(partner, at, block)
            dots = jnp.sum(beside.astype(jnp.float32) * got.astype(jnp.float32), axis=-1)
            dgates = dgates.at[slot_here].add(jnp.where(live, dots, 0.0))
        if gates is not None:
            got = got * jnp.take(gates, slot_here).astype(got.dtype)[:, None]
        rows = jax.lax.dynamic_update_slice_in_dim(rows, jnp.where(live[:, None], got, 0), at, 0)
        return rows, dgates

    return jax.lax.fori_loop(0, -(-count // block), step, (rows, dgates))


def _add_rows(
    walk: _Walk,
    out: jax.Array,
    rows: jax.Array,
    slot: jax.Array,
    count: jax.Array,
    gates: jax.Array | None = None,
) -> jax.Array:
    """``out[slot[i] // top_k] += rows[i]`` (times ``gates[slot[i]]``) for the slots ``i <
    count``, block by block as `_take_rows` walks them; no row of `rows` past `count` reaches
    `out`, whatever it holds. The transpose of `_take_rows`."""
    block = walk.block_rows

    def step(index, out):
        at = index * block
        slot_here = jax.lax.dynamic_slice_in_dim(slot, at, block)
        live = at + jnp.arange(block) < count
        given = jax.lax.dynamic_slice_in_dim(rows, at, block)
        if gates is not None:
            given = given * jnp.take(gates, slot_here).astype(given.dtype)[:, None]
        # (a select, after the product: what is not a number stays out)
        return out.at[slot_here // walk.top_k].add(jnp.where(live[:, None], given, 0))

    return jax.lax.fori_loop(0, -(-count // block), step, out)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_rows(walk: _Walk, x: jax.Array, slot: jax.Array, count: jax.Array) -> jax.Array:
    """The dispatch's gather: the token's row of `x` for each of the first `count` sorted
    slots of `slot`, zeros after. A loop whose trip count is a value of the step has no
    reverse rule of its own, so the rule is written here: the transpose is `_add_rows`."""
    return _take_rows(walk, x, slot, count)[0]


def _dispatch_rows_fwd(walk, x, slot, count):
    return _take_rows(walk, x, slot, count)[0], (slot, count)


def _dispatch_rows_bwd(walk, kept, d_rows):
    slot, count = kept
    d_x = _add_rows(walk, jnp.zeros((walk.tokens, d_rows.shape[1]), d_rows.dtype), d_rows, slot, count)
    return d_x, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine_rows(
    walk: _Walk, out: jax.Array, y: jax.Array, gates: jax.Array, slot: jax.Array, count: jax.Array
) -> jax.Array:
    """The combine's weighted scatter-add: `out` plus ``gates[slot[i]] * y[i]`` at the token
    of each of the first `count` sorted slots. Its rule gathers the cotangent's rows back
    (times the gates) and reads the gates' gradient off the same rows (`_take_rows`)."""
    return _add_rows(walk, out, y, slot, count, gates)


def _combine_rows_fwd(walk, out, y, gates, slot, count):
    return _add_rows(walk, out, y, slot, count, gates), (y, gates, slot, count)


def _combine_rows_bwd(walk, kept, d_out):
    y, gates, slot, count = kept
    d_y, d_gates = _take_rows(walk, d_out, slot, count, gates, partner=y)
    return d_out, d_y, d_gates.astype(gates.dtype), None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


class _Activation(NamedTuple):
    """The pass between the two grouped products as planned before the step: the family's
    callable, the rows a block of its walk takes, and the walk's form (`_share_activation`)."""

    act: Callable
    block_rows: int
    form: str


# a grid step of the activation's kernel holds about this many bytes of its wider side in VMEM
# (twice: the next block arrives while this one is computed), beside the other side and the
# float32 values it computes with
_KERNEL_BLOCK_BYTES = 4 * 2**20


def _share_activation(rows: jax.Array, act: Callable, capacity: int, width: int) -> _Activation:
    """How `_activate_rows` walks ``[capacity, width]`` rows of `rows`' dtype. ``"pallas"``:
    one elementwise launch a pass (`ops/pallas/moe.routed_row_blocks`), where
    a kernel can be launched (`_one_tpu`), both widths fill whole lane rows of 128
    and a block whole sublane tiles — at another width XLA copies all `capacity` rows into
    the layout Mosaic wants and back, the very pass this walk is there to avoid (the tower's
    1856). Else ``"xla_loop"``: `lax.fori_loop` over the same blocks, as the rows' walks.
    Nothing a user sets."""
    widths = (width, jax.eval_shape(act, jax.ShapeDtypeStruct((1, width), rows.dtype)).shape[1])
    size = (capacity, max(widths), rows.dtype.itemsize)
    block = _walk_block_rows(*size, _KERNEL_BLOCK_BYTES)
    if _one_tpu(rows) and not any(w % 128 for w in widths) and block % 16 == 0:
        return _Activation(act, block, "pallas")
    return _Activation(act, _walk_block_rows(*size), "xla_loop")


def _loop_row_blocks(of_block: Callable, count: jax.Array, sides: tuple, width: int, block_rows: int) -> jax.Array:
    """``of_block(*blocks)`` for the blocks of `block_rows` rows of `sides` that hold one of the
    first `count` rows, in a ``[rows, width]`` buffer; the loop stops after the block that
    holds the last of them, and the rows past it are zero."""

    def step(index, out):
        at = index * block_rows
        here = of_block(*(jax.lax.dynamic_slice_in_dim(side, at, block_rows) for side in sides))
        return jax.lax.dynamic_update_slice_in_dim(out, here.astype(out.dtype), at, 0)

    out = jnp.zeros((sides[0].shape[0], width), sides[0].dtype)
    return jax.lax.fori_loop(0, -(-count // block_rows), step, out)


def _row_blocks(form: str) -> Callable:
    if form == "xla_loop":
        return _loop_row_blocks
    from .pallas.moe import routed_row_blocks

    return routed_row_blocks


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _activate_rows(plan: _Activation, h: jax.Array, count: jax.Array) -> jax.Array:
    """``plan.act`` over the rows of `h` below `count`, block by block, stopping after the
    block that holds the last of them (`act` works row by row; a gated one halves the
    width). What lies past that block is undefined, as `h`'s own rows past `count` are. The
    rule walks the same blocks of `h` and of the cotangent — written here for the reason
    `_dispatch_rows` gives. The kernel computes in float32 and rounds once; the loop is
    `act` as XLA compiles it for `h`'s dtype."""
    width = jax.eval_shape(plan.act, h).shape[1]
    return _row_blocks(plan.form)(plan.act, count, (h,), width, plan.block_rows)


def _activate_rows_fwd(plan, h, count):
    return _activate_rows(plan, h, count), (h, count)


def _activate_rows_bwd(plan, kept, d_out):
    h, count = kept

    def pull(h_here, d_here):
        return jax.vjp(plan.act, h_here)[1](d_here)[0]

    return _row_blocks(plan.form)(pull, count, (h, d_out), h.shape[1], plan.block_rows), None


_activate_rows.defvjp(_activate_rows_fwd, _activate_rows_bwd)


# lists that `watch_dispatch_plans` opened, innermost last
_PLAN_WATCHERS: list[list[dict]] = []


@contextmanager
def watch_dispatch_plans():
    """What `experts_held_ragged` planned in the trace inside: one entry a call (`capacity`,
    `block_rows`, `blocks_per_capacity`, `form`; `activation_block_rows`, `activation_form`,
    `group_sizes`). The model reads it for its ``moe_dispatch_plan`` event."""
    seen: list[dict] = []
    _PLAN_WATCHERS.append(seen)
    try:
        yield seen
    finally:
        _PLAN_WATCHERS.pop()


def experts_held_ragged(
    x: jax.Array,
    router_weights: jax.Array,
    selected_experts: jax.Array,
    w_fc: jax.Array,
    w_proj: jax.Array,
    act: Callable,
    num_experts: int,
    first_expert: int,
    capacity: int | None = None,
) -> tuple[jax.Array, dict]:
    """One chip's share of an expert layer: the banks hold experts ``first_expert ..
    first_expert + E_held - 1`` of a router that scores all `num_experts`.

    Token-slots of held experts are sorted to the front, grouped by expert, and go through
    two grouped products (`_share_grouped_product`); slots of absent experts are dropped before
    them — no dummy bank, nothing stands in for the other chips — and what those experts
    would have added is left out of the result. Shapes are static, the routed rows are not:
    `capacity` (default: four times the even share ``T k E_held / num_experts``, rounded up
    to 512) is the size of the buffers, and the work follows `routed`, the slots the step
    sent here: the dispatch's gather and the combine's weighted scatter-add (`_dispatch_rows`,
    `_combine_rows`), and their transposes in the backward pass, walk the sorted slots in
    blocks of rows (`_walk_block_rows`: from the shapes) and stop after the block that holds
    the last routed one, as the grouped products walk only their groups' tiles, and so does
    the activation between the two products (`_activate_rows`, in blocks of its own:
    `_share_activation`). The groups are read off the sorted keys, not counted slot by slot.
    What lies past `routed` in a buffer is never read: the products and the activation leave
    those rows undefined, and no select stands guard — the loops' bounds do. A step that
    routes more here than `capacity` walks the sorted slots in chunks of `capacity` rows instead (a scan whose
    chunks past the last routed row are skipped, each chunk re-computed in the backward
    pass, the last one costing its rows too): slower, one chunk's rows in memory, and no row
    dropped however uneven the routing. `lax.cond` chooses.

    x ``[T, d]``; router_weights / selected_experts ``[T, k]`` (ids over all experts);
    w_fc ``[E_held, d, f]``, w_proj ``[E_held, f, d]`` (no biases, no GLU: `act` is applied
    to the whole of ``f``). Returns (``[T, d]``, counters): ``routed_slots``,
    ``absent_slots`` and ``fullest_expert_rows`` (int32 scalars) and ``held_expert_rows``
    (int32 ``[E_held]``: the rows of each held expert, whose sum and maximum the others are)."""
    tokens, hidden = x.shape
    top_k = selected_experts.shape[-1]
    held = w_fc.shape[0]
    slots = tokens * top_k
    if capacity is None:
        capacity = -(-4 * slots * held // num_experts // 512) * 512
    capacity = min(max(capacity, 1), slots)
    chunks = -(-slots // capacity)
    walk = _Walk(tokens, top_k, _walk_block_rows(capacity, hidden, x.dtype.itemsize))
    # both asked here, where the model is traced
    grouped_product = _share_grouped_product(x)
    activation = _share_activation(x, act, capacity, w_fc.shape[-1])
    for seen in _PLAN_WATCHERS:
        seen.append(
            {
                "capacity": capacity,
                "block_rows": walk.block_rows,
                "blocks_per_capacity": capacity // walk.block_rows,
                "form": "xla_loop",
                "activation_block_rows": activation.block_rows,
                "activation_form": activation.form,
                "group_sizes": "sorted_keys",
            }
        )

    with jax.named_scope("moe_dispatch"):
        local = selected_experts.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)  # absent experts sort last
        # one stable sort gives the order and the sorted keys, and the keys give the groups
        sorted_key, order = jax.lax.sort(
            (key, jnp.arange(slots, dtype=jnp.int32)), num_keys=1, is_stable=True
        )
        group_ends = jnp.searchsorted(sorted_key, jnp.arange(1, held + 1, dtype=key.dtype)).astype(jnp.int32)
        group_sizes = jnp.diff(group_ends, prepend=0)
        group_starts = group_ends - group_sizes
        routed = group_ends[-1]
        order = jnp.pad(order, (0, chunks * capacity - slots))  # whole chunks to slice

    def add_rows(out, start, x, w_fc, w_proj, gates):
        """Add what the sorted slots ``start .. start + capacity`` give to `out`."""
        with jax.named_scope("moe_dispatch"):
            slot = jax.lax.dynamic_slice_in_dim(order, start, capacity)
            count = jnp.clip(routed - start, 0, capacity)
            xs = _dispatch_rows(walk, x, slot, count)
            # the part of every expert's group that lies in these rows
            sizes = jnp.clip(
                jnp.minimum(group_ends, start + capacity) - jnp.maximum(group_starts, start), 0
            )
        with jax.named_scope("moe_experts"):
            h = _activate_rows(activation, grouped_product(xs, w_fc, sizes), count)
            y = grouped_product(h, w_proj, sizes)
        with jax.named_scope("moe_combine"):
            return _combine_rows(walk, out, y, gates, slot, count)

    def at_once(x, w_fc, w_proj, gates):
        return add_rows(jnp.zeros((tokens, hidden), x.dtype), 0, x, w_fc, w_proj, gates)

    def in_chunks(x, w_fc, w_proj, gates):
        @jax.checkpoint
        def chunk(out, start):
            return jax.lax.cond(
                start < routed,
                lambda out: add_rows(out, start, x, w_fc, w_proj, gates),
                lambda out: out,
                out,
            )

        out, _ = jax.lax.scan(
            lambda out, start: (chunk(out, start), None),
            jnp.zeros((tokens, hidden), x.dtype),
            jnp.arange(chunks, dtype=jnp.int32) * capacity,
        )
        return out

    operands = (x, w_fc, w_proj, router_weights.reshape(-1))
    if chunks == 1:
        out = at_once(*operands)
    else:
        out = jax.lax.cond(routed <= capacity, at_once, in_chunks, *operands)
    counters = {
        "routed_slots": routed,
        "absent_slots": slots - routed,
        "fullest_expert_rows": jnp.max(group_sizes),
        "held_expert_rows": group_sizes,
    }
    return out, counters


def combine_weights(
    router_weights: jax.Array, selected_experts: jax.Array, num_experts: int
) -> jax.Array:
    """Dense [T, E] combine matrix from top-k (weights, indices) — feeds `experts_eager`."""
    one_hot = jax.nn.one_hot(selected_experts, num_experts, dtype=router_weights.dtype)
    return jnp.einsum("tk,tke->te", router_weights, one_hot)


def _local_expert_compute(
    x: jax.Array,
    expert_ids: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act: Callable,
    num_local_experts: int,
) -> jax.Array:
    """Grouped GEMM over rows tagged with a local expert id; id == num_local_experts marks an
    empty slot (routed to a zero-padded dummy bank so the group sizes stay exact). With the
    ``moe_dispatch`` family on the Pallas backend the two `ragged_dot`s are replaced by the
    grouped-GEMM kernel (`ops/pallas/moe.py` grouped_mlp) over the same sorted layout, so
    the EP all_to_all path rides the kernel tier too."""
    order = jnp.argsort(expert_ids, stable=True)
    group_sizes = jnp.bincount(expert_ids, length=num_local_experts + 1).astype(jnp.int32)

    w_fc_pad = jnp.concatenate([w_fc, jnp.zeros_like(w_fc[:1])], axis=0)
    w_proj_pad = jnp.concatenate([w_proj, jnp.zeros_like(w_proj[:1])], axis=0)
    b_fc_pad = (
        None if b_fc is None else jnp.concatenate([b_fc, jnp.zeros_like(b_fc[:1])], axis=0)
    )
    b_proj_pad = (
        None
        if b_proj is None
        else jnp.concatenate([b_proj, jnp.zeros_like(b_proj[:1])], axis=0)
    )

    xs = jnp.take(x, order, axis=0)
    ids_sorted = jnp.take(expert_ids, order)

    from .pallas import use_pallas

    if use_pallas("moe_dispatch"):
        from .pallas.moe import grouped_mlp

        y = grouped_mlp(
            xs, ids_sorted, group_sizes, w_fc_pad, b_fc_pad, w_proj_pad, b_proj_pad, act
        )
    else:
        h = jax.lax.ragged_dot(xs, w_fc_pad, group_sizes)
        if b_fc_pad is not None:
            h = h + jnp.take(b_fc_pad, ids_sorted, axis=0)
        h = act(h)
        y = jax.lax.ragged_dot(h, w_proj_pad, group_sizes)
        if b_proj_pad is not None:
            y = y + jnp.take(b_proj_pad, ids_sorted, axis=0)
    # dummy-slot rows are zero already (zero-padded banks, zero-padded bias); the mask keeps
    # that invariant explicit rather than depending on the padding
    y = jnp.where((ids_sorted < num_local_experts)[:, None], y, 0.0)

    # unsort back to slot order
    return jnp.zeros_like(y).at[order].set(y)


def experts_ep_a2a(
    x: jax.Array,
    router_weights: jax.Array,
    selected_experts: jax.Array,
    w_fc: jax.Array,
    b_fc: jax.Array | None,
    w_proj: jax.Array,
    b_proj: jax.Array | None,
    act: Callable,
    num_experts: int,
    mesh,
    capacity_factor: float = 2.0,
    token_axes: tuple[str, ...] = ("dp", "fsdp", "ep", "tp"),
) -> jax.Array:
    """Expert-parallel dropful dispatch: `all_to_all` token exchange over the "ep" mesh axis.

    The reference never distributes experts (its ScatterMoE only TP-shards the intermediate dim,
    `moe_TP/scatter.py:118-123`, and has no all_to_all anywhere — SURVEY §2.6 names real EP as a
    north-star differentiator). Design: tokens are sharded over every batch-ish axis incl. "tp"
    (so no rank duplicates expert FLOPs); expert banks are sharded over "ep" (E/ep per device,
    gathered over fsdp/tp at entry — ZeRO-style gather-on-use). Each device routes its local
    tokens, packs per-destination send buffers of fixed `capacity` (static shapes for XLA),
    exchanges them with one `lax.all_to_all`, runs its local experts as a grouped GEMM, and
    sends results back with a second all_to_all; gates are applied at the source. Tokens beyond
    capacity are dropped (Switch-Transformer semantics) — `capacity_factor >= ep` guarantees
    droplessness. The token count must divide by the token_axes product (callers fall back to
    the dense paths otherwise, models/moe_dolomite.py).
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape["ep"]
    assert num_experts % ep == 0, f"num_experts {num_experts} not divisible by ep {ep}"
    num_local = num_experts // ep

    def body(x, router_weights, selected_experts, w_fc, b_fc, w_proj, b_proj):
        tokens_local, d = x.shape
        top_k = selected_experts.shape[-1]
        assignments = tokens_local * top_k
        capacity = min(
            assignments, max(1, int(capacity_factor * tokens_local * top_k / ep))  # dolint: disable=tracer-python-cast (all static shapes/config)
        )

        flat_experts = selected_experts.reshape(-1)  # [A]
        dest = flat_experts // num_local  # destination ep shard per assignment
        local_id = flat_experts % num_local

        # slot of each assignment within its destination's buffer (stable sort -> rank in group)
        order = jnp.argsort(dest, stable=True)
        sorted_dest = jnp.take(dest, order)
        first_of_group = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
        rank_sorted = jnp.arange(assignments) - first_of_group
        slot = jnp.zeros((assignments,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

        valid = slot < capacity  # overflow slots scatter out of bounds -> mode="drop"
        token_index = jnp.arange(assignments) // top_k

        send_x = (
            jnp.zeros((ep, capacity, d), x.dtype)
            .at[dest, slot]
            .set(jnp.take(x, token_index, axis=0), mode="drop")
        )
        send_ids = (
            jnp.full((ep, capacity), num_local, jnp.int32)
            .at[dest, slot]
            .set(local_id.astype(jnp.int32), mode="drop")
        )

        recv_x = jax.lax.all_to_all(send_x, "ep", split_axis=0, concat_axis=0, tiled=True)
        recv_ids = jax.lax.all_to_all(send_ids, "ep", split_axis=0, concat_axis=0, tiled=True)

        y = _local_expert_compute(
            recv_x.reshape(ep * capacity, d),
            recv_ids.reshape(ep * capacity),
            w_fc,
            b_fc,
            w_proj,
            b_proj,
            act,
            num_local,
        ).reshape(ep, capacity, d)

        back = jax.lax.all_to_all(y, "ep", split_axis=0, concat_axis=0, tiled=True)

        # combine at the source: gather each assignment's result, weight by its gate
        # (out-of-bounds gathers for dropped slots clamp; the valid mask zeroes them)
        gathered = back[dest, slot]  # [A, d]
        gates = router_weights.reshape(-1).astype(gathered.dtype)
        contrib = gathered * (gates * valid.astype(gathered.dtype))[:, None]
        return jnp.zeros_like(x).at[token_index].add(contrib)

    t_spec = P(token_axes, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            t_spec,
            P(token_axes, None),
            P(token_axes, None),
            P("ep", None, None),
            None if b_fc is None else P("ep", None),
            P("ep", None, None),
            None if b_proj is None else P("ep", None),
        ),
        out_specs=t_spec,
        check_vma=False,
    )(x, router_weights, selected_experts, w_fc, b_fc, w_proj, b_proj)
