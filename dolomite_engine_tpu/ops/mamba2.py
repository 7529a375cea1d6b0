"""Mamba-2 (state-space duality) mixer ops: the selective scan in chunks and its token-by-token
form; the causal depthwise convolution in front of them lives in `ops/causal_conv.py` since a
second family uses it (re-exported here) — all three reset at document boundaries of a packed
row.

Recurrence, per head ``h`` of width ``P`` with a state ``S`` of ``[P, N]`` (``N`` the state
size; ``B`` and ``C`` are shared by the heads of a group, head ``h`` reads group
``h // (H // G)``):

    a_t = exp(A_h * dt_t)                      A_h = -exp(A_log_h) < 0, dt_t > 0
    S_t = a_t S_{t-1} + dt_t x_t B_t^T         S is zero before a document's first token
    y_t = S_t C_t + D_h x_t

`mamba2_recurrent` runs it as written (`lax.scan` over time): the ground truth of the tests.
`mamba2_chunked` is the training form ("Transformers are SSMs", Dao & Gu 2024, the minimal
SSD algorithm): inside a chunk of ``L`` tokens the output is a masked ``[L, L]`` matrix
product ``(decay o C B^T) X``, each chunk leaves a state, and the states are carried from
chunk to chunk by a short scan. The decay's logarithms, their cumulative sums and the
exponentials stay in float32; the matrix products take the operands' dtype and accumulate
in float32; the state is carried in float32; ``scores`` is rounded to the operands' dtype
before its product. That is the contract, and it has two lowerings, chosen by `mamba2_scan`
(the entry the mixer calls) from what the trace observes:

  - `mamba2_chunked`, plain `jnp`, differentiated by JAX under a `jax.checkpoint`: what runs
    off the TPU, under a mesh of several devices and at shapes the kernel does not tile, and
    what the kernel is tested against. Its ``[L, L]`` tensors and their cotangents go
    through HBM.
  - `ops/pallas/mamba2.mamba2_chunked_kernel`: one Pallas kernel a pass with a backward
    rule of its own (`jax.custom_vjp`), which keeps every ``[L, L]`` tensor in VMEM. A
    `custom_vjp`'s backward inherits the scopes of its call, so both launches are found
    under the mixer's ``mamba2_scan`` scope.

Packed rows: ``segment_ids`` ``[B, T]`` (equal ids = one document, non-decreasing along a
row). A pair of tokens of different documents has decay zero, a chunk's incoming state
reaches only the tokens of the document that the previous chunk ended in, and a
convolution tap that would read the previous document reads zero. A state or a tap that
crosses into the next document is a wrong model, not a slow one.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp

from .causal_conv import causal_conv1d  # noqa: F401  (two families' convolution now: its own module)


def mamba2_recurrent(
    x: jax.Array,
    dt: jax.Array,
    a_log_decay: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """The recurrence token by token. x ``[B, T, H, P]``; dt ``[B, T, H]`` (after softplus);
    a_log_decay ``[H]`` (``A_h``, negative); b, c ``[B, T, G, N]``; d ``[H]``. Returns
    ``[B, T, H, P]`` in float32."""
    batch, length, heads, width = x.shape
    groups, state = b.shape[-2:]
    per_group = heads // groups
    f32 = jnp.float32
    x, dt, b, c = x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32)
    b = jnp.repeat(b, per_group, axis=2)  # [B, T, H, N]
    c = jnp.repeat(c, per_group, axis=2)
    if segment_ids is None:
        first = jnp.zeros((batch, length), bool)
    else:
        before = jnp.pad(segment_ids, ((0, 0), (1, 0)), constant_values=-1)[:, :length]
        first = before != segment_ids

    def step(s, inputs):
        x_t, dt_t, b_t, c_t, first_t = inputs
        decay = jnp.exp(a_log_decay.astype(f32) * dt_t)  # [B, H]
        decay = jnp.where(first_t[:, None], 0.0, decay)
        s = s * decay[..., None, None] + jnp.einsum("bh,bhp,bhn->bhpn", dt_t, x_t, b_t)
        y_t = jnp.einsum("bhpn,bhn->bhp", s, c_t) + d.astype(f32)[:, None] * x_t
        return s, y_t

    time_major = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(
        step,
        jnp.zeros((batch, heads, width, state), f32),
        tuple(map(time_major, (x, dt, b, c, first))),
    )
    return jnp.moveaxis(y, 0, 1)


def mamba2_chunked(
    x: jax.Array,
    dt: jax.Array,
    a_log_decay: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    segment_ids: jax.Array | None = None,
    chunk_size: int = 128,
) -> jax.Array:
    """The same function in chunks of ``chunk_size`` tokens (``T`` must divide). Shapes as
    `mamba2_recurrent`; returns ``[B, T, H, P]`` in x's dtype."""
    batch, length, heads, width = x.shape
    groups, state = b.shape[-2:]
    per_group = heads // groups
    if length % chunk_size:
        raise ValueError(f"sequence length {length} is not a multiple of the chunk {chunk_size}")
    chunks = length // chunk_size
    f32 = jnp.float32
    dtype = x.dtype

    # [B, c, L, ...]; heads as (group, head in group) so that B and C broadcast over a group
    xc = x.reshape(batch, chunks, chunk_size, groups, per_group, width)
    dtc = dt.astype(f32).reshape(batch, chunks, chunk_size, groups, per_group)
    bc = b.reshape(batch, chunks, chunk_size, groups, state)
    cc = c.reshape(batch, chunks, chunk_size, groups, state)
    log_a = dtc * a_log_decay.astype(f32).reshape(groups, per_group)
    cum = jnp.cumsum(log_a, axis=2)  # inclusive: sum of log a over the chunk up to l

    if segment_ids is None:
        seg = jnp.zeros((batch, chunks, chunk_size), jnp.int32)
    else:
        seg = segment_ids.reshape(batch, chunks, chunk_size)
    last_seg = seg[:, :, -1]
    # the document the state that enters a chunk belongs to (none enters the first chunk)
    entering_seg = jnp.pad(last_seg, ((0, 0), (1, 0)), constant_values=-1)[:, :chunks]

    # ---- inside a chunk: y_l += sum_{s<=l, same document} exp(cum_l - cum_s) dt_s (C_l.B_s) x_s
    causal = jnp.tril(jnp.ones((chunk_size, chunk_size), bool))
    pair = causal & (seg[:, :, :, None] == seg[:, :, None, :])  # [B, c, L, S]
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, preferred_element_type=f32)
    cum_h = jnp.moveaxis(cum, 2, -1)  # [B, c, G, R, L]
    span = cum_h[..., :, None] - cum_h[..., None, :]  # [B, c, G, R, L, S]
    decay = jnp.exp(jnp.where(pair[:, :, None, None], span, -jnp.inf))
    dt_h = jnp.moveaxis(dtc, 2, -1)  # [B, c, G, R, S]
    scores = (decay * cb[:, :, :, None] * dt_h[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, xc, preferred_element_type=f32)

    # ---- what a chunk leaves: sum_s exp(cum_last - cum_s) dt_s x_s B_s^T over the tokens of
    # the document the chunk ends in
    to_end = jnp.where(
        (seg == last_seg[:, :, None])[..., None, None], jnp.exp(cum[:, :, -1:] - cum), 0.0
    )
    weighted = (xc * (to_end * dtc)[..., None].astype(dtype)).astype(dtype)
    left = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, weighted, preferred_element_type=f32)

    # ---- from chunk to chunk: the state survives a chunk only if the chunk ends in the
    # document it began in
    through = jnp.where(
        (last_seg == entering_seg)[..., None, None], jnp.exp(cum[:, :, -1]), 0.0
    )  # [B, c, G, R]

    def carry(s, inputs):
        through_c, left_c = inputs
        return s * through_c[..., None, None] + left_c, s

    _, entering = jax.lax.scan(
        carry,
        jnp.zeros((batch, groups, per_group, width, state), f32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(left, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, c, G, R, P, N]: the state before chunk c

    # ---- the entering state's part: exp(cum_l) C_l S, for the tokens of its document
    reach = jnp.where((seg == entering_seg[:, :, None])[..., None, None], jnp.exp(cum), 0.0)
    from_state = jnp.einsum(
        "bclgn,bcgrpn->bclgrp", cc, entering.astype(dtype), preferred_element_type=f32
    )
    y = y + from_state * reach[..., None]
    y = y + xc.astype(f32) * d.astype(f32).reshape(groups, per_group)[..., None]
    return y.reshape(batch, length, heads, width).astype(dtype)


# lists that `watch_scan_lowerings` opened, innermost last
_LOWERING_WATCHERS: list[list[dict]] = []


@contextmanager
def watch_scan_lowerings():
    """What `mamba2_scan` chose in the trace inside: one entry a call (`scan_lowering`'s
    record). The model reads it for its ``mamba2_scan_plan`` event."""
    seen: list[dict] = []
    _LOWERING_WATCHERS.append(seen)
    try:
        yield seen
    finally:
        _LOWERING_WATCHERS.pop()


def scan_lowering(x_shape: tuple, bc_shape: tuple, chunk_size: int, itemsize: int = 2) -> dict:
    """Which lowering of the chunked scan a trace takes, and why: ``form`` (``kernel`` or
    ``jnp``), ``reason``, the ``chunk``, the kernel launches a pass, and the bytes the
    kernel's backward rule keeps (of them the states entering the chunks). The rule
    `ops/moe._share_grouped_product` uses for megablox: on a TPU, in a trace with no
    multi-device mesh, at shapes the kernel tiles (`ops/pallas/mamba2.tiles`), the kernel;
    anything else the `jnp` form (``reason`` ``backend``, ``mesh`` or ``shape``). Nothing a
    user sets. Under a mesh the Mosaic kernel would have to go through
    `parallel.sharding.shard_kernel` with the scan's layout across chips (rows over the data
    axes), which is not built; off the TPU it would run interpreted. A row the chunk does
    not divide is one chunk of the whole row, in `jnp`."""
    from ..parallel.sharding import kernel_sharding

    length = x_shape[1]
    chunk = chunk_size if length % chunk_size == 0 else length
    plan = {"form": "jnp", "chunk": chunk, "launches_per_pass": 0, "kept_bytes": 0, "kept_state_bytes": 0}
    layout = ((tuple(x_shape), (None,) * len(x_shape)),)
    if jax.default_backend() != "tpu":
        return dict(plan, reason="backend")
    if kernel_sharding(layout, layout) is not None:
        return dict(plan, reason="mesh")
    from .pallas import mamba2 as kernel

    shapes = (*x_shape, *bc_shape[-2:], chunk)
    if not kernel.tiles(*shapes):
        return dict(plan, reason="shape")
    kept = kernel.kept_bytes(*shapes, itemsize)
    return dict(
        plan,
        form="kernel",
        reason="one TPU, shapes that tile",
        launches_per_pass=1,
        kept_bytes=sum(kept.values()),
        kept_state_bytes=kept["entering_states"],
    )


def mamba2_scan(
    x: jax.Array,
    dt: jax.Array,
    a_log_decay: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    segment_ids: jax.Array | None = None,
    chunk_size: int = 128,
) -> jax.Array:
    """The chunked scan as the mixer runs it (arguments and result as `mamba2_chunked`; a
    row the chunk does not divide is taken as one chunk): the kernel where `scan_lowering`
    says so, else the `jnp` form under a `jax.checkpoint` — its ``[L, L]`` residuals are
    cheaper to build again than to keep; the kernel's rule keeps none."""
    plan = scan_lowering(x.shape, b.shape, chunk_size, x.dtype.itemsize)
    operands = (x, dt, a_log_decay, b, c, d, segment_ids, plan["chunk"])
    if plan["form"] == "kernel":
        from .pallas.mamba2 import mamba2_chunked_kernel

        y = mamba2_chunked_kernel(*operands)
    else:
        y = jax.checkpoint(mamba2_chunked, static_argnums=(7,))(*operands)
    if _LOWERING_WATCHERS:
        _LOWERING_WATCHERS[-1].append(plan)
    return y


def gated_group_rmsnorm(
    y: jax.Array, gate: jax.Array, weight: jax.Array, groups: int, eps: float
) -> jax.Array:
    """``RMSNorm(y * silu(gate))`` over ``groups`` equal slices of the last axis, then the
    weight (Mamba-2's gated norm with ``norm_before_gate`` false). float32 inside, and
    re-computed in the backward pass: its float32 intermediates are three times its inputs."""

    @jax.checkpoint
    def norm(y, gate, weight):
        dtype = y.dtype
        h = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        grouped = h.reshape(*h.shape[:-1], groups, h.shape[-1] // groups)
        grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
        return (grouped.reshape(h.shape) * weight.astype(jnp.float32)).astype(dtype)

    return norm(y, gate, weight)
