"""The causal depthwise convolution over time of a packed row: the Mamba-2 mixer's (`nemotron_h`:
4 taps, a bias, silu after it) and the gated short convolution's (`lfm2_moe`: 3 taps, no bias,
no activation). A tap that would read before the row's start or into another document reads
zero: a tap that crosses is a wrong model, not a slow one."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv1d(
    x: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Causal depthwise convolution over time: ``y_t = b + sum_k w[:, k] x_{t-(K-1-k)}``
    (torch ``Conv1d(groups=C, padding=K-1)`` cut to ``T``: the last tap reads ``x_t``).

    x ``[B, T, C]``, weight ``[C, K]``, bias ``[C]``. A tap whose token lies before the row
    or in another document (``segment_ids`` differ) contributes nothing."""
    length = x.shape[1]
    taps = weight.shape[-1]
    y = x * weight[:, taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
        if segment_ids is not None:
            earlier = jnp.pad(segment_ids, ((0, 0), (back, 0)), constant_values=-1)[:, :length]
            shifted = jnp.where((earlier == segment_ids)[..., None], shifted, 0)
        y = y + shifted * weight[:, taps - 1 - back]
    if bias is not None:
        y = y + bias
    return y
