"""Required operations and bytes of the gated short convolution's gates and taps (training:
forward + backward) over the tokens of the traced steps, and the scope by which the trace finds
them.

The program computes ``y = C * conv(B * u)`` — a gate, a causal depthwise convolution of
``taps`` taps with a reset at document boundaries, a gate — as elementwise XLA operations under
the ``short_conv_gates_taps`` scope (``dolomite_engine_tpu/models/lfm2_moe.ShortConv``,
``ops/causal_conv.causal_conv1d``); JAX differentiates it, so the backward pass runs under
``transpose(...)`` of the same scope, and under block remat the forward runs twice. The part is
memory-bound: what a later fused kernel is judged by is the least traffic, in the activations'
dtype, of a pass that keeps nothing but its inputs: forward reads B, C and u and writes y;
backward reads B, C, u and dy and writes dB, dC and du (the products between them need not leave
the chip; the filter, its gradient and the segment ids are small and left out). Operations:
a token's channel takes a multiply for each gate and a multiply-add for each tap forward, and
about twice that backward.
"""

from __future__ import annotations

from benchmark.kernels.splash_attention import roofline_seconds  # noqa: F401  (the same rule)

SCOPE = "short_conv_gates_taps"


def train_flops(channels: int, taps: int, conv_layers: int, tokens: float) -> float:
    return 3.0 * (2.0 * taps + 2.0) * channels * conv_layers * tokens


def train_bytes(channels: int, conv_layers: int, tokens: float, itemsize: int = 2) -> float:
    forward = 3 + 1
    backward = 4 + 3
    return float(tokens) * conv_layers * channels * (forward + backward) * itemsize
