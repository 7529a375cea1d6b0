"""Required operations and bytes of the Mamba-2 selective scan (training: forward +
backward) over the tokens of the traced steps, and the scope by which the trace finds it.

The program computes the scan in chunks under the ``mamba2_scan`` scope
(``dolomite_engine_tpu/ops/mamba2.py``); JAX differentiates it, so the backward pass runs
under ``transpose(...)`` of the same scope. Operations: ``flops_nemotron_h``'s count of the
chunked algorithm, 3 x forward. Bytes, the least a scan that keeps nothing but its inputs
could move, in the activations' dtype: forward reads x, B, C and dt and writes y; backward
reads x, B, C, dt and dy and writes dx, dB, dC and ddt (the per-head vectors A, D and their
gradients are small and left out).
"""

from __future__ import annotations

from benchmark.flops_nemotron_h import scan_forward_flops_per_token
from benchmark.kernels.splash_attention import roofline_seconds  # noqa: F401  (the same rule)
from benchmark.weights_nemotron_h import model_dims

SCOPE = "mamba2_scan"


def train_flops(cfg: dict, tokens: int) -> float:
    layers = model_dims(cfg)["pattern"].count("M")
    return 3.0 * scan_forward_flops_per_token(cfg) * tokens * layers


def train_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    m = model_dims(cfg)
    x = m["m_heads"] * m["m_width"]
    bc = 2 * m["m_groups"] * m["m_state"]
    dt = m["m_heads"]
    forward = (x + bc + dt) + x
    backward = (x + bc + dt) + x + (x + bc + dt)
    return float(tokens) * m["pattern"].count("M") * (forward + backward) * itemsize
