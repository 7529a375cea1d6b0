"""Required operations and bytes of the splash attention kernel family (training:
forward + backward, causal), and the name by which the trace finds it.

The trace names an operation after its ``jax.named_scope``; jax's splash kernels sit in
scopes that start with ``splash_mha`` (forward ``splash_mha_fwd``-like, backward
``splash_mha_dkv`` / ``splash_mha_dq``-like names all share the prefix).
"""

from __future__ import annotations

SCOPE_PREFIX = "splash_mha"


def train_flops(n_layer: int, n_head: int, head_dim: int, sequence_length: int, rows: int) -> float:
    """Causal: half the square. Forward 2 matmuls (QK^T, PV); backward 5 (recompute QK^T,
    dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q) = 3.5 x forward in all. 2 flops a
    multiply-add."""
    square = sequence_length * (sequence_length + 1) / 2
    forward = 2 * 2.0 * n_head * head_dim * square
    return rows * n_layer * forward * 3.5


def train_bytes(
    n_layer: int, n_head: int, n_kv: int, head_dim: int, sequence_length: int, rows: int, itemsize: int = 2
) -> float:
    """The least traffic: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV (the row statistics are small and left out)."""
    q = n_head * head_dim * sequence_length * itemsize
    kv = n_kv * head_dim * sequence_length * itemsize
    forward = 2 * q + 2 * kv
    backward = 3 * q + 2 * kv + q + 2 * kv
    return rows * n_layer * (forward + backward)


def roofline_seconds(flops: float, bytes_moved: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = bytes_moved / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
