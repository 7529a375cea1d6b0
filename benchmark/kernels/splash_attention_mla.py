"""Operations and bytes of the splash attention kernels at latent attention's head — scores
over ``qk`` columns (nope + rope: 192), values of ``v`` (128), as many key/value heads as query
heads (the training form expands them) — causal over the packed row, as the accepted
``kernels/splash_attention.py`` takes its row; and the name by which the trace finds them (the
same ``splash_mha*`` scopes: the program has one attention kernel).

By launch, a query-key pair of a head (2 flops a multiply-add):
  forward   QK^T (qk) and PV (v)
  dkv       S again (qk), dP = dO V^T (v), dV = P^T dO (v), dK = dS^T Q (qk)
  dq        S again (qk), dP again (v), dQ = dS K (qk)
jax's kernel runs dkv and dq as two launches, each recomputing S and dP. *Required* counts
S and dP once in the backward pass: forward's two products and five more.
"""

from __future__ import annotations

from benchmark.kernels.splash_attention import SCOPE_PREFIX, roofline_seconds  # noqa: F401  (the same scope, the same rule)


def flops_by_launch(layers: int, heads: int, qk: int, v: int, sequence_length: int, rows: int) -> dict:
    """{"forward", "dkv", "dq"}: what each launch computes, causal (half the square)."""
    pairs = rows * layers * heads * sequence_length * (sequence_length + 1) / 2
    return {"forward": 2.0 * (qk + v) * pairs, "dkv": 2.0 * (2 * qk + 2 * v) * pairs, "dq": 2.0 * (2 * qk + v) * pairs}


def train_flops(layers: int, heads: int, qk: int, v: int, sequence_length: int, rows: int) -> float:
    """Required: forward's QK^T and PV, and the backward's S, dP, dV, dQ, dK — each once."""
    pairs = rows * layers * heads * sequence_length * (sequence_length + 1) / 2
    return 2.0 * ((qk + v) + (qk + v + v + qk + qk)) * pairs


def bytes_by_launch(layers: int, heads: int, qk: int, v: int, sequence_length: int, rows: int, itemsize: int = 2) -> dict:
    """The least traffic of each launch (the row statistics are small and left out): forward
    reads Q, K, V and writes O; dkv reads Q, K, V, dO and writes dK, dV; dq reads Q, K, V, dO
    and writes dQ."""
    wide = rows * layers * heads * qk * sequence_length * itemsize  # Q, K, dQ, dK
    narrow = rows * layers * heads * v * sequence_length * itemsize  # V, O, dO, dV
    return {"forward": 2 * wide + 2 * narrow, "dkv": 3 * wide + 3 * narrow, "dq": 3 * wide + 2 * narrow}


def train_bytes(layers: int, heads: int, qk: int, v: int, sequence_length: int, rows: int, itemsize: int = 2) -> float:
    """Required: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO once and
    writes dQ, dK, dV."""
    wide = rows * layers * heads * qk * sequence_length * itemsize
    narrow = rows * layers * heads * v * sequence_length * itemsize
    return (2 * wide + 2 * narrow) + (2 * wide + 3 * narrow + 2 * wide + narrow)
