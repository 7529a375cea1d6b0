"""Operations and bytes of the splash attention kernels **over the block pairs the step's
documents made them visit** — not over half the square, which since PR 31 reads work the
kernel avoided as efficiency (PERF.md section 7) — and the name by which the trace finds them
(the same ``splash_mha*`` scopes: the program has one attention kernel).

``visited_block_pairs`` is the program's ``splash_blocks_visited`` counter summed over the
traced steps (one attention layer's worth a step: the layers share the ids): the (row, query
block, key block) triples whose grid step runs. The kernel computes a visited block whole —
``block_q x block_kv`` query-key pairs a head, masked inside — so that is what it is charged
with: a share under 100% by construction, where a count of the unmasked pairs alone would charge
the diagonal blocks with half. Required, a pair of a head (2 flops a multiply-add): forward's
QK^T and PV, and the backward's S, dP, dV, dQ, dK, each once (jax's kernel recomputes S and dP
in each of its two backward launches). Bytes, the least: whole rows, as
``kernels/splash_attention.train_bytes`` (a block's keys are read once however many queries
visit them).
"""

from __future__ import annotations

from benchmark.kernels.splash_attention import SCOPE_PREFIX, roofline_seconds, train_bytes  # noqa: F401  (the same scope, rule and traffic)


def train_flops(layers: int, heads: int, head_dim: int, block_q: int, block_kv: int, visited_block_pairs: float) -> float:
    pairs = float(visited_block_pairs) * block_q * block_kv * heads * layers
    return 2.0 * (2 * head_dim + 5 * head_dim) * pairs
