"""``kernels/moe_grouped_matmul_gated.py``'s counts from the sizes themselves (hidden ``d``, an
expert's width ``f``, the experts ``held``), for a family whose configuration is not
`joyai_llm_flash`'s (`lfm2_moe`: 2048, 1536, 8): the program runs ``[up | gate] -> up *
silu(gate) -> down`` as two grouped products under the ``moe_experts`` scope
(``dolomite_engine_tpu/ops/moe.experts_held_ragged``), three d x f products a row.
``routed_rows`` is the sum of the program's ``routed_slots`` counter over the traced steps and
the layers of experts. Operations: three products a row forward, six backward. Bytes, the least:
forward reads the rows and both banks and writes the 2 f and d rows; backward reads the rows, the
2 f rows, both banks and the output's gradient, and writes the rows' gradient and both banks'
gradients (float32).
"""

from __future__ import annotations

from benchmark.kernels.moe_grouped_matmul import SCOPE, roofline_seconds  # noqa: F401  (the same scope, the same rule)


def train_flops(d: int, f: int, routed_rows: float) -> float:
    return 3.0 * 3 * 2.0 * d * f * routed_rows


def train_bytes(d: int, f: int, held: int, routed_rows: float, layer_steps: float, itemsize: int = 2) -> float:
    """``layer_steps``: layers of experts x traced steps (each reads its banks once a pass)."""
    bank = held * 3 * d * f  # both banks of a layer: [held, d, 2 f] and [held, f, d]
    rows_forward = routed_rows * (d + 2 * f + d) * itemsize
    rows_backward = routed_rows * (d + 2 * f + d + 2 * f + d) * itemsize
    banks = layer_steps * (bank * itemsize + bank * itemsize + bank * 4)
    return rows_forward + rows_backward + banks
