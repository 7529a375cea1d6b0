"""``kernels/moe_grouped_matmul.py`` for gated (SwiGLU) banks: the program runs ``[up | gate] ->
up * silu(gate) -> down`` as two grouped products under the ``moe_experts`` scope
(``dolomite_engine_tpu/ops/moe.experts_held_ragged``), the first of width 2 f — three d x f
products a row where the ungated banks take two. ``routed_rows`` is the sum of the program's
``routed_slots`` counter over the traced steps and the layers of experts. Operations: three
products a row forward, six backward. Bytes, the least: forward reads the rows and both banks
and writes the 2 f and d rows (the f rows between them need not leave the chip); backward reads
the rows, the 2 f rows, both banks and the output's gradient, and writes the rows' gradient and
both banks' gradients (float32).
"""

from __future__ import annotations

from benchmark.kernels.moe_grouped_matmul import SCOPE, roofline_seconds  # noqa: F401  (the same scope, the same rule)
from benchmark.weights_joyai_flash import model_dims


def train_flops(cfg: dict, routed_rows: float) -> float:
    m = model_dims(cfg)
    return 3.0 * 3 * 2.0 * m["d"] * m["f"] * routed_rows


def train_bytes(cfg: dict, routed_rows: float, layer_steps: int, itemsize: int = 2) -> float:
    """``layer_steps``: layers of experts x traced steps (each reads its banks once a pass)."""
    m = model_dims(cfg)
    bank = m["held"] * 3 * m["d"] * m["f"]  # both banks of a layer: [held, d, 2 f] and [held, f, d]
    rows_forward = routed_rows * (m["d"] + 2 * m["f"] + m["d"]) * itemsize
    rows_backward = routed_rows * (m["d"] + 2 * m["f"] + m["d"] + 2 * m["f"] + m["d"]) * itemsize
    banks = layer_steps * (bank * itemsize + bank * itemsize + bank * 4)
    return rows_forward + rows_backward + banks
