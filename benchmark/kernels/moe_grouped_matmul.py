"""Required operations and bytes of the routed experts' grouped products (training: forward
+ backward) for the rows the program really routed to the experts it holds, and the scope by
which the trace finds them.

The program runs ``up -> relu^2 -> down`` as two grouped products under the ``moe_experts``
scope (``dolomite_engine_tpu/ops/moe.experts_held_ragged``). ``routed_rows`` is the sum of
the program's ``routed_slots`` counter over the traced steps and the layers of experts —
not ``tokens x top_k``, and not an assumed even split. Operations: each row takes two
products of d x f in the forward pass and four in the backward (a gradient for the row and
one for the bank, each product). Bytes, the least: forward reads the rows and both banks and
writes the hidden and output rows; backward reads rows, hidden rows, both banks and the
output's gradient, and writes the rows' gradient and both banks' gradients (float32).
"""

from __future__ import annotations

from benchmark.kernels.splash_attention import roofline_seconds  # noqa: F401  (the same rule)
from benchmark.weights_nemotron_h import model_dims

SCOPE = "moe_experts"


def train_flops(cfg: dict, routed_rows: float) -> float:
    m = model_dims(cfg)
    return 3.0 * 2 * 2.0 * m["d"] * m["f"] * routed_rows


def train_bytes(cfg: dict, routed_rows: float, layer_steps: int, itemsize: int = 2) -> float:
    """``layer_steps``: layers of experts x traced steps (each reads its banks once a pass)."""
    m = model_dims(cfg)
    bank = m["held"] * m["d"] * m["f"]
    rows_forward = routed_rows * (m["d"] + m["f"] + m["d"]) * itemsize
    rows_backward = routed_rows * (m["d"] + m["f"] + m["d"] + m["f"] + m["d"]) * itemsize
    banks = layer_steps * (2 * bank * itemsize + 2 * bank * itemsize + 2 * bank * 4)
    return rows_forward + rows_backward + banks
