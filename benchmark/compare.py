"""The comparisons that decide ``correct`` (the yardstick: kept with the benchmark)."""

from __future__ import annotations

import math
import statistics

LAYER_LEAVES = ("ln_1", "c_attn", "attn_c_proj", "ln_2", "c_fc", "mlp_c_proj")


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The widest gap, over the leaves, between the program's norm of a leaf and the
    reference's — the gap between the two norms, not the norm of a difference — measured
    against the reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero). Returns the gap and the leaf that has it."""
    if set(program) != set(reference):
        raise ValueError(f"leaves differ: {sorted(set(program) ^ set(reference))}")
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        value = program[name]
        if not math.isfinite(value):
            return math.inf, name
        gap = abs(value - ref) / max(ref, median, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where
