"""``moe_grouped_matmul_roofline`` for `afmoe`'s gated (SwiGLU) banks (2048 x 1024, 8 held; the
shared expert beside them stands under its own scope and is not counted): the reader of
``moe_grouped_matmul_roofline.lfm2`` — which asks the cell's weights module for ``d``, ``f`` and
``held`` and calls the size-parameterised ``benchmark/kernels/moe_grouped_matmul_swiglu.py`` —
on this family's cells, not another copy of it (ROADMAP D9 makes the suffixed readers one).
Layer: kernels. Moves ``train_tokens_per_s_per_chip``.
"""

import os

from benchmark.afmoe_trace import of_this_family
from benchmark.spec import load_module

_sized = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "moe_grouped_matmul_roofline.lfm2.py"), "benchmark_layer_metric_moe_grouped_matmul_roofline_lfm2"
)


def read(result, ctx):
    if not of_this_family(ctx):
        return None
    return _sized.read(result, ctx)
