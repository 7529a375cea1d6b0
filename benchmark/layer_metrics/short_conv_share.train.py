"""Share (%) of the traced train steps' device busy time spent under the ``short_conv`` scope,
forward and backward, self times: the gated short-convolution operators (in-projection, the two
gates and the taps, out-projection) of `lfm2_moe`. Prints the step's split with every operation
counted once (``benchmark/lfm2_trace.py``). Layer: blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.lfm2_trace import exclusive_table, say_table
from benchmark.tower_trace import scope_share


def read(result, ctx):
    table = exclusive_table(result)
    if table is None:
        return None
    say_table(table)
    return scope_share(result, "short_conv")
