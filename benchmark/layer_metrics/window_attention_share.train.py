"""Share (%) of the traced train steps' device busy time spent under the ``attention_window``
scope, forward and backward, self times: the sliding-window attention layers of `afmoe`
(projections, QK norms, rope, the splash kernels on the windowed block tables, the gate, the
out-projection). Prints the step's split with every operation counted once
(``benchmark/afmoe_trace.py``). Layer: afmoe blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.afmoe_trace import exclusive_table, say_table
from benchmark.tower_trace import scope_share


def read(result, ctx):
    table = exclusive_table(result)
    if table is None:
        return None
    say_table(table)
    return scope_share(result, "attention_window")
