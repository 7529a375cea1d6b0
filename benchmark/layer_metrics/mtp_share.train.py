"""Share (%) of the traced train steps' device busy time spent under the ``mtp`` scope, forward
and backward, self times: the multi-token-prediction module of `joyai_llm_flash` — its
projection, its block (latent attention and experts of its own) and its pass through the head
and the loss. Prints the step's split with every operation counted once
(``benchmark/joyai_trace.py``). Layer: blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.joyai_trace import exclusive_table, say_table
from benchmark.tower_trace import scope_share


def read(result, ctx):
    table = exclusive_table(result)
    if table is None:
        return None
    say_table(table)
    return scope_share(result, "mtp")
