"""Share (%) of the traced train steps' device busy time spent under the ``attention_gate``
scope, forward and backward, self times: the gate on attention's output (``sigmoid(W_g h)``
multiplied into the heads' output before the out-projection: one projection as wide as the
heads' output and an elementwise pass, every attention layer). Layer: afmoe blocks. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_share


def read(result, ctx):
    return scope_share(result, "attention_gate")
