"""Share (%) of the traced train steps' device busy time spent under the ``attention_full``
scope, forward and backward, self times: the full-attention layers of `afmoe` (no positions;
the splash kernels on the documents' block tables). Layer: afmoe blocks. Moves
``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_share


def read(result, ctx):
    return scope_share(result, "attention_full")
