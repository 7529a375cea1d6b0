"""Share (%) of the traced train steps' device busy time spent under the ``attention`` scope,
forward and backward, self times: the attention layer (projections and the splash kernels) of the
`nemotron_h` tower. Layer: tower blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_share


def read(result, ctx):
    return scope_share(result, "attention")
