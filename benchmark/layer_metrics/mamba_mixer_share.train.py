"""Share (%) of the traced train steps' device busy time spent under the ``mamba_mixer`` scope,
forward and backward, self times: the Mamba-2 mixers (in-projection, convolution, scan, gated norm, out-projection) of the
`nemotron_h` tower. Layer: tower blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_share


def read(result, ctx):
    return scope_share(result, "mamba_mixer")
