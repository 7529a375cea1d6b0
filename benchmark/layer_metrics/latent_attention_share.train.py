"""Share (%) of the traced train steps' device busy time spent under the ``latent_attention``
scope, forward and backward, self times: latent attention (the four low-rank projections, the
rotation, the splash kernels, the out-projection) of every block of `joyai_llm_flash`, the
multi-token-prediction module's included. Layer: blocks. Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark.tower_trace import scope_share


def read(result, ctx):
    return scope_share(result, "latent_attention")
