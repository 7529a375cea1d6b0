"""Share (%) of the traced training steps' window in which no operation ran on the device:
1 - union of the device's operation intervals over the window. Layer: device. Moves
``train_tokens_per_s_per_chip``.
"""


def read(result, ctx):
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    return 100.0 * result.trace.idle_share
