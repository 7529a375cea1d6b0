"""Device milliseconds a traced training step spends in the forward pass of the transformer
blocks: self time (``benchmark/phases.py``) of the operations of ``jit_train_step`` whose
framework name lies under the program's ``blocks`` scope without ``transpose(``, with the
blocks' weight casts that XLA hoists out of the layer scan. Layer: train step, device.
Moves ``train_tokens_per_s_per_chip``. Grows with depth: a cell cut to 4 of 32 layers shows
an eighth of the model's.
"""

from benchmark import phases


def read(result, ctx):
    return phases.phase_ms(phases.table_of(result), "blocks", "fwd")
