"""Device milliseconds a traced training step spends in the backward pass of the
transformer blocks: self time (``benchmark/phases.py``) of the operations of
``jit_train_step`` under the program's ``blocks`` scope with ``transpose(`` in their
framework name. What rematerialisation replays is included and cannot be printed apart:
under ``checkpoint/`` the replayed forward and the backward carry one name. Layer: train
step, device. Moves ``train_tokens_per_s_per_chip``. Grows with depth.
"""

from benchmark import phases


def read(result, ctx):
    return phases.phase_ms(phases.table_of(result), "blocks", "bwd")
