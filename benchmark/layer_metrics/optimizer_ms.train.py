"""Device milliseconds a traced training step spends on its gradients after the backward
pass: the upcast and global-norm clipping (scope ``grad_clip``), the optimizer update with
the ``cond`` of ``skip_nonfinite`` around it (``optimizer``) and, where the step accumulates
micro-batches, the accumulation's own operations (``accumulate``). Self time of the
operations of ``jit_train_step`` (``benchmark/phases.py``). Layer: train step, device. Moves
``train_tokens_per_s_per_chip``. Grows with the parameters, not with the tokens.
"""

from benchmark import phases


def read(result, ctx):
    return phases.phase_ms(phases.table_of(result), "optimizer")
