"""Driver ``train_packed_mtp``: ``train_packed_tower`` for a model whose loss has a second part
(multi-token prediction) that the step returns beside its counters.

The run is ``train_packed_tower.run`` — the same seams, the same window, the same checks, the
same last line; nothing of it is copied here. This driver adds ONE comparison to those that
decide ``correct``: the second part of the loss in the checked steps (``mtp_loss`` of the
program's ``step_counters`` events) against the reference's (``mtp_losses`` of its
``train_steps``), under the limit ``mtp_loss_gap``. The whole loss is ``main + coef x second``,
so a fault in the second pass shows in ``loss_gap`` at a third of its size only. It stands at
two module attributes of ``train_packed_tower`` that ``run`` looks up when it runs, as that
driver stands at ``pretrain``'s: ``modules_of`` (to keep what the reference returned) and
``compare_with_reference`` (to append the check, for the program and for the control alike).

Why ``train_packed_tower`` could not serve as it stands: its comparison knows one loss a step.
Everything else a third non-dense configuration needed it already took from the
configuration's file (``benchmark_modules``, ``tiny``, ``layer_metrics_without_an_entry``).
"""

from __future__ import annotations

import math
import os

from benchmark.drivers import train_packed_tower as tower
from benchmark.drivers.train_packed import read_telemetry
from benchmark.harness import Check


def second_loss_checks(mine: list, reference: list, limit: float) -> list:
    checks = []
    for i, ref in enumerate(reference):
        if i >= len(mine) or mine[i] is None:
            checks.append(Check(f"mtp_loss_gap_step{i + 1}", math.inf, limit, False, "(the step returned no second loss)"))
            continue
        gap = abs(mine[i] - ref)
        checks.append(Check(f"mtp_loss_gap_step{i + 1}", gap, limit, gap <= limit, f"(program {mine[i]:.5f}, reference {ref:.5f})"))
    return checks


def program_second_losses(ctx, steps: int) -> list:
    """``mtp_loss`` of the program's ``step_counters`` events of steps 1 .. ``steps``."""
    by_step = {
        r["step"]: r.get("mtp_loss") for r in read_telemetry(os.path.join(ctx.out_dir, "ckpt"))
        if r.get("kind") == "event" and r.get("event") == "step_counters"
    }
    return [by_step.get(step + 1) for step in range(steps)]


def run(ctx):
    followed: dict = {}  # quant -> what the reference's train_steps returned
    modules_of, compare = tower.modules_of, tower.compare_with_reference

    def keeping(config):
        weights, reference = modules_of(config)

        class Kept:
            @staticmethod
            def train_steps(cfg, seed, batches, optimizer, quant=None):
                followed[quant] = reference.train_steps(cfg, seed, batches, optimizer, quant=quant)
                return followed[quant]

        return weights, Kept

    def comparing(losses, grad_norms, delta_norms, program_rows, reference, limits):
        checks = compare(losses, grad_norms, delta_norms, program_rows, reference, limits)
        control = followed.get("fp8")
        if control is not None and losses is control["losses"]:
            mine = control["mtp_losses"]  # the control, put in the program's place
        else:
            mine = program_second_losses(ctx, len(reference["mtp_losses"]))
        return checks + second_loss_checks(mine, reference["mtp_losses"], limits.get("mtp_loss_gap", math.nan))

    tower.modules_of, tower.compare_with_reference = keeping, comparing
    try:
        return tower.run(ctx)
    finally:
        tower.modules_of, tower.compare_with_reference = modules_of, compare
