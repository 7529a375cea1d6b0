"""Driver ``train_packed_loop``: ``train_packed_tower`` for a looped model, whose step runs one
stack several times and whose loss weighs the passes' cross-entropies by an exit gate's
distribution.

The run is ``train_packed_tower.run`` — the same seams, the same window, the same last line;
nothing of it is copied here. What a loop changes is what ``correct`` compares, so this driver
stands at three module attributes of ``train_packed_tower`` that ``run`` looks up when it runs
(as ``train_packed_mtp`` stands at two):

- ``compare_with_reference`` is ``loop_checks`` below: beside the loss, the first gradient and the
  parameters' change, **every pass's mean cross-entropy** and **the mean of every pass's exit
  probability** in the checked steps (``pass_loss_<t>`` / ``exit_mass_<t>`` of the program's
  ``step_counters`` events against the reference's ``pass_losses`` / ``exit_mass``; the exit mass
  of the last checked step under a limit of its own, ``exit_mass_gap_last_step``), and the first
  gradient in four groups: the blocks' worst leaf (the final norm with them), the embedding, the
  head, the gate. A loop that drops a pass or a loss that ignores the gate moves the whole loss by
  less than rounding does (fresh weights score every pass alike), and shows here;
- ``modules_of``, to keep what the reference returned and was given;
- ``read_telemetry``, because ``run`` reads ``routed_slots`` of every ``step_counters`` event (its
  families have experts): an event without experts says it routed to none.

With ``control`` (``tools/read_limits.py``: never in a benchmark run) two controls beside the
tower's fp8 one, each the reference put in the program's place: ``three_passes`` (the loop run
three times) and ``unweighted`` (the plain mean of the passes' cross-entropies: the gate out of
the loss). ``benchmark/limits/<cell>.json`` says which limit each must exceed.
"""

from __future__ import annotations

import math
import os

from benchmark import compare
from benchmark.drivers import train_packed_tower as tower
from benchmark.harness import Check

GROUPS = (  # (limit's name, the leaves of the group)
    ("first_grad_norm_worst_block_leaf_gap", lambda k: k.startswith("layer") or k == "ln_f"),
    ("first_grad_norm_wte_gap", lambda k: k == "wte"),
    ("first_grad_norm_head_gap", lambda k: k == "lm_head"),
    ("first_grad_norm_gate_gap", lambda k: k.startswith("gate_")),
)


def worst_pass_gap(mine, reference) -> float:
    """The widest gap between two lists of numbers a pass; another count of passes is no match."""
    if mine is None or len(mine) != len(reference) or any(x is None for x in mine):
        return math.inf
    return max(abs(a - b) for a, b in zip(mine, reference))


def loop_checks(losses, grad_norms, delta_norms, parts: dict, reference: dict, limits: dict) -> list:
    """The numbers of ``correct`` for a training cell of a looped model, each beside its limit.
    ``parts``: ``pass_losses`` and ``exit_mass``, a step an entry, a pass a number."""
    checks = []

    def check(name, value, note="", limit_name=None):
        limit = limits.get(limit_name or name.split("_step")[0], math.nan)
        checks.append(Check(name, value, limit, value <= limit, note))

    for i, (mine, ref) in enumerate(zip(losses, reference["losses"])):
        check(f"loss_gap_step{i + 1}", abs(mine - ref), f"(program {mine:.5f}, reference {ref:.5f})")
    last = len(reference["exit_mass"]) - 1
    for name, key in (("pass_loss_gap", "pass_losses"), ("exit_mass_gap", "exit_mass")):
        for i, ref in enumerate(reference[key]):
            mine = parts[key][i] if i < len(parts[key]) else None
            # the gate's distribution is held to its limit while the gate has hardly moved; at the last
            # checked step rounding alone moves percents of the mass (the limits' file says what was read)
            limit_name = "exit_mass_gap_last_step" if key == "exit_mass" and i == last and last > 0 else None
            check(f"{name}_step{i + 1}", worst_pass_gap(mine, ref), f"(program {mine}, reference {ref})", limit_name)
    for name, of_group in GROUPS:
        if grad_norms is None:
            check(name, math.inf, "(the loop never handed out its state)")
            continue
        mine, ref = ({k: v for k, v in norms.items() if of_group(k)} for norms in (grad_norms, reference["grad_norms"]))
        gap, where = compare.worst_leaf_gap(mine, ref)
        check(name, gap, f"(at {where}: program {mine.get(where)}, reference {ref.get(where)})")
    if delta_norms is None:
        check("param_change_norm_worst_leaf_gap", math.inf, "(the loop never handed out its state)")
    else:
        gap, where = compare.worst_leaf_gap(delta_norms, reference["delta_norms"])
        check("param_change_norm_worst_leaf_gap", gap, f"(at {where}: program {delta_norms.get(where)}, reference {reference['delta_norms'].get(where)})")
    return checks


def program_parts(ctx, steps: int, passes: int) -> dict:
    """``pass_loss_<t>`` and ``exit_mass_<t>`` of the program's ``step_counters`` events of steps
    1 .. ``steps`` (None where a step returned none)."""
    by_step = {
        r["step"]: r for r in tower.read_telemetry(os.path.join(ctx.out_dir, "ckpt"))
        if r.get("kind") == "event" and r.get("event") == "step_counters"
    }
    read = lambda name: [[by_step.get(s + 1, {}).get(f"{name}_{t + 1}") for t in range(passes)] for s in range(steps)]  # noqa: E731
    return {"pass_losses": read("pass_loss"), "exit_mass": read("exit_mass")}


def as_control(name: str, checks: list) -> list:
    return [Check(f"control_{name}_{c.name}", c.value, c.limit, not c.ok, "(the control should exceed the limit) " + c.note) for c in checks]


def run(ctx):
    kept: dict = {}  # quant -> what the reference's train_steps returned; "arguments": what it was given
    modules_of, compare_with_reference, read_telemetry = tower.modules_of, tower.compare_with_reference, tower.read_telemetry
    reference_module = modules_of(ctx.cell.config)[1]

    def keeping(config):
        weights, reference = modules_of(config)

        class Kept:
            @staticmethod
            def train_steps(cfg, seed, batches, optimizer, quant=None):
                kept["arguments"] = (cfg, seed, batches, optimizer)
                kept[quant] = reference.train_steps(cfg, seed, batches, optimizer, quant=quant)
                return kept[quant]

        return weights, Kept

    def comparing(losses, grad_norms, delta_norms, program_rows, reference, limits):
        control = kept.get("fp8")
        if control is not None and losses is control["losses"]:
            parts = control  # the control, put in the program's place
        else:
            parts = program_parts(ctx, len(reference["losses"]), len(reference["pass_losses"][0]))
        return loop_checks(losses, grad_norms, delta_norms, parts, reference, limits)

    def reading(save_path):
        records = read_telemetry(save_path)
        for record in records:
            if record.get("event") == "step_counters":
                record.setdefault("routed_slots", [])  # no experts: none routed to
        return records

    tower.modules_of, tower.compare_with_reference, tower.read_telemetry = keeping, comparing, reading
    try:
        result = tower.run(ctx)
    finally:
        tower.modules_of, tower.compare_with_reference, tower.read_telemetry = modules_of, compare_with_reference, read_telemetry
    if ctx.control and None in kept:
        for name, change in (("three_passes", dict(passes=len(kept[None]["pass_losses"][0]) - 1)), ("unweighted", dict(weigh=False))):
            control = reference_module.train_steps(*kept["arguments"], **change)
            result.checks += as_control(
                name, loop_checks(control["losses"], control["grad_norms"], control["delta_norms"], control, kept[None], ctx.cell.limits)
            )
    return result
