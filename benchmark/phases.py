"""Where a train step's device time goes, by phase: the two rules the phase readers share.

The program names the phases of its jitted step with ``jax.named_scope`` (PR 24:
``train_utils.make_train_step``, ``models/gpt_dolomite``, ``ops/loss``), and JAX wraps what
the backward pass runs in ``transpose(...)``. An operation's framework name (``tf_op``)
therefore says which phase it belongs to and in which direction:

    jit(train_step)/jvp(GPTDolomiteForCausalLM)/transformer/blocks/while/body/closed_call/h_scan/b0/mlp/c_fc/dot_general
    jit(train_step)/transpose(jvp(GPTDolomiteForCausalLM))/head_loss/loss_chunks/while/body/closed_call/transpose(jvp(ce_chunk))/dot_general
    jit(train_step)/optimizer/cond/branch_1_fun/add

The ``XLA Ops`` line of a device plane nests: the event of a ``while`` or a ``conditional``
covers the events of its body (looked at by hand on the first trace of PR 24: the events of
one step summed to 334.6 ms, those at depth 0 to 174.05 ms, the program's own event 174.06
ms). So times are *self* times: an event's duration less what its children cover.
"""

from __future__ import annotations

import re

# scope -> the phase metric it is counted in. The embedding is counted with the head it is
# tied to (neither grows with depth); gradient accumulation and clipping with the update.
PHASE_OF_SCOPE = {
    "embed": "head_loss",
    "final_norm": "head_loss",
    "head_loss": "head_loss",
    "blocks": "blocks",
    "accumulate": "optimizer",
    "grad_clip": "optimizer",
    "optimizer": "optimizer",
}
# An operation XLA made itself (a cast hoisted out of the layer scan) has no framework name,
# but its HLO text still names the leaf of the train state it reads:
# ``convert(f32[...] %state_params__transformer____h_scan____b0____mlp____c_fc____kernel__.1)``
STATE_LEAF = re.compile(r"%state_params__(\w+)")
PHASE_OF_STATE_GROUP = (
    (re.compile(r"^h_(scan|\d+)$"), "blocks"),
    (re.compile(r"^(wte|wpe)$"), "embed"),
    (re.compile(r"^ln_f$"), "final_norm"),
    (re.compile(r"^lm_head$"), "head_loss"),
)


def self_times(events: list) -> list:
    """[(event, self nanoseconds)] of one trace line whose events nest: an event's duration
    less the durations of its children (the events that start and end inside it, one level
    down). Events that only touch (one ends where the next starts) are siblings."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    covered = [0.0] * len(ordered)
    stack: list[int] = []  # indices of the events that are open at this point
    for i, event in enumerate(ordered):
        while stack and event.start_ns >= ordered[stack[-1]].end_ns - 1e-3:
            stack.pop()
        if stack:
            covered[stack[-1]] += event.duration_ns
        stack.append(i)
    return [(event, max(event.duration_ns - covered[i], 0.0)) for i, event in enumerate(ordered)]


def scope_core(component: str) -> str:
    """``transpose(jvp(head_loss))`` -> ``head_loss``: a path component without the
    transforms JAX wrapped around it."""
    while "(" in component and component.endswith(")"):
        component = component[component.index("(") + 1 : -1]
    return component


def phase_of(tf_op: str, hlo: str = "") -> tuple | None:
    """(scope, direction) of an operation: the innermost phase scope on its framework name
    and ``"bwd"`` where the name carries ``transpose(``, else ``"fwd"``; for an operation
    without a framework name, the phase of the train-state leaf its HLO text reads (always
    forward: a cast of a weight). None where neither names a phase."""
    path = str(tf_op or "").rstrip(":")
    for component in reversed(path.split("/")):
        scope = scope_core(component)
        if scope in PHASE_OF_SCOPE:
            return scope, "bwd" if "transpose(" in path else "fwd"
    if not path:
        leaf = STATE_LEAF.search(hlo or "")
        if leaf:
            for group in leaf.group(1).split("__"):
                for pattern, scope in PHASE_OF_STATE_GROUP:
                    if pattern.match(group.strip("_")):
                        return scope, "fwd"
    return None


def train_step_phases(trace, function: str = "train_step") -> dict | None:
    """Self time of the first chip's operations inside executions of ``jit_<function>``, by
    (scope, direction), over the trace. None where the trace has no such program or no
    operation's framework name carries a phase scope (a program from before PR 24)."""
    device = trace.devices[0]
    executions = [m for m in device.modules if m.name.startswith("jit_" + function + "(")]
    if not executions:
        return None
    program_ids = {m.name[m.name.index("(") + 1 : -1] for m in executions}
    ops = [e for e in device.ops if str(e.stats.get("program_id", "")) in program_ids]
    seconds: dict = {}
    unattributed: dict = {}
    by_name = 0
    for event, self_ns in self_times(ops):
        tf_op = str(event.stats.get("tf_op", ""))
        key = phase_of(tf_op, event.name)
        if key is None:
            category = str(event.stats.get("hlo_category", "?"))
            unattributed[category] = unattributed.get(category, 0.0) + self_ns / 1e9
            continue
        by_name += bool(tf_op)
        if key == ("head_loss", "bwd") and "/jvp(" in tf_op:
            key = ("head_loss", "bwd_replay")  # the chunk's forward, run again by its backward rule
        seconds[key] = seconds.get(key, 0.0) + self_ns / 1e9
    if not by_name:
        return None
    return {
        "steps": len(executions),
        "seconds": seconds,
        "unattributed": unattributed,
        "total_s": sum(seconds.values()) + sum(unattributed.values()),
    }


_newest: tuple = (None, None)  # (trace, its table): the five phase readers ask for the same one


def table_of(result) -> dict | None:
    """:func:`train_step_phases` of a run's trace, reduced once a trace; None for a run
    that traced no training steps."""
    global _newest
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    if _newest[0] is not result.trace:
        _newest = (result.trace, train_step_phases(result.trace))
    return _newest[1]


def phase_ms(table: dict | None, phase: str, direction: str | None = None) -> float | None:
    """Milliseconds a traced step spent in a phase metric (``blocks``, ``head_loss``,
    ``optimizer``) by :func:`train_step_phases`' table, in one direction (``fwd`` / ``bwd``,
    replays counted as ``bwd``) or both. None where the trace names no phase."""
    if table is None:
        return None
    total = sum(
        s for (scope, way), s in table["seconds"].items()
        if PHASE_OF_SCOPE[scope] == phase and (direction is None or way.startswith(direction))
    )
    return 1e3 * total / table["steps"]


def describe(table: dict) -> str:
    """One line: every scope and direction in ms a step, what was left unattributed by HLO
    category, and the sum."""
    steps = table["steps"]
    parts = [f"{scope}.{way} {1e3 * s / steps:.3f}" for (scope, way), s in sorted(table["seconds"].items())]
    left = [f"{category} {1e3 * s / steps:.3f}" for category, s in sorted(table["unattributed"].items(), key=lambda kv: -kv[1])]
    return (
        f"ms a step over {steps} steps: " + ", ".join(parts) + "; unattributed: " + (", ".join(left) or "none")
        + f"; sum {1e3 * table['total_s'] / steps:.3f}"
    )
