"""What the readers of the `nemotron_h` tower's per-layer metrics share: self time of the
train step's device operations under a named scope, and the program's ``step_counters``
events of the steps a number is read over.

A scope's operations nest on the ``XLA Ops`` line (a ``while`` covers its body), so times
are self times (``phases.self_times``). A name counts for a scope when one of its path
components, without the transforms JAX wrapped around it (``transpose(jvp(mamba2_scan))``),
is the scope. Every function returns None where there is nothing to read — a program from
before these scopes and counters — and raises nothing.
"""

from __future__ import annotations

from benchmark import phases

# XLA's TPU compiler turns `lax.ragged_dot` into a kernel of its own and names it after
# itself: the event's framework name is "ragged-dot-none:" whatever scope the call stood in
# (looked at on the cell's first trace, PR 26). The program has no other grouped product, so
# such an event counts for the experts' layer and for their grouped products' scope.
UNNAMED_GROUPED_PRODUCT = "%ragged-dot"
GROUPED_PRODUCT_SCOPE = "moe_experts"

_newest: tuple = (None, None)  # (trace, its table)


def scope_table(result) -> dict | None:
    """{"steps": n, "busy_s": s, "self_s": {component: seconds}}: self seconds of the first
    chip's operations inside ``jit_train_step`` by every path component of their names."""
    global _newest
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    if _newest[0] is result.trace:
        return _newest[1]
    device = result.trace.devices[0]
    executions = [m for m in device.modules if m.name.startswith("jit_train_step(")]
    table = None
    if executions:
        program_ids = {m.name[m.name.index("(") + 1 : -1] for m in executions}
        ops = [e for e in device.ops if str(e.stats.get("program_id", "")) in program_ids]
        seconds: dict = {}
        busy = 0.0
        for event, self_ns in phases.self_times(ops):
            busy += self_ns
            path = str(event.stats.get("tf_op", "")).rstrip(":")
            components = {phases.scope_core(c) for c in path.split("/") if c}
            if "/" not in path and event.name.startswith(UNNAMED_GROUPED_PRODUCT):
                components |= {"moe", GROUPED_PRODUCT_SCOPE}
            for component in components:
                seconds[component] = seconds.get(component, 0.0) + self_ns / 1e9
        table = {"steps": len(executions), "busy_s": busy / 1e9, "self_s": seconds}
    _newest = (result.trace, table)
    return table


def scope_seconds(result, scope: str) -> float | None:
    table = scope_table(result)
    if table is None or scope not in table["self_s"]:
        return None
    return table["self_s"][scope]


def scope_share(result, scope: str) -> float | None:
    """% of the train step's device busy time (self times) under ``scope``, forward and
    backward."""
    table = scope_table(result)
    if table is None or scope not in table["self_s"] or table["busy_s"] <= 0:
        return None
    return 100.0 * table["self_s"][scope] / table["busy_s"]


def step_counters(result, first_step: int | None = None, last_step: int | None = None) -> list:
    """The program's ``step_counters`` events (one a synced step) with ``first_step <= step
    <= last_step``; by default the steps the run's rate is read over (the traced steps of a
    traced run, else the window's)."""
    facts = result.facts
    if first_step is None:
        if "traced_first_step" in facts:
            first_step = facts["traced_first_step"]
            last_step = first_step + facts["traced_steps"] - 1
        else:
            first_step, last_step = facts.get("first_measured_step"), facts.get("last_measured_step")
    if first_step is None or last_step is None:
        return []
    return [
        r for r in result.telemetry
        if r.get("kind") == "event" and r.get("event") == "step_counters" and first_step <= r.get("step", -1) <= last_step
    ]


def routed_slots_per_token(result) -> float | None:
    """Token-slots a token sent to the experts held here, a layer of experts, over those
    steps (the program's counter)."""
    events = step_counters(result)
    if not events or "tokens_per_step" not in result.facts:
        return None
    per_layer = [sum(e["routed_slots"]) / len(e["routed_slots"]) for e in events]
    return sum(per_layer) / len(per_layer) / result.facts["tokens_per_step"]
