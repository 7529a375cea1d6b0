"""What the two readers of the device's idle time share (``idle_in_step_ms.train``,
``idle_between_programs_ms.train``): the gaps of the first chip's busy time, each cut at the
boundaries of the step's program.

``reduce_trace.idle_gaps`` charges a whole gap to the host span over its middle and knows no
program boundary. Here a gap — between two merged busy intervals of the ``XLA Ops`` line, at
least 20 us as there — is cut where an execution of ``jit_<function>`` on the ``XLA Modules``
line starts or ends: the part inside an execution is the program waiting on itself (a loop
whose trip count is a value of the step, a ``cond``, a copy it waits for), the part outside
is the device waiting for the host. The two parts, the gaps under 20 us and the window's two
edges sum to ``window_s x idle_share`` by construction.

The ``XLA Ops`` line nests: a ``while`` or ``conditional`` event covers its body's, so the
busy union counts a loop's event as busy from end to end, and what a loop waits inside
itself shows only as the event's self time (``phases.self_times``): :func:`container_self_times`.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from benchmark import reduce_trace as rt
from benchmark.phases import scope_core, self_times

MIN_GAP_NS = 20_000.0  # reduce_trace.idle_gaps' floor
CONTAINERS = ("while", "conditional")
# path components of a framework name that are structure, not a scope the program named
STRUCTURAL = {"while", "body", "cond", "closed_call", "checkpoint", "pjit", "remat", "custom_vjp_call", "custom_jvp_call"}


@dataclass
class Gap:
    start_ns: float
    end_ns: float
    before: str  # the scope of the operation that ended last before the gap
    after: str  # the scope of the first operation after it
    inside_ns: float  # of the gap, inside an execution of the step's program
    outside: list  # [(start_ns, end_ns)]: its parts outside every such execution


@dataclass
class GapTable:
    steps: int  # executions of the step's program
    gaps: list  # Gap, of at least MIN_GAP_NS
    small_ns: float  # summed gaps under the floor: counted in neither reader
    small_count: int
    edges_ns: float  # the window before the first operation and after the last
    idle_ns: float  # window_s x idle_share

    @property
    def inside_ns(self) -> float:
        return sum(g.inside_ns for g in self.gaps)

    @property
    def outside_ns(self) -> float:
        return sum(b - a for g in self.gaps for a, b in g.outside)

    def identity(self) -> str:
        """The window's idle time by its four parts, ms."""
        return (
            f"idle of the window {self.idle_ns / 1e6:.3f} ms = inside the step's program {self.inside_ns / 1e6:.3f} "
            f"+ outside it {self.outside_ns / 1e6:.3f} + {self.small_count} gaps under {MIN_GAP_NS / 1e3:.0f} us "
            f"{self.small_ns / 1e6:.3f} + the window's two edges {self.edges_ns / 1e6:.3f}"
        )


def container_kind(event) -> str | None:
    """``while`` or ``conditional`` for an event that covers its body's events, else None:
    by the trace's ``hlo_category``, by the instruction's name where it carries none."""
    category = str(event.stats.get("hlo_category", ""))
    if category:
        return category if category in CONTAINERS else None
    return next((kind for kind in CONTAINERS if event.name.lstrip("%").startswith(kind)), None)


def scope_label(event) -> str:
    """The last two scopes the program named on an operation's framework name (``jit(f)/a/
    while/body/b/op:`` -> ``a/b``, with `` bwd`` under a ``transpose(``); the HLO
    instruction's name without its number where there is none."""
    path = str(event.stats.get("tf_op", "")).rstrip(":")
    parts = [scope_core(c) for c in path.split("/") if c]
    if len(parts) < 2:
        return re.sub(r"[.\d]+$", "", rt.op_label(event)) or "?"
    named = [p for p in parts[1:-1] if p not in STRUCTURAL and not p.startswith("branch_")]
    return ("/".join(named[-2:]) or parts[-1]) + (" bwd" if "transpose(" in path else "")


def gap_table(trace, function: str = "train_step", min_gap_ns: float = MIN_GAP_NS) -> GapTable | None:
    """The first chip's gaps over the trace, cut at the executions of ``jit_<function>``.
    None where the trace holds no such execution. Both readers ask for one trace's table: it
    is kept on the trace."""
    kept = trace.__dict__.setdefault("_gap_tables", {})
    if (function, min_gap_ns) in kept:
        return kept[function, min_gap_ns]
    device = trace.devices[0]
    executions = sorted((m.start_ns, m.end_ns) for m in device.modules if m.name.startswith(f"jit_{function}("))
    table = None
    if executions and device.ops:
        ops = sorted(device.ops, key=lambda e: (e.start_ns, -e.duration_ns))
        gaps, small_ns, small_count = [], 0.0, 0
        busy_end, last_leaf = ops[0].start_ns, None
        for i, op in enumerate(ops):
            if op.start_ns > busy_end:
                if op.start_ns - busy_end < min_gap_ns:
                    small_ns += op.start_ns - busy_end
                    small_count += 1
                else:
                    # the first operation that is no container: the one a loop that opens
                    # where the gap ends runs first
                    first_leaf = first_leaf_from(ops, i) or op
                    before = scope_label(last_leaf) if last_leaf is not None else "?"
                    after = (f"enters {container_kind(op)}: " if container_kind(op) else "") + scope_label(first_leaf)
                    inside, outside = cut(busy_end, op.start_ns, executions)
                    gaps.append(Gap(busy_end, op.start_ns, before, after, inside, outside))
            if container_kind(op) is None and (last_leaf is None or op.end_ns >= last_leaf.end_ns):
                last_leaf = op
            busy_end = max(busy_end, op.end_ns)
        extent = busy_end - ops[0].start_ns
        idle_ns = trace.window_s * 1e9 * trace.idle_share
        table = GapTable(len(executions), gaps, small_ns, small_count, trace.window_s * 1e9 - extent, idle_ns)
    kept[function, min_gap_ns] = table
    return table


def first_leaf_from(ops: list, at: int):
    """The first event from index ``at`` on that is no container (None: there is none)."""
    for event in ops[at : at + 64]:  # a loop nest is a few events deep
        if container_kind(event) is None:
            return event
    return None


def cut(start: float, end: float, executions: list) -> tuple[float, list]:
    """(nanoseconds of [start, end) inside the sorted, disjoint ``executions``, its parts
    outside all of them)."""
    inside, outside, at = 0.0, [], start
    for a, b in executions:
        if b <= start:
            continue
        if a >= end:
            break
        if a > at:
            outside.append((at, a))
        inside += min(b, end) - max(a, start)
        at = max(at, min(b, end))
    if at < end:
        outside.append((at, end))
    return inside, outside


def container_self_times(trace, function: str = "train_step") -> dict:
    """{scope of a loop's or a cond's first operation: self seconds of the container's
    event}: what the event covers and no operation inside it does — the loop's own
    bookkeeping and whatever it waits for between its body's operations. The busy union
    counts all of it as busy."""
    device = trace.devices[0]
    program_ids = {m.name[m.name.index("(") + 1 : -1] for m in device.modules if m.name.startswith(f"jit_{function}(")}
    ops = [e for e in device.ops if str(e.stats.get("program_id", "")) in program_ids]
    timed = self_times(ops)
    events = [event for event, _ in timed]
    totals: dict = {}
    for i, (event, self_ns) in enumerate(timed):
        kind = container_kind(event)
        if kind is None:
            continue
        inner = first_leaf_from(events, i + 1)
        inside = inner is not None and inner.start_ns < event.end_ns
        label = f"{kind}: " + (scope_label(inner) if inside else "(empty)")
        totals[label] = totals.get(label, 0.0) + self_ns / 1e9
    return totals
