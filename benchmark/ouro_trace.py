"""What the readers of `ouro`'s per-layer metrics share beside ``tower_trace``: whether a cell runs
this family, and the device time of each PASS of the loop.

The loop is one ``lax.scan``: a device trace shows one ``while`` a direction a step, whose body's
operations recur once an iteration under the one scope ``pass`` — the passes have no names of
their own. An iteration is found by recurrence: an operation of the body runs once a pass, so
the operations under ``pass`` whose name recurs `passes` times a step are the body's, and the
starts of the earliest of them cut the step's forward operations into passes; the same on the
backward side (names that carry
``transpose(``: the blocks' replays and backward passes), whose iterations run from the last
pass to the first.
"""

from __future__ import annotations

from benchmark import phases

PASS_SCOPE = "pass"


def of_this_family(ctx) -> bool:
    """Whether the cell's configuration names this family's modules (``benchmark_modules``)."""
    return ctx.cell.config.get("benchmark_modules", {}).get("weights") == "benchmark.weights_ouro"


def _cut(events: list, passes: int) -> list | None:
    """[seconds an iteration] of one direction's operations of one step ([(event, self ns)], by
    start). An operation of the loop's body runs once an iteration, so its name recurs `passes`
    times a step; one that recurs otherwise lies outside the loop (a cast the compiler hoisted)
    and is left out. The earliest body operation's starts cut the step into iterations. None
    where nothing recurs `passes` times."""
    count: dict = {}
    for event, _ in events:
        count[event.name] = count.get(event.name, 0) + 1
    body = [(event, self_ns) for event, self_ns in events if count[event.name] == passes]
    if not body:
        return None
    starts = [event.start_ns for event, _ in body if event.name == body[0][0].name]
    seconds = [0.0] * passes
    for event, self_ns in body:
        seconds[max(sum(start <= event.start_ns for start in starts) - 1, 0)] += self_ns / 1e9
    return seconds


_newest: tuple = (None, None, None)  # (trace, passes, its table): two readers ask for the same one


def pass_seconds(result, passes: int) -> list | None:
    """[[seconds of pass 1, ..., pass T] a traced step]: self time of the first chip's operations
    under the ``pass`` scope, forward and backward of a pass added. None where the run traced no
    train step, the program names no pass, or an iteration cannot be told from the next."""
    global _newest
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    if _newest[0] is not result.trace or _newest[1] != passes:
        _newest = (result.trace, passes, _pass_seconds(result.trace, passes))
    return _newest[2]


def _pass_seconds(trace, passes: int) -> list | None:
    device = trace.devices[0]
    executions = sorted((m for m in device.modules if m.name.startswith("jit_train_step(")), key=lambda m: m.start_ns)
    if not executions:
        return None
    program_ids = {m.name[m.name.index("(") + 1 : -1] for m in executions}
    ops = [e for e in device.ops if str(e.stats.get("program_id", "")) in program_ids]
    in_pass = []
    for event, self_ns in phases.self_times(ops):
        path = str(event.stats.get("tf_op", "")).rstrip(":")
        if PASS_SCOPE in {phases.scope_core(c) for c in path.split("/") if c}:
            in_pass.append((event, self_ns, "transpose(" in path))
    if not in_pass:
        return None
    steps = []
    for execution in executions:
        mine = [(e, ns, back) for e, ns, back in in_pass if execution.start_ns <= e.start_ns < execution.end_ns]
        forward = _cut([(e, ns) for e, ns, back in mine if not back], passes)
        backward = _cut([(e, ns) for e, ns, back in mine if back], passes)
        if forward is None or backward is None:
            return None
        steps.append([f + b for f, b in zip(forward, reversed(backward))])
    return steps
