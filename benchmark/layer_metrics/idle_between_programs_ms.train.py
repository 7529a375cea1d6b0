"""Milliseconds a traced training step in which the device ran nothing *outside* the step's
program: the gaps (at least 20 us) of the first chip's busy time, less their parts inside an
execution of ``jit_train_step`` (``benchmark/idle_trace.py``; those are
``idle_in_step_ms.train``). The device waits for the host here, and the host loop mends it.
Each gap is shared among the annotations of the loop's thread it overlaps **in proportion to
the overlap**, innermost (shortest) first — ``breakdown.idle_gaps`` charges a whole gap to
the span over its middle — so the nested spans of ``loop.sync`` and ``loop.log``
(``sync.step``, ``sync.read``, ``log.read``, ``log.track``, ``log.progress``) take what lies
under them and the parent what is left. Spans that the program's ``step`` records list under
``t.off_loop`` ran on another thread (the prefetch worker's) and claim nothing: what they
overlap is printed beside. A garbage collection (``gc.collect``) holds every thread and
claims wherever it ran. Printed: ms a step by span and by (program before -> program
after). Layer: device (TPU busy and idle). Moves ``train_tokens_per_s_per_chip``.
"""

from benchmark import idle_trace
from benchmark import reduce_trace as rt

HOLDS_EVERY_THREAD = {"gc.collect"}


def share_by_overlap(start: float, end: float, spans: list) -> dict:
    """{span name: nanoseconds} of [start, end): each span, shortest first, takes the part
    of its overlap that no shorter span took; ``(no host span)`` what none covers."""
    shares: dict = {}
    taken: list = []  # disjoint, sorted
    for span in sorted(spans, key=lambda s: s.duration_ns):
        a, b = max(span.start_ns, start), min(span.end_ns, end)
        if b <= a:
            continue
        free = (b - a) - sum(max(min(b, y) - max(a, x), 0.0) for x, y in taken)
        if free > 0:
            shares[span.name] = shares.get(span.name, 0.0) + free
            taken = rt.merged(taken + [(a, b)])
    left = (end - start) - sum(y - x for x, y in taken)
    if left > 1e-3:
        shares["(no host span)"] = left
    return shares


def program_names(modules: list, start: float, end: float) -> str:
    """``before -> after``: the programs on either side of [start, end), or the one it lies
    inside (an eager program of the loop that waits on itself)."""
    name = lambda m: m.name.split("(")[0]  # noqa: E731
    around = [m for m in modules if m.start_ns <= start and m.end_ns >= end]
    if around:
        return f"inside {name(around[0])}"
    before = max((m for m in modules if m.end_ns <= start + 1e3), key=lambda m: m.end_ns, default=None)
    after = min((m for m in modules if m.start_ns >= end - 1e3), key=lambda m: m.start_ns, default=None)
    return f"{name(before) if before else '(window opens)'} -> {name(after) if after else '(window closes)'}"


def between_programs(trace, function: str = "train_step", off_loop: frozenset = frozenset()):
    """(ms a step, {span: ns}, {programs: ns}, {off-loop span: ns it overlapped}) of the
    gaps outside every execution of ``jit_<function>``; None where the trace holds none."""
    table = idle_trace.gap_table(trace, function)
    if table is None:
        return None
    annotations = [s for s in trace.host_spans if rt.is_annotation(s.name)]
    of_loop = [s for s in annotations if s.name not in off_loop or s.name in HOLDS_EVERY_THREAD]
    beside = [s for s in annotations if s.name in off_loop and s.name not in HOLDS_EVERY_THREAD]
    modules = trace.devices[0].modules
    by_span: dict = {}
    by_programs: dict = {}
    overlapped: dict = {}
    for gap in table.gaps:
        for start, end in gap.outside:
            for name, ns in share_by_overlap(start, end, [s for s in of_loop if s.start_ns < end and s.end_ns > start]).items():
                by_span[name] = by_span.get(name, 0.0) + ns
            key = program_names(modules, start, end)
            by_programs[key] = by_programs.get(key, 0.0) + (end - start)
            for span in beside:
                ns = min(span.end_ns, end) - max(span.start_ns, start)
                if ns > 0:
                    overlapped[span.name] = overlapped.get(span.name, 0.0) + ns
    return table.outside_ns / 1e6 / table.steps, by_span, by_programs, overlapped, table


def read(result, ctx):
    if result.trace is None or "traced_steps" not in result.facts:
        return None
    off_loop = frozenset(
        name for r in result.telemetry if r.get("kind") == "step" for name in r.get("t", {}).get("off_loop", ())
    )
    found = between_programs(result.trace, off_loop=off_loop)
    if found is None:
        return None
    value, by_span, by_programs, overlapped, table = found

    def line(totals: dict) -> str:
        ordered = sorted(totals.items(), key=lambda kv: -kv[1])[:12]
        return "; ".join(f"{name} {ns / 1e6 / table.steps:.3f}" for name, ns in ordered)

    print(f"idle_between_programs_ms.train: {table.identity()}; over {table.steps} steps", flush=True)
    print(f"idle_between_programs_ms.train: ms a step by the loop's span (shared by overlap): {line(by_span)}", flush=True)
    print(f"idle_between_programs_ms.train: ms a step by programs: {line(by_programs)}", flush=True)
    if overlapped:
        print(f"idle_between_programs_ms.train: other threads' spans over those gaps, ms a step: {line(overlapped)}", flush=True)
    return value
