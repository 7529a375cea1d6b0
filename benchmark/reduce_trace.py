"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer metrics read.

What the trace holds on a TPU (looked at by hand, PR 23): one plane ``/device:TPU:<n>`` a
chip, with a line ``XLA Modules`` (one event a program execution, named
``jit_<function>(<hash>)``), a line ``XLA Ops`` (one event an operation, named by its HLO
text; its metadata carries ``tf_op`` — the framework name, ``jit(f)/scope/.../op:``, where a
``jax.named_scope`` survives — ``hlo_category``, ``flops`` and ``bytes_accessed``) and a line
``Async XLA Ops`` (copies and collectives in flight; no metric reads it yet); and a plane
``/host:CPU`` whose lines hold the host's spans, ``jax.profiler.TraceAnnotation`` among
them. Device and host share one clock to within about a millisecond.
"""

from __future__ import annotations

import glob
import os
import statistics
from dataclasses import dataclass

from .xplane import Event, Plane, read_xplane


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a directory given to ``jax.profiler.start_trace``."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals (nanoseconds in, seconds out)."""
    total, current_start, current_end = 0.0, None, None
    for start, end in sorted(intervals):
        if current_end is None or start > current_end:
            if current_end is not None:
                total += current_end - current_start
            current_start, current_end = start, end
        else:
            current_end = max(current_end, end)
    if current_end is not None:
        total += current_end - current_start
    return total / 1e9


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


@dataclass
class DeviceTrace:
    name: str
    modules: list  # Events of the XLA Modules line
    ops: list  # Events of the XLA Ops line

    def busy_intervals(self) -> list[tuple[float, float]]:
        return [(e.start_ns, e.end_ns) for e in self.ops]


@dataclass
class TraceSummary:
    """One reduced trace. ``window_s`` is the traced window's length as the benchmark timed
    it on the host (between ``start_trace`` returning and ``stop_trace`` being called);
    where that is not given it is the extent of the device events."""

    devices: list  # DeviceTrace, in plane order
    host_spans: list  # Events of the host plane that are annotations (not python frames)
    window_s: float

    # -- device
    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips used."""
        return statistics.fmean(union_seconds(d.busy_intervals()) for d in self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_seconds(self, scope_prefix: str) -> float:
        """Summed device time, on the first chip, of operations under a named scope whose
        name starts with ``scope_prefix`` (a kernel family)."""
        needle = "/" + scope_prefix
        return sum(e.duration_ns for e in self.devices[0].ops if needle in str(e.stats.get("tf_op", ""))) / 1e9

    def program_durations(self, function_prefix: str) -> list[float]:
        """Device seconds of each execution, on the first chip, of programs jitted from a
        function whose name starts with ``function_prefix``."""
        needle = "jit_" + function_prefix
        return [e.duration_ns / 1e9 for e in self.devices[0].modules if e.name.startswith(needle)]

    def program_durations_by_program(self, function_prefix: str) -> dict:
        """As ``program_durations``, apart for each compiled program (``jit_f(<hash>)``): one
        function jitted at several shapes gives several programs under one name."""
        needle = "jit_" + function_prefix
        out: dict = {}
        for e in self.devices[0].modules:
            if e.name.startswith(needle):
                out.setdefault(e.name, []).append(e.duration_ns / 1e9)
        return out

    def program_names(self) -> dict:
        names: dict = {}
        for e in self.devices[0].modules:
            key = e.name.split("(")[0]
            names[key] = names.get(key, 0) + 1
        return names

    def program_gaps(self) -> list[float]:
        """Seconds between the end of one program on the first chip and the start of the
        next."""
        modules = sorted(self.devices[0].modules, key=lambda e: e.start_ns)
        return [max(b.start_ns - a.end_ns, 0.0) / 1e9 for a, b in zip(modules, modules[1:])]

    # -- breakdown
    def top_device_ops(self, count: int = 10) -> list:
        """[name, seconds] of the operations that took most time on the first chip, under
        the name the trace gives: the named scope where one survives, else the HLO name."""
        totals: dict = {}
        for e in self.devices[0].ops:
            totals[op_label(e)] = totals.get(op_label(e), 0.0) + e.duration_ns / 1e9
        return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:count]]

    def idle_gaps(self, count: int = 10, min_gap_ns: float = 20_000.0) -> list:
        """[host span, seconds]: the first chip's idle time inside the window, summed by
        the innermost annotation of the program or the benchmark that covers the middle of each
        gap (the runtime's own innermost span where there is none)."""
        busy = merged(self.devices[0].busy_intervals())
        totals: dict = {}
        for (_, end_a), (start_b, _) in zip(busy, busy[1:]):
            gap = start_b - end_a
            if gap < min_gap_ns:
                continue
            middle = end_a + gap / 2
            covering = [s for s in self.host_spans if s.start_ns <= middle <= s.end_ns]
            named = [s for s in covering if is_annotation(s.name)] or covering
            name = min(named, key=lambda s: s.duration_ns).name if named else "(no host span)"
            totals[name] = totals.get(name, 0.0) + gap / 1e9
        return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:count]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_device_ops(), "idle_gaps": self.idle_gaps()}


def is_annotation(name: str) -> bool:
    """A span the program or the benchmark wrote (``train_step``, ``data_fetch``,
    ``bench.engine_step``), not one of the runtime's own (``PjitFunction(f)``,
    ``tpu::System::Execute``, ``D2H Dispatch``): lower case, no call syntax."""
    return name == name.lower() and not any(c in name for c in "(:$ ")


def op_label(event: Event) -> str:
    """``scope/op`` from the framework name where there is one (``jit(f)/a/b/op:`` ->
    the last two parts), else the HLO instruction's name."""
    tf_op = str(event.stats.get("tf_op", "")).rstrip(":")
    parts = [p for p in tf_op.split("/") if p]
    if len(parts) >= 2:
        return "/".join(parts[-2:])[:120]
    return str(event.stats.get("display_name") or event.name.split(" = ")[0]).lstrip("%")[:120]


def summarize(planes: list[Plane], window_s: float | None = None) -> TraceSummary:
    devices, host_spans = [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line.events for line in plane.lines}
            if lines.get("XLA Ops"):
                devices.append(
                    DeviceTrace(plane.name, lines.get("XLA Modules", []), lines["XLA Ops"])
                )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host_spans.extend(e for e in line.events if not e.name.startswith("$"))
    if not devices:
        raise ValueError("the trace holds no device plane with operations")
    if window_s is None:
        events = [e for d in devices for e in d.ops]
        window_s = (max(e.end_ns for e in events) - min(e.start_ns for e in events)) / 1e9
    return TraceSummary(devices, host_spans, window_s)


def reduce_trace(trace_dir_or_file: str, window_s: float | None = None) -> TraceSummary:
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = find_xplane(path)
    return summarize(read_xplane(path), window_s)
