"""What the drivers and the command share: the run's context, the result a driver
returns, a number compared for ``correct``, and the counter of compilations."""

from __future__ import annotations

from dataclasses import dataclass, field


def say(message: str) -> None:
    print(message, flush=True)


@dataclass
class Context:
    """What a driver gets."""

    cell: object
    spec: object
    seed: int
    seconds: float
    trace: bool
    tiny: bool
    out_dir: str
    process_start: float
    peaks: dict | None
    compiles: "CompileCounter"
    skip_check: bool = False  # tools only (the rate sweep): no reference, never correct
    control: bool = False  # also read the control (the limits' upper end): never in a benchmark run

    def mark(self, what: str) -> None:
        """Where set-up's time goes: seconds since the process started."""
        import time

        say(f"benchmark: [{time.perf_counter() - self.process_start:7.2f} s] {what}")


@dataclass
class Check:
    """One number compared for ``correct``, beside its limit."""

    name: str
    value: float
    limit: float
    ok: bool
    note: str = ""


@dataclass
class RunResult:
    """What a driver returns."""

    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value (every end-to-end metric of the cell)
    checks: list  # Check
    memory_stats: dict | None = None
    # what the per-layer readers read (a traced run fills the trace)
    trace: object | None = None  # reduce_trace.TraceSummary
    telemetry: list = field(default_factory=list)  # the program's telemetry records
    requests: list = field(default_factory=list)  # the serve driver's per-request records
    facts: dict = field(default_factory=dict)  # sizes and counts of the window


class CompileCounter:
    """Counts XLA backend compilations (cache hits too: a program that is fetched inside
    the window was not warmed up). ``open()`` and ``close()`` bracket the measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.in_window = 0
        self.names: list = []  # what compiled inside the window, and for how long
        self._open = False
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT and self._open:
            self.in_window += 1
            self.names.append(f"{kwargs.get('fun_name', '?')} ({duration * 1e3:.1f} ms)")

    def open(self) -> None:
        self._open = True

    def close(self) -> None:
        self._open = False


def device_record(devices, memory_stats: dict | None) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int((memory_stats or {}).get("peak_bytes_in_use", 0)),
    }


def fullest_memory_stats(devices) -> dict | None:
    """``memory_stats()`` of the chip with the highest peak (None on the CPU backend)."""
    stats = [s for s in (d.memory_stats() for d in devices) if s]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0), default=None)
