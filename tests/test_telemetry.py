"""Telemetry layer tests (ISSUE 2 tentpole): JSONL sink round-trip and schema, goodput
window accounting math, on-demand profiler trigger polling, cross-module counter wiring
(retry/fault-tolerance/checkpointing), the fixed profiler-schedule fix, and a tiny
train-loop smoke run guarding the sink against partial-write corruption.

All CPU-only pytrees — no sharded-model paths (those are broken at seed, see memory)."""

import gc
import importlib.util
import json
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dolomite_engine_tpu import finetune, train_utils
from dolomite_engine_tpu.arguments import TrainingArgs
from dolomite_engine_tpu.checkpointing import save_checkpoint
from dolomite_engine_tpu.train_utils import (
    TrainState,
    get_profiler_context,
    handle_nonfinite_step,
    reset_profiler_schedule,
)
from dolomite_engine_tpu.utils import StallWatchdog, retry_io
from dolomite_engine_tpu.utils.diagnostics import HealthMonitor
from dolomite_engine_tpu.utils.telemetry import (
    OnDemandProfiler,
    Telemetry,
    _NullTelemetry,
    build_telemetry,
    detect_peak_tflops_per_device,
    get_telemetry,
    install_telemetry,
    span_holding_the_excess,
    uninstall_telemetry,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_summary_tool():
    spec = importlib.util.spec_from_file_location(
        "telemetry_summary", os.path.join(_REPO_ROOT, "tools", "telemetry_summary.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_sink(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.fixture(autouse=True)
def _clean_registry():
    uninstall_telemetry()
    reset_profiler_schedule()
    yield
    uninstall_telemetry()
    reset_profiler_schedule()


# --------------------------------------------------------------------------- sink schema


def test_sink_round_trip_and_schema(tmp_path):
    sink = tmp_path / "telemetry" / "rank-00000.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    telemetry.count("io_retries", 2)
    telemetry.gauge("custom", 7)
    telemetry.event("nan_skips", step=3, total=1)
    telemetry.record_step(1, data_seconds=0.25, step_seconds=2.0)  # first step -> compile
    telemetry.record_step(2, data_seconds=0.25, step_seconds=0.5)
    telemetry.emit_window(2)
    telemetry.close()

    records = _read_sink(sink)
    kinds = [r["kind"] for r in records]
    assert kinds == ["run_start", "event", "step", "step", "window", "run_end"]
    # every record is rank-tagged and timestamped
    assert all(r["rank"] == 0 and "ts" in r for r in records)

    run_start = records[0]
    assert run_start["schema"] == 1
    assert run_start["devices"] == jax.device_count()

    first_step, second_step = records[2], records[3]
    assert first_step["step"] == 1 and "compile" in first_step["t"]
    assert "step" not in first_step["t"]  # first-step wall time is all compile
    assert second_step["t"]["step"] == pytest.approx(0.5)

    window = records[4]
    assert window["counters"]["io_retries"] == 2
    assert window["counters"]["nan_skips"] == 0  # canonical set pre-seeded at 0
    assert window["gauges"]["custom"] == 7
    assert records[-1]["kind"] == "run_end"
    assert records[-1]["counters"]["io_retries"] == 2


def test_sink_none_is_noop_but_registry_still_counts():
    telemetry = Telemetry(sink_path=None, rank=0)
    telemetry.count("nan_skips", event=True, step=1)
    telemetry.record_step(1, 0.1, 0.1)
    assert telemetry.emit_window(1) is not None
    telemetry.close()
    assert telemetry.counters["nan_skips"] == 1


# --------------------------------------------------------------------------- goodput math


def test_goodput_window_accounting_and_mfu(tmp_path):
    telemetry = Telemetry(
        sink_path=str(tmp_path / "t.jsonl"),
        model_tflops_per_step=10.0,  # 10 TFLOPs per step per group
        peak_tflops_per_device=100.0,
        devices_per_group=2,
        rank=0,
    )
    telemetry.record_step(1, data_seconds=1.0, step_seconds=5.0)  # compile
    telemetry.record_step(2, data_seconds=1.0, step_seconds=0.5)
    telemetry.record_step(3, data_seconds=1.0, step_seconds=0.3)

    # steady mean step = 0.4s -> 25 TFLOPs/group achieved vs 200 peak -> 12.5% MFU
    assert telemetry.current_mfu() == pytest.approx(12.5)

    with telemetry.span("loop.checkpoint", bucket="checkpoint"):
        pass
    window = telemetry.emit_window(3)
    goodput = window["goodput"]
    assert goodput["compile"] == pytest.approx(5.0)
    assert goodput["data"] == pytest.approx(3.0)
    assert goodput["step"] == pytest.approx(0.8)
    assert window["step_time"] == {"count": 2, "mean": 0.4, "min": 0.3, "max": 0.5}
    assert window["mfu_pct"] == pytest.approx(12.5)
    assert window["tflops_per_group"] == pytest.approx(25.0)
    # wall is real elapsed time (tiny here), so "other" >= 0 and buckets don't exceed wall
    assert goodput["other"] >= 0.0

    # window accumulators reset; counters are cumulative
    telemetry.count("nan_skips")
    assert telemetry.current_mfu() is None  # no steady steps in the new window yet
    window2 = telemetry.emit_window(4)
    assert window2["goodput"]["compile"] == 0.0
    assert window2["counters"]["nan_skips"] == 1
    telemetry.close()


def test_mfu_none_without_peak_or_model_flops():
    telemetry = Telemetry(sink_path=None, model_tflops_per_step=None, rank=0)
    telemetry.record_step(1, 0.1, 0.1)
    telemetry.record_step(2, 0.1, 0.1)
    assert telemetry.current_mfu() is None
    telemetry.close()


def test_tracker_fanout_scalars(tmp_path):
    tracked = []

    class _Tracker:
        def track(self, values, step=None, context=None):
            tracked.append((values, step, context))

    telemetry = Telemetry(
        sink_path=None,
        experiments_tracker=_Tracker(),
        model_tflops_per_step=1.0,
        peak_tflops_per_device=10.0,
        rank=0,
    )
    telemetry.record_step(1, 0.1, 0.1)
    telemetry.record_step(2, 0.1, 0.1)
    telemetry.count("io_retries")
    telemetry.emit_window(2)
    telemetry.close()

    assert len(tracked) == 1
    values, step, context = tracked[0]
    assert step == 2 and context == "telemetry"
    assert "goodput/goodput_pct" in values
    assert "goodput/mfu_pct" in values
    assert values["counter/io_retries"] == 1


def test_detect_peak_tflops_by_device_kind():
    class _FakeDevice:
        device_kind = "TPU v4"

    assert detect_peak_tflops_per_device(_FakeDevice()) == 275.0
    _FakeDevice.device_kind = "TPU v5 lite"  # what a v5e reports
    assert detect_peak_tflops_per_device(_FakeDevice()) == 197.0
    _FakeDevice.device_kind = "cpu"
    assert detect_peak_tflops_per_device(_FakeDevice()) is None
    assert detect_peak_tflops_per_device() is None  # this process runs on the CPU
    # a TPU the table does not know is an error, never a default
    _FakeDevice.device_kind = "TPU v9 mega"
    with pytest.raises(ValueError, match="v9 mega"):
        detect_peak_tflops_per_device(_FakeDevice())


# --------------------------------------------------------------------------- on-demand profiler


@pytest.fixture()
def _fake_profiler(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda path: calls.append(("start", path)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop", None)))
    return calls


def test_on_demand_touch_file_trigger(tmp_path, _fake_profiler):
    trigger = tmp_path / "PROFILE_TRIGGER"
    profiler = OnDemandProfiler(
        str(trigger), str(tmp_path / "traces"), num_steps=2, use_signal=False
    )
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), profiler=profiler, rank=0)

    telemetry.poll_profiler(1)
    assert _fake_profiler == []  # no trigger yet

    trigger.touch()
    telemetry.poll_profiler(2)  # consumes the trigger, starts the capture
    assert not trigger.exists()
    assert _fake_profiler == [("start", str(tmp_path / "traces" / "step3"))]
    assert profiler.active

    telemetry.poll_profiler(3)  # 1 step covered, window not done
    assert len(_fake_profiler) == 1
    telemetry.poll_profiler(4)  # 2 steps covered -> stop
    assert _fake_profiler[-1] == ("stop", None)
    assert not profiler.active
    assert telemetry.counters["profiles_captured"] == 1

    events = [r for r in _read_sink(sink) if r["kind"] == "event"]
    assert [e["event"] for e in events] == ["profile_start", "profiles_captured"]
    telemetry.close()


def test_on_demand_sigusr1_trigger(tmp_path, _fake_profiler):
    previous = signal.getsignal(signal.SIGUSR1)
    try:
        profiler = OnDemandProfiler(
            str(tmp_path / "trigger"), str(tmp_path / "traces"), num_steps=1, use_signal=True
        )
        os.kill(os.getpid(), signal.SIGUSR1)
        import time

        deadline = time.time() + 2
        while not profiler._signal_flag.is_set() and time.time() < deadline:
            time.sleep(0.01)
        profiler.poll(5)
        assert _fake_profiler and _fake_profiler[0][0] == "start"
        profiler.poll(6)
        assert _fake_profiler[-1][0] == "stop"
    finally:
        signal.signal(signal.SIGUSR1, previous)


def test_on_demand_close_commits_in_flight_capture(tmp_path, _fake_profiler):
    profiler = OnDemandProfiler(
        str(tmp_path / "trigger"), str(tmp_path / "traces"), num_steps=10, use_signal=False
    )
    (tmp_path / "trigger").touch()
    profiler.poll(1)
    assert profiler.active
    profiler.close()  # run ended mid-capture: the trace must still be committed
    assert _fake_profiler[-1][0] == "stop"
    assert not profiler.active


def test_failed_capture_start_never_kills_training(tmp_path, monkeypatch):
    def boom(path):
        raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    profiler = OnDemandProfiler(
        str(tmp_path / "trigger"), str(tmp_path / "traces"), num_steps=1, use_signal=False
    )
    (tmp_path / "trigger").touch()
    profiler.poll(1)  # must swallow the error
    assert not profiler.active


class _Pending:
    """Stands for a dispatched step's output: `jax.block_until_ready` waits on it."""

    def __init__(self, calls):
        self.calls = calls

    def block_until_ready(self):
        self.calls.append(("wait", None))
        return self


def test_both_capture_paths_stop_only_after_the_wait(tmp_path, monkeypatch, _fake_profiler):
    """A capture holds whole steps: the on-demand path and the fixed schedule start and stop
    the profiler only after a wait on the newest dispatched step's outputs, through one
    helper (dispatch is asynchronous: a trace stopped right after it loses the step)."""
    pending = _Pending(_fake_profiler)
    profiler = OnDemandProfiler(
        str(tmp_path / "trigger"), str(tmp_path / "traces"), num_steps=1, use_signal=False
    )
    (tmp_path / "trigger").touch()
    profiler.poll(1, last_outputs={"loss": pending})
    profiler.poll(2, last_outputs={"loss": pending})
    assert [c[0] for c in _fake_profiler] == ["wait", "start", "wait", "stop"]

    # the fixed schedule enters and leaves `jax.profiler.trace` (start_trace / stop_trace)
    del _fake_profiler[:]

    class _Trace:
        def __init__(self, path):
            self.path = path

        def __enter__(self):
            _fake_profiler.append(("start", self.path))

        def __exit__(self, *exc_info):
            _fake_profiler.append(("stop", None))

    monkeypatch.setattr(jax.profiler, "trace", _Trace)
    outputs = {"step": None}  # the loop's `metrics`: None before the first dispatch
    with get_profiler_context(str(tmp_path / "fixed"), 6, lambda: outputs["step"]):
        assert [c[0] for c in _fake_profiler] == ["start"]  # nothing dispatched: no wait
        outputs["step"] = {"loss": pending}  # the step dispatched inside the context
    assert [c[0] for c in _fake_profiler] == ["start", "wait", "stop"]

    # a capture never raises into training: a wait or a stop that fails is logged
    def boom():
        raise RuntimeError("profiler backend gone")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    (tmp_path / "trigger").touch()
    profiler.poll(3, last_outputs=None)
    profiler.poll(4, last_outputs=None)
    assert not profiler.active


# --------------------------------------------------------------------------- counter wiring


def test_retry_io_counts_retries_and_failures(tmp_path):
    telemetry = Telemetry(sink_path=str(tmp_path / "t.jsonl"), rank=0)
    install_telemetry(telemetry)

    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("blip")
        return "ok"

    assert retry_io(flaky, attempts=3, sleep=lambda d: None) == "ok"
    assert telemetry.counters["io_retries"] == 2

    with pytest.raises(OSError):
        retry_io(lambda: (_ for _ in ()).throw(OSError("down")), attempts=2, sleep=lambda d: None)
    assert telemetry.counters["io_retries"] == 3
    assert telemetry.counters["io_failures"] == 1
    events = [r for r in _read_sink(tmp_path / "t.jsonl") if r["kind"] == "event"]
    assert any(e["event"] == "io_failures" for e in events)
    telemetry.close()


def test_nonfinite_step_counts_nan_skips(tmp_path):
    telemetry = Telemetry(sink_path=str(tmp_path / "t.jsonl"), rank=0)
    install_telemetry(telemetry)
    consecutive = handle_nonfinite_step(True, 0, global_step=7, max_consecutive=10)
    assert consecutive == 1
    handle_nonfinite_step(False, consecutive, global_step=8, max_consecutive=10)
    assert telemetry.counters["nan_skips"] == 1
    events = [r for r in _read_sink(tmp_path / "t.jsonl") if r["kind"] == "event"]
    assert events[0]["event"] == "nan_skips" and events[0]["step"] == 7
    telemetry.close()


def test_stall_watchdog_counts_loader_stalls(tmp_path):
    telemetry = Telemetry(sink_path=str(tmp_path / "t.jsonl"), rank=0)
    install_telemetry(telemetry)
    release = threading.Event()

    def hung():
        yield 1
        release.wait(30)

    watchdog = StallWatchdog(hung(), timeout_seconds=0.2)
    assert next(watchdog) == 1
    with pytest.raises(RuntimeError, match="stalled"):
        next(watchdog)
    release.set()
    watchdog.close()
    assert telemetry.counters["loader_stalls"] == 1
    telemetry.close()


def test_checkpoint_save_and_prune_counters(tmp_path):
    telemetry = Telemetry(sink_path=None, rank=0)
    install_telemetry(telemetry)

    params = {"w": jnp.ones((4,), jnp.float32)}
    optimizer = optax.sgd(1e-2)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params)
    )
    args = TrainingArgs(
        model_args=dict(
            model_class="AutoModelForCausalLM",
            pretrained_config=dict(
                model_type="gpt_dolomite", vocab_size=8, n_positions=8, n_embd=4,
                n_layer=1, n_head=1,
            ),
        ),
        tuning_args=dict(tuning_method="full_finetuning"),
        training_parameters=dict(
            num_training_steps=5, micro_batch_size=2, eval_during_training=False
        ),
        datasets=[dict(class_name="DebugDataset", data_name="debug", class_args={})],
        save_args=dict(save_path=str(tmp_path / "ckpt"), save_interval=1, keep_last_n=1),
    )
    save_checkpoint(args, None, state, None, None, iteration=1)
    save_checkpoint(args, None, state, None, None, iteration=2)  # prunes global_step1
    assert telemetry.counters["checkpoints_saved"] == 2
    assert telemetry.counters["checkpoints_pruned"] == 1
    telemetry.close()


def test_step_record_split_sums_to_wall_time(tmp_path):
    """Inside a train loop the loop thread's outermost spans tile the iteration: the step
    record's split sums to its wall time; nested spans and other threads' spans stay out;
    `t.data` and `t.step` stay what the caller measured."""
    import time

    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    telemetry.begin_iterations()
    for step in (1, 2, 3):
        with telemetry.span("loop.data_wait"):
            time.sleep(0.002)
            with telemetry.span("data_fetch"):  # nested: inside its parent, not a part
                time.sleep(0.001)
        with telemetry.span("train_step", step=step):
            time.sleep(0.003)
            # another thread's span (the prefetch worker's) is no part of the loop's split
            worker = threading.Thread(target=telemetry.span("prefetch_assemble").__enter__)
            worker.start()
            worker.join()
        with telemetry.span("loop.checkpoint", bucket="checkpoint"):
            time.sleep(0.001)
        telemetry.record_step(step, data_seconds=0.002, step_seconds=0.003)
    window = telemetry.emit_window(3)
    telemetry.close()

    steps = [r for r in _read_sink(sink) if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3]
    for record in steps:
        t = record["t"]
        assert t["data"] == 0.002 and t.get("step", t.get("compile")) == 0.003
        assert set(t["split"]) <= {"loop.record", "loop.data_wait", "train_step", "loop.checkpoint"}
        assert abs(sum(t["split"].values()) - t["wall"]) < max(0.01 * t["wall"], 2e-4)
        assert t["split"]["loop.data_wait"] >= 0.003  # the nested span is inside it
    # the write of a step's record is the head of the next iteration
    assert "loop.record" not in steps[0]["t"]["split"]
    assert list(steps[1]["t"]["split"])[0] == "loop.record"
    assert window["goodput"]["checkpoint"] >= 0.003  # a span's bucket is fed by the same cut


def test_nested_spans_go_to_inner_under_their_own_names(tmp_path):
    """A span nested in one of the loop's is a part of its parent: `t.inner` keeps it under
    its own name (summed over the iteration), the parts sum to the parent to within call
    overhead, `t.split` is what it was, and an iteration without one carries no `inner`."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    telemetry.begin_iterations()
    for step in (1, 2):
        with telemetry.span("loop.sync"):
            if step == 1:
                for _ in range(2):  # the same name twice: summed
                    with telemetry.span("sync.step"):
                        time.sleep(0.002)
                with telemetry.span("sync.read"):
                    time.sleep(0.001)
                    with telemetry.span("deeper"):  # two levels down: still its own name
                        time.sleep(0.001)
        telemetry.record_step(step, 0.0, 0.005)
    telemetry.close()
    first, second = [r["t"] for r in _read_sink(sink) if r["kind"] == "step"]
    assert list(first["inner"]) == ["sync.step", "deeper", "sync.read"]  # in the order they closed
    assert first["inner"]["sync.step"] >= 0.004 and first["inner"]["sync.read"] >= 0.002 and first["inner"]["sync.read"] > first["inner"]["deeper"] >= 0.001
    parts = first["inner"]["sync.step"] + first["inner"]["sync.read"]
    assert 0 <= first["split"]["loop.sync"] - parts < 2e-3
    assert list(first["split"]) == ["loop.sync"] and abs(first["wall"] - first["split"]["loop.sync"]) < 2e-3
    assert "inner" not in second and list(second["split"]) == ["loop.record", "loop.sync"]


def test_spans_closed_on_another_thread_reach_the_iterations_off_loop(tmp_path):
    """The prefetch worker's spans close beside the loop: the iteration in which they closed
    lists their seconds by name under `t.off_loop`; the next one starts from nothing."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)

    def worker():
        for _ in range(2):
            with telemetry.span("prefetch_assemble"):
                time.sleep(0.002)
        with telemetry.span("data_fetch", bucket="data"):
            time.sleep(0.001)

    with telemetry.span("prefetch_assemble"):  # before a train loop: annotates only
        pass
    telemetry.begin_iterations()
    for step in (1, 2):
        with telemetry.span("train_step", step=step):
            if step == 1:
                thread = threading.Thread(target=worker)
                thread.start()
                thread.join()
        telemetry.record_step(step, 0.0, 0.005)
    so_far = telemetry.iteration_so_far()  # the third iteration has only the write of the second's record
    assert list(so_far["split"]) == ["loop.record"] and "off_loop" not in so_far and "inner" not in so_far
    window = telemetry.emit_window(2)
    telemetry.close()
    first, second = [r["t"] for r in _read_sink(sink) if r["kind"] == "step"]
    assert set(first["off_loop"]) == {"prefetch_assemble", "data_fetch"}
    assert first["off_loop"]["prefetch_assemble"] >= 0.004 and first["off_loop"]["data_fetch"] >= 0.001
    assert "off_loop" not in second and "inner" not in first
    assert set(first["split"]) == {"train_step"}  # another thread's span is no part of the loop's tiling
    assert window["goodput"]["data"] >= 0.001  # and its bucket is fed as before


def test_no_span_of_another_thread_is_lost_between_two_records(tmp_path, monkeypatch):
    """Sixteen workers close spans while the loop drains `t.off_loop` as fast as it can: every
    span lands in exactly one iteration's record. A clock that reads 0 at a span's start and 1
    at its end, thread by thread, makes the seconds a count."""
    import itertools
    import sys

    from dolomite_engine_tpu.utils import telemetry as telemetry_module

    local = threading.local()

    def clock():
        if not hasattr(local, "ticks"):
            local.ticks = itertools.cycle((0.0, 1.0))
        return next(local.ticks)

    workers, spans_each = 16, 200
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    monkeypatch.setattr(telemetry_module.time, "perf_counter", clock)
    telemetry.begin_iterations()

    def work():
        for _ in range(spans_each):
            with telemetry.span("prefetch_assemble"):
                pass

    threads = [threading.Thread(target=work) for _ in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for thread in threads:
            thread.start()
        step = 0
        deadline = time.monotonic() + 60
        while any(thread.is_alive() for thread in threads) and time.monotonic() < deadline:
            step += 1
            telemetry.record_step(step, 0.0, 0.0)
        for thread in threads:
            thread.join(timeout=10)
        assert not any(thread.is_alive() for thread in threads)
    finally:
        sys.setswitchinterval(interval)
    telemetry.record_step(step + 1, 0.0, 0.0)  # what closed after the last drain
    monkeypatch.undo()
    telemetry.close()
    steps = [r["t"] for r in _read_sink(sink) if r["kind"] == "step"]
    assert sum(t.get("off_loop", {}).get("prefetch_assemble", 0.0) for t in steps) == workers * spans_each
    assert all(set(t.get("off_loop", {})) <= {"prefetch_assemble"} for t in steps)


def test_garbage_collections_are_timed_from_begin_iterations_to_close(tmp_path):
    """`t.gc`: seconds and count of the collections that ran in the iteration, absent when
    none ran; the hook is installed by `begin_iterations` and gone after `close`."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    hooks = len(gc.callbacks)
    was_enabled = gc.isenabled()
    gc.disable()  # no collection of the interpreter's own inside the test's iterations
    try:
        gc.collect()  # before the loop: not counted
        telemetry.begin_iterations()
        assert len(gc.callbacks) == hooks + 1
        telemetry.begin_iterations()  # again: still one hook
        assert len(gc.callbacks) == hooks + 1
        for step in (1, 2, 3):
            with telemetry.span("loop.sync"):
                if step == 2:
                    gc.collect()
                    thread = threading.Thread(target=gc.collect)  # any thread's collection holds them all
                    thread.start()
                    thread.join()
            if step == 2:
                assert telemetry.iteration_so_far()["gc"]["count"] == 2  # asking takes nothing away
            telemetry.record_step(step, 0.0, 0.001)
        telemetry.close()
        assert len(gc.callbacks) == hooks
        gc.collect()  # after close: nobody listens, nothing raised
    finally:
        if was_enabled:
            gc.enable()
    first, second, third = [r["t"] for r in _read_sink(sink) if r["kind"] == "step"]
    assert "gc" not in first and "gc" not in third
    assert second["gc"]["count"] == 2 and 0 < second["gc"]["seconds"] <= second["wall"]
    assert set(second["split"]) == {"loop.record", "loop.sync"}  # a collection is no span of the tiling


def test_every_iteration_reads_the_process_once(tmp_path):
    """`t.host`: the iteration's involuntary context switches, major page faults and CPU
    seconds; an iteration that sleeps used no CPU, one that spins used what it spun. The spin
    is timed on the process's CPU clock, the one `t.host` reads: on the wall's, a machine that
    runs six workers' tests gives a spin of 20 ms under 10 ms of CPU (it failed so once in PR
    40's runs, ROADMAP D18, and at aac3fe9 beside two dozen busy processes)."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    telemetry.begin_iterations()
    with telemetry.span("loop.sync"):
        time.sleep(0.02)
    telemetry.record_step(1, 0.0, 0.02)
    with telemetry.span("loop.sync"):
        until = time.process_time() + 0.02
        while time.process_time() < until:
            pass
    telemetry.record_step(2, 0.0, 0.02)
    telemetry.close()
    slept, spun = [r["t"] for r in _read_sink(sink) if r["kind"] == "step"]
    for t in (slept, spun):
        assert set(t["host"]) == {"nivcsw", "majflt", "cpu"}
        assert isinstance(t["host"]["nivcsw"], int) and isinstance(t["host"]["majflt"], int)
    assert slept["host"]["cpu"] < 0.01 <= spun["host"]["cpu"]
    assert list(slept) == ["data", "wall", "split", "host", "compile"]  # the optional parts are absent


@pytest.mark.parametrize(
    "stalled, blamed",
    [
        ("sync.step", "sync.step"),  # the step's program took long: the nested span, not `loop.sync`
        ("sync.read", "sync.read"),
        ("loop.sync", "loop.sync"),  # in the parent itself, outside its parts
        ("loop.data_wait", "loop.data_wait"),  # a span with nothing nested in it
    ],
)
def test_a_slow_steps_anomaly_says_what_the_host_was_doing(tmp_path, stalled, blamed):
    """The `anomaly` event of signal `step_time` carries the flagged iteration's parts up to
    the monitor's call — `split`, `inner`, `host` (and `gc`, `off_loop` when there were
    any) — and `blame`: the span that holds the excess over its own rolling median."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    monitor = HealthMonitor(telemetry)
    telemetry.begin_iterations()

    def pause(name):
        if name == stalled and step == 14:
            gc.collect()  # what held it: a collection, and 30 ms more
            time.sleep(0.03)
        time.sleep(0.001)

    for step in range(1, 15):
        start = time.perf_counter()
        with telemetry.span("loop.data_wait"):
            pause("loop.data_wait")
        with telemetry.span("loop.sync"):
            pause("loop.sync")
            with telemetry.span("sync.step"):
                pause("sync.step")
            with telemetry.span("sync.read"):
                pause("sync.read")
        with telemetry.span("loop.account"):
            # the monitor is told a step time (a loaded machine's own pauses flag nothing)
            flagged = monitor.observe_step(step, step_seconds=0.034 if step == 14 else 0.004)
        assert bool(flagged) == (step == 14), (step, flagged)
        telemetry.record_step(step, 0.0, time.perf_counter() - start)
    telemetry.close()
    (event,) = [r for r in _read_sink(sink) if r["kind"] == "event" and r["event"] == "anomaly"]
    assert event["signal"] == "step_time" and event["step"] == 14 and event["ratio"] >= 2.0
    assert event["blame"] == blamed
    assert list(event["split"]) == ["loop.record", "loop.data_wait", "loop.sync"]  # up to the monitor's call
    assert list(event["inner"]) == ["sync.step", "sync.read"]
    assert {**event["split"], **event["inner"]}[stalled] >= 0.03
    assert event["gc"]["count"] >= 1 and set(event["host"]) == {"nivcsw", "majflt", "cpu"}
    # the step's own record carries the same parts, whole
    record = [r for r in _read_sink(sink) if r["kind"] == "step"][-1]["t"]
    assert record["inner"] == event["inner"] and record["split"]["loop.sync"] == event["split"]["loop.sync"]
    assert "loop.account" in record["split"] and record["gc"] == event["gc"]


@pytest.mark.parametrize(
    "split, inner, blamed",
    [
        ({}, {}, None),
        ({"a": 0.010, "b": 0.011}, {}, "b"),  # 1 ms over its median against none
        ({"a": 0.300, "b": 0.010}, {}, "a"),
        ({"a": 0.300, "b": 0.010}, {"a.x": 0.290, "a.y": 0.001}, "a.x"),  # the part that holds it
        ({"a": 0.300, "b": 0.010}, {"a.x": 0.100, "a.y": 0.101}, "a"),  # no part holds half: the parent
        ({"a": 0.010, "b": 0.010, "new": 0.050}, {}, "new"),  # a span the other iterations never ran
        ({"a": 0.010, "b": 0.010}, {"rare": 0.004}, "rare"),  # compared with the iterations that ran it
    ],
)
def test_span_holding_the_excess_and_the_summary_tools_copy_agree(split, inner, blamed):
    history = [{"a": 0.010, "b": 0.010, "a.x": 0.001, "a.y": 0.001}] * 5 + [{"a": 0.012, "b": 0.010, "rare": 0.001}]
    assert span_holding_the_excess(split, inner, history) == blamed
    assert _load_summary_tool().span_holding_the_excess(split, inner, history) == blamed


def test_summary_tool_prints_the_slowest_iterations(tmp_path, capsys):
    """An untraced run that held a stall says what it was: step, wall, ratio to the median,
    the span that holds the excess, and what else the host did in that iteration."""
    sink = tmp_path / "rank-00000.jsonl"
    split = {"loop.data_wait": 0.001, "train_step": 0.002, "loop.sync": 0.145, "loop.log": 0.005}
    inner = {"sync.step": 0.143, "sync.read": 0.0015, "log.read": 0.0005, "log.track": 0.004, "log.progress": 0.0004}
    host = {"nivcsw": 0, "majflt": 0, "cpu": 0.012}
    records = []
    for step in range(2, 42):
        t = {"data": 1e-5, "step": 0.148, "wall": 0.153, "split": dict(split), "inner": dict(inner), "host": dict(host)}
        if step == 17:  # a collection of 0.9 s while the loop read the loss
            t["split"]["loop.sync"] += 0.9
            t["inner"]["sync.read"] += 0.9
            t.update(wall=1.053, gc={"seconds": 0.899, "count": 1}, off_loop={"prefetch_assemble": 0.002})
        if step == 30:  # the process lost the CPU inside the entry point's log line
            t["split"]["loop.log"] += 0.25
            t["inner"]["log.track"] += 0.25
            t.update(wall=0.403, host={"nivcsw": 41, "majflt": 3, "cpu": 0.013})
        records.append({"kind": "step", "ts": 0.0, "rank": 0, "step": step, "t": t})
    sink.write_text("".join(json.dumps(r) + "\n" for r in records))
    assert _load_summary_tool().main([str(sink)]) == 0
    out = capsys.readouterr().out
    rows = [line for line in out.splitlines() if line.startswith("| step ") and "p50" not in line]
    assert len(rows) == 2  # the other 38 lie at the median
    assert rows[0].startswith("| step 17 | 1053 | 6.88 | sync.read 901.5 ms | 1 in 899 ms | prefetch_assemble 2 ms |")
    assert rows[1].startswith("| step 30 | 403 | 2.63 | log.track 254 ms | - | - | 41 involuntary switches, 3 major faults, cpu 13 ms |")
    assert "slowest iterations (median 153 ms)" in out


def test_null_registry_answers_everything_the_real_one_does():
    """`_NullTelemetry` parity: every public method of `Telemetry` exists on the stand-in
    (a caller never asks which one it got), and the new ones return what an absent loop has."""
    public = {name for name in vars(Telemetry) if not name.startswith("_") and callable(getattr(Telemetry, name))}
    assert public <= set(dir(_NullTelemetry)), public - set(dir(_NullTelemetry))
    null = get_telemetry()
    assert null.iteration_so_far() == {}
    null.begin_iterations()  # installs nothing
    assert not any(getattr(hook, "__self__", None) is null for hook in gc.callbacks)
    # a monitor over the stand-in flags a slow step without asking for more
    monitor = HealthMonitor(null)
    for step in range(1, 14):
        monitor.observe_step(step, step_seconds=0.01)
    (anomaly,) = monitor.observe_step(14, step_seconds=0.05)
    assert anomaly == {"signal": "step_time", "value": 0.05, "ratio": 5.0}


def test_span_outside_a_train_loop_costs_no_write(tmp_path):
    """Without `begin_iterations` a span only annotates: no record, no split — tools and
    the serving engine can cut their boundaries through the same primitive for free."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    before = len(_read_sink(sink))
    with telemetry.span("data_fetch"):
        pass
    with telemetry.span("eval", bucket="eval"):
        pass
    assert len(_read_sink(sink)) == before
    assert telemetry._split == {}
    telemetry.record_step(1, 0.1, 0.1)
    (record,) = [r for r in _read_sink(sink) if r["kind"] == "step"]
    assert set(record["t"]) == {"data", "compile"}  # no wall, no split outside a loop
    telemetry.close()
    with get_telemetry().span("data_fetch"):  # the null registry annotates too
        pass


def test_compiles_counter_names_the_step_of_a_recompile(tmp_path):
    """A run that recompiles mid-training says so: the `compiles` counter and a `compile`
    event with the seconds, the program and the step it fell in."""
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    jax.jit(lambda x: x - 1.0)(jnp.ones((11,))).block_until_ready()  # before the loop: not counted
    assert "compiles" not in telemetry.counters
    telemetry.begin_iterations()

    @jax.jit
    def scale_for_compile_test(x):
        return x * 3.0

    with telemetry.span("train_step", step=7):
        scale_for_compile_test(jnp.ones((3,))).block_until_ready()
    first = telemetry.counters["compiles"]
    assert first >= 1
    with telemetry.span("train_step", step=8):
        scale_for_compile_test(jnp.ones((3,))).block_until_ready()  # cached: no compile
    assert telemetry.counters["compiles"] == first
    with telemetry.span("train_step", step=9):
        scale_for_compile_test(jnp.ones((5,))).block_until_ready()  # a new shape: recompile
    assert telemetry.counters["compiles"] > first
    uninstall_telemetry()
    jax.jit(lambda x: x + 1.0)(jnp.ones((7,))).block_until_ready()  # nobody installed: not counted
    counted = telemetry.counters["compiles"]
    telemetry.close()

    events = [
        r for r in _read_sink(sink)
        if r["kind"] == "event" and r["event"] == "compile"
        and "scale_for_compile_test" in str(r["program"])
    ]
    assert [e["step"] for e in events] == [7, 9]
    assert all(e["seconds"] > 0 for e in events)
    assert _read_sink(sink)[-1]["counters"]["compiles"] == counted


def test_loss_tiling_event_is_written_once_a_trace(tmp_path):
    """The chunked loss says how it engaged: one `loss_tiling` event where the step is traced,
    not one a trace of the same shapes, and a new one when the shapes choose another plan —
    the summed rule's (`logits_products` 1: the gradients leave the differentiated forward,
    over a token block's kept logits) and the per-token rule's (2: its backward forms the
    logits again, token blocks x vocabulary tiles, the bytes its loops carry)."""
    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy

    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)

    def grads(batch, seq, vocab, weights=None):
        hidden = jnp.ones((batch, seq, 16), jnp.float32)
        table = jnp.ones((vocab, 16), jnp.float32)
        labels = jnp.zeros((batch, seq), jnp.int32)
        loss = lambda h, t: fused_linear_cross_entropy(  # noqa: E731
            h, t, labels, chunk_size=8, compute_dtype=jnp.float32, weights=weights
        )
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(hidden, table)

    grads(2, 64, 1999)
    grads(2, 64, 1999)  # traced again (another jit of the same step): nothing new to say
    grads(2, 64, 199)
    grads(2, 64, 1999, jnp.ones((2, 64)))
    grads(2, 64, 199, jnp.ones((2, 64)))
    uninstall_telemetry()
    get_telemetry().event_once("loss_tiling", token_blocks=1)  # the no-op registry has the method too
    telemetry.close()

    events = [r for r in _read_sink(sink) if r["kind"] == "event" and r["event"] == "loss_tiling"]
    assert [e["logits_products"] for e in events] == [1, 1, 2, 2]
    for kept, vocab in zip(events[:2], (1999, 199)):
        assert (kept["token_blocks"], kept["kept_logits_bytes"], kept["table_carry_bytes"]) == (1, 4 * 128 * vocab, 0)
        assert kept["accumulator_bytes_moved"] == 4 * 16 * (vocab + 128) and kept["tokens_per_device"] == 128
        assert not {"hidden_carry_bytes", "vocab_tiles", "tile_rows"} & set(kept)  # they went with the vocabulary scan
    one_block, two_blocks = events[2:]
    assert [(e["token_blocks"], e["vocab_tiles"], e["tile_rows"]) for e in events[2:]] == [(1, 8, 256), (2, 4, 50)]
    assert one_block["table_carry_bytes"] == 0 and one_block["hidden_carry_bytes"] == 4 * 16 * 128
    assert two_blocks["table_carry_bytes"] == 4 * 16 * 200 and two_blocks["tokens_per_device"] == 128
    assert "kept_logits_bytes" not in one_block
    assert all("step" not in e and e["vocab_shards"] == 1 for e in events)


def test_null_registry_is_safe_without_install():
    null = get_telemetry()
    null.count("anything", event=True, step=1)
    null.record_step(1, 0.1, 0.1)
    with null.span("loop.checkpoint", bucket="checkpoint"):
        pass
    assert null.emit_window(1) is None
    assert null.current_mfu() is None
    null.poll_profiler(1)
    null.close()


# --------------------------------------------------------------------------- fixed profiler schedule


def test_profiler_schedule_absolute_and_one_shot(monkeypatch):
    from contextlib import nullcontext as _nullcm

    traces = []
    monkeypatch.setattr(
        jax.profiler, "trace", lambda path: traces.append(path) or _nullcm()
    )

    # fresh run: steps 1..5 skipped, step 6 traced, then done
    for step in range(1, 6):
        with get_profiler_context("/tmp/trace", step):
            pass
    assert traces == []
    with get_profiler_context("/tmp/trace", 6):
        pass
    assert traces == ["/tmp/trace"]
    # one-shot: the window never re-captures, even if the step moves backwards
    with get_profiler_context("/tmp/trace", 6):
        pass
    assert traces == ["/tmp/trace"]

    # resumed run past the window: never captures
    reset_profiler_schedule()
    with get_profiler_context("/tmp/trace", 100):
        pass
    with get_profiler_context("/tmp/trace", 6):  # even a backwards step after the latch
        pass
    assert traces == ["/tmp/trace"]

    # no trace path -> never anything
    reset_profiler_schedule()
    with get_profiler_context(None, 6):
        pass
    assert traces == ["/tmp/trace"]


# --------------------------------------------------------------------------- smoke: real train loop


class _Model:
    def loss(self, params, batch, rngs=None, train=True, fp8_state=None):
        return jnp.mean(params["w"] * batch["x"])


class _Loader:
    def __init__(self, n=4):
        self.n = n

    def __iter__(self):
        for _ in range(self.n):
            yield {"x": np.ones((2, 4), np.float32)}

    def state_dict(self):
        return {}

    def load_state_dict(self, sd):
        pass


def _train_args(tmp_path, num_steps=6, **logging_kwargs):
    return TrainingArgs(
        model_args=dict(
            model_class="AutoModelForCausalLM",
            pretrained_config=dict(
                model_type="gpt_dolomite", vocab_size=8, n_positions=8, n_embd=4,
                n_layer=1, n_head=1,
            ),
        ),
        tuning_args=dict(tuning_method="full_finetuning"),
        training_parameters=dict(
            num_training_steps=num_steps,
            micro_batch_size=2,
            gradient_accumulation_steps=1,
            eval_during_training=False,
        ),
        datasets=[dict(class_name="DebugDataset", data_name="debug", class_args={})],
        save_args=dict(save_path=str(tmp_path / "ckpt"), save_interval=3),
        logging_args=dict(log_interval=2, **logging_kwargs),
        random_args=dict(seed=3),
    )


def _run_loop(args):
    params = {"w": jnp.ones((4,), jnp.float32), "b": jnp.zeros((2,), jnp.float32)}
    optimizer = optax.adam(1e-2)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params)
    )
    finetune.train(
        args, _Model(), state, optimizer, lambda step: 1e-2, _Loader(), None,
        experiments_tracker=None,
    )


def test_smoke_train_loop_sink_valid_and_monotone(tmp_path):
    """CI guard for the sink format: one tiny real train loop with default telemetry, then
    every line must parse as JSON and step records must be strictly monotone."""
    _run_loop(_train_args(tmp_path))

    sink = tmp_path / "ckpt" / "telemetry" / "rank-00000.jsonl"
    assert sink.is_file()
    records = _read_sink(sink)  # json.loads raises on any torn/partial line

    kinds = {r["kind"] for r in records}
    assert {"run_start", "step", "window", "run_end"} <= kinds

    steps = [r["step"] for r in records if r["kind"] == "step"]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)  # strictly monotone
    assert steps == list(range(1, 7))

    windows = [r for r in records if r["kind"] == "window"]
    assert [w["step"] for w in windows] == [2, 4, 6]
    for window in windows:
        assert set(window["goodput"]) == {
            "compile", "data", "step", "checkpoint", "eval", "other", "goodput_pct"
        }
    # the save at step 3 lands in the step-4 window; the save at step 6 in its own
    assert windows[1]["goodput"]["checkpoint"] > 0.0
    assert windows[1]["counters"]["checkpoints_saved"] == 1
    assert windows[2]["counters"]["checkpoints_saved"] == 2
    # registry is uninstalled after the loop
    assert get_telemetry().__class__.__name__ == "_NullTelemetry"


def test_smoke_summary_tool_renders(tmp_path, capsys):
    _run_loop(_train_args(tmp_path))
    tool = _load_summary_tool()
    assert tool.main([str(tmp_path / "ckpt")]) == 0
    out = capsys.readouterr().out
    assert "goodput" in out and "checkpoints_saved" in out
    assert "train step (steady)" in out


def test_on_demand_capture_in_real_loop(tmp_path, _fake_profiler):
    """Touch-file trigger wired through args -> build_telemetry -> the real finetune loop."""
    trigger = tmp_path / "ckpt" / "telemetry" / "PROFILE_TRIGGER"
    trigger.parent.mkdir(parents=True)
    trigger.touch()
    args = _train_args(
        tmp_path,
        telemetry=dict(on_demand_profiling=True, profile_steps=2, profile_on_sigusr1=False),
    )
    _run_loop(args)

    assert [c[0] for c in _fake_profiler] == ["start", "stop"]
    assert not trigger.exists()
    records = _read_sink(tmp_path / "ckpt" / "telemetry" / "rank-00000.jsonl")
    events = [r["event"] for r in records if r["kind"] == "event"]
    assert "profile_start" in events and "profiles_captured" in events


def test_build_telemetry_derives_paths(tmp_path):
    args = _train_args(tmp_path, telemetry=dict(on_demand_profiling=True))
    telemetry = build_telemetry(args, model_tflops_per_step=1.0, devices_per_group=2)
    assert telemetry.sink_path == str(
        tmp_path / "ckpt" / "telemetry" / f"rank-{jax.process_index():05d}.jsonl"
    )
    assert telemetry.profiler is not None
    assert telemetry.profiler.trigger_path == str(
        tmp_path / "ckpt" / "telemetry" / "PROFILE_TRIGGER"
    )
    assert telemetry.profiler.output_path == str(tmp_path / "ckpt" / "telemetry" / "traces")
    assert telemetry.devices_per_group == 2
    telemetry.close()


def test_telemetry_args_validation():
    with pytest.raises(Exception):
        _train_args_bad = TrainingArgs(
            model_args=dict(
                model_class="AutoModelForCausalLM",
                pretrained_config=dict(
                    model_type="gpt_dolomite", vocab_size=8, n_positions=8, n_embd=4,
                    n_layer=1, n_head=1,
                ),
            ),
            tuning_args=dict(tuning_method="full_finetuning"),
            training_parameters=dict(num_training_steps=5, micro_batch_size=2),
            datasets=[dict(class_name="DebugDataset", data_name="debug", class_args={})],
            save_args=dict(save_path="/tmp/x", save_interval=1),
            logging_args=dict(telemetry=dict(profile_steps=0)),
        )
