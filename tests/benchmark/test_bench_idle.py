"""The two readers of the device's idle time (PR 35: ``idle_in_step_ms.train``,
``idle_between_programs_ms.train``, over ``benchmark/idle_trace.py``) on the trace recorded on
a v5e (``small.xplane.pb.gz``: four executions of ``jit_train_like`` with 10 ms sleeps
between) and on hand-built planes: a gap inside the step's program, a gap that spans two host
spans and is shared by overlap, and the identity that holds the two to the window's idle
share. Also the tool that runs named readers on a cell's traced run (``tools/read_layers``)."""

import os
from types import SimpleNamespace

import pytest

from benchmark import idle_trace
from benchmark import reduce_trace as rt
from benchmark.harness import RunResult
from benchmark.spec import Spec
from benchmark.xplane import Event

TRACE = os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz")
PROGRAM = "4717001606996803062"
READERS = ["idle_in_step_ms.train", "idle_between_programs_ms.train"]
FWD = "jit(train_step)/jvp(Lfm2MoeForCausalLM)/transformer/blocks"


def reader(name: str):
    return Spec.load().layer_metric(name)


def op(name, start_us, duration_us, tf_op="", category="fusion", program=PROGRAM):
    stats = {"program_id": program, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


def span(name, start_us, end_us):
    return Event(name, start_us * 1e3, (end_us - start_us) * 1e3, {})


def built_result(steps: int = 2, off_loop=()) -> RunResult:
    """Steps of 2000 us. A step's program runs 0-1000: the router (0-300), then 100 us of
    nothing before a `while` of the experts' dispatch opens (400-700, its body's gather
    405-690 and 5 us of its own at either end), 5 us (under the floor) before the combine
    (705-1000). The loop's rng split runs 1500-1505. Between programs the device waits
    1000-1500 and 1505-2000. On the host `loop.sync` 900-1200 holds `sync.step` 900-1010 and
    `sync.read` 1010-1190; `loop.log` 1200-1400 holds `log.track` 1250-1400; `loop.rng`
    1450-1520; `train_step` (the dispatch) 1520-1990. The worker's `prefetch_assemble` runs
    1300-1480 and one garbage collection 1100-1130."""
    ops, modules, host = [], [], []
    for step in range(steps):
        t = step * 2000
        ops += [
            op("%fusion.1", t, 300, f"{FWD}/moe/moe_router/dot_general:"),
            op("%while.7", t + 400, 300, category="while"),
            op("%fusion.2", t + 405, 285, f"{FWD}/moe/moe_dispatch/while/body/jit(_take)/gather:"),
            op("%fusion.3", t + 705, 295, f"jit(train_step)/transpose(jvp(Lfm2MoeForCausalLM))/transformer/blocks/moe/moe_combine/scatter-add:"),
            op("%fusion.x", t + 1500, 5, "jit(_threefry_split)/threefry2x32:", program="77"),
        ]
        modules += [
            Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}),
            Event("jit__threefry_split(77)", (t + 1500) * 1e3, 5e3, {}),
        ]
        host += [
            span("loop.sync", t + 900, t + 1200), span("sync.step", t + 900, t + 1010), span("sync.read", t + 1010, t + 1190),
            span("gc.collect", t + 1100, t + 1130),
            span("loop.log", t + 1200, t + 1400), span("log.track", t + 1250, t + 1400),
            span("prefetch_assemble", t + 1300, t + 1480),
            span("loop.rng", t + 1450, t + 1520), span("train_step", t + 1520, t + 1990),
            span("PjitFunction(train_step)", t + 1525, t + 1985),  # the runtime's own: no annotation
        ]
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], host, window_s=steps * 2000e-6)
    telemetry = [
        {"kind": "step", "step": 7 + i, "t": {"data": 1e-5, "step": 0.002, "off_loop": {name: 1e-4 for name in off_loop}}}
        for i in range(steps)
    ]
    return RunResult(attempted=steps, failed=0, end_to_end={}, checks=[], trace=trace, telemetry=telemetry,
                     facts=dict(traced_steps=steps, first_measured_step=7, last_measured_step=6 + steps))


# ---- the recorded trace

def test_identity_on_the_recorded_trace():
    """Inside the program + outside it + the gaps under 20 us + the window's edges are the
    window's idle share; on this trace all of it lies outside (the host sleeps)."""
    trace = rt.reduce_trace(TRACE)
    in_ms, table = reader(READERS[0]).in_step(trace, "train_like")
    out_ms, by_span, by_programs, overlapped, same = reader(READERS[1]).between_programs(trace, "train_like")
    assert same is table and table.steps == 4 and overlapped == {}
    whole_ms = 1e3 * trace.window_s * trace.idle_share
    assert (in_ms + out_ms) * table.steps == pytest.approx(whole_ms - table.edges_ns / 1e6, rel=0.01)
    assert table.inside_ns + table.outside_ns + table.small_ns + table.edges_ns == pytest.approx(table.idle_ns, rel=1e-9)
    assert in_ms == pytest.approx(0.0, abs=1e-3) and out_ms == pytest.approx(12.43, rel=0.01)
    # the sleeps hold four fifths of it, and each gap is cut at the annotations' ends, not charged to one
    assert by_span["bench.sleep"] / table.outside_ns == pytest.approx(0.807, abs=0.01)
    assert set(by_span) == {"bench.sleep", "train_step", "bench.engine_step", "(no host span)"}
    assert sum(by_span.values()) == pytest.approx(table.outside_ns)
    assert set(by_programs) == {"jit_train_like -> jit_decode_impl", "jit_decode_impl -> jit_train_like"}
    # a given window longer than the events' extent: the rest is the edges
    longer = idle_trace.gap_table(rt.reduce_trace(TRACE, window_s=0.1), "train_like")
    assert longer.edges_ns == pytest.approx(0.1e9 - 1e9 * trace.window_s, rel=1e-6)
    assert longer.inside_ns + longer.outside_ns + longer.small_ns + longer.edges_ns == pytest.approx(longer.idle_ns, rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_the_step_program(name):
    """The recorded trace has no `jit_train_step`, an untraced run no trace: None, no raise."""
    recorded = RunResult(attempted=1, failed=0, end_to_end={}, checks=[], trace=rt.reduce_trace(TRACE),
                         facts=dict(traced_steps=4, first_measured_step=1, last_measured_step=4))
    assert reader(name).read(recorded, None) is None
    assert reader(name).read(RunResult(attempted=1, failed=0, end_to_end={}, checks=[]), None) is None


# ---- hand-built planes

def test_a_gap_inside_the_program_is_the_programs(capsys):
    result = built_result()
    assert reader("idle_in_step_ms.train").read(result, None) == pytest.approx(0.100)
    out = capsys.readouterr().out
    # by the scopes the program named: the router's product before, the dispatch's loop after
    assert "moe/moe_router -> enters while: moe_dispatch/_take 0.100 (2)" in out
    assert "1.0 gaps a step inside the program" in out
    # the loop's event covers 300 us and its body 285: 15 us a step of its own, which the busy union counts busy
    assert "self time of the program's loops and conds (counted busy), ms a step: 0.015 in 1 places" in out
    assert "while: moe_dispatch/_take 0.015" in out
    assert idle_trace.container_self_times(result.trace) == {"while: moe_dispatch/_take": pytest.approx(2 * 15e-6)}
    # the 5 us before the combine lie under the floor: in neither reader, and said
    table = idle_trace.gap_table(result.trace)
    assert table.small_count == 2 and table.small_ns == pytest.approx(2 * 5e3)
    assert "2 gaps under 20 us 0.010" in out


def test_a_gap_between_programs_is_shared_by_overlap(capsys):
    """1000-1500 lies under `sync.step` (10), the collection (30), `sync.read` (150 of its 180),
    `loop.sync` itself (10), `loop.log` itself (50), `log.track` (150), `loop.rng` (50) and 50
    that no span covers; 1505-2000 under `loop.rng` (15) and the dispatch (470), 10 uncovered."""
    result = built_result(off_loop=["prefetch_assemble"])
    value = reader("idle_between_programs_ms.train").read(result, None)
    # the last step's 1505-2000 is the window's edge: 500 + 495 us of one step, 500 of the other
    assert value == pytest.approx((500 + 495 + 500) / 2 / 1e3)
    module = reader("idle_between_programs_ms.train")
    _, by_span, by_programs, overlapped, table = module.between_programs(result.trace, off_loop=frozenset(["prefetch_assemble"]))
    us = {name: ns / 1e3 for name, ns in by_span.items()}
    assert us == pytest.approx({
        "sync.step": 20, "gc.collect": 60, "sync.read": 300, "loop.sync": 20, "loop.log": 100, "log.track": 300,
        "loop.rng": 100 + 15, "train_step": 470, "(no host span)": 100 + 10,
    })
    assert sum(by_span.values()) == pytest.approx(table.outside_ns)
    # the worker's span ran beside 180 us of each first gap and claims none of it
    assert overlapped == {"prefetch_assemble": pytest.approx(2 * 180e3)}
    assert {k: v / 1e3 for k, v in by_programs.items()} == pytest.approx(
        {"jit_train_step -> jit__threefry_split": 1000, "jit__threefry_split -> jit_train_step": 495}
    )
    out = capsys.readouterr().out
    assert "sync.read 0.150" in out and "log.track 0.150" in out and "prefetch_assemble 0.180" in out
    # where `breakdown.idle_gaps` charges the whole of the first gap to the span over its middle
    assert dict(result.trace.idle_gaps())["log.track"] == pytest.approx(2 * 500e-6)


def test_a_span_of_another_thread_claims_unless_the_program_says_whose_it_is():
    """The parent writes no `t.off_loop`: its worker's spans are taken for the loop's — here
    the 50 us of 1400-1450 that no shorter span covers, which were no span's — the total does
    not move, and nothing is raised."""
    result = built_result()
    module = reader("idle_between_programs_ms.train")
    value, by_span, _, overlapped, table = module.between_programs(result.trace)
    assert overlapped == {} and by_span["prefetch_assemble"] == pytest.approx(2 * 50e3)
    assert by_span["(no host span)"] == pytest.approx(10e3)
    assert sum(by_span.values()) == pytest.approx(table.outside_ns)
    assert value == module.read(result, None)


def test_the_two_readers_and_the_edges_are_the_idle_share():
    result = built_result(steps=3)
    trace = result.trace
    in_ms = reader(READERS[0]).read(result, None)
    out_ms = reader(READERS[1]).read(result, None)
    table = idle_trace.gap_table(trace)
    assert table.steps == 3 and table.edges_ns == pytest.approx(495e3)  # after the last rng split
    idle_ms = 1e3 * trace.window_s * trace.idle_share
    assert (in_ms + out_ms) * 3 + table.small_ns / 1e6 == pytest.approx(idle_ms - table.edges_ns / 1e6)
    assert idle_ms == pytest.approx(3 * (100 + 5 + 500 + 495) / 1e3)


@pytest.mark.parametrize(
    "start, end, inside, outside",
    [
        (10, 20, 10, []),  # inside one execution
        (120, 180, 0, [(120, 180)]),  # between two
        (90, 210, 10 + 10, [(100, 200)]),  # from inside one to inside the next
        (50, 450, 50 + 100 + 50, [(100, 200), (300, 400)]),  # over a whole execution
        (400, 600, 100, [(500, 600)]),  # past the last
        (-50, 0, 0, [(-50, 0)]),  # before the first, touching it
    ],
)
def test_a_gap_is_cut_at_the_executions_ends(start, end, inside, outside):
    executions = [(0, 100), (200, 300), (400, 500)]
    assert idle_trace.cut(start, end, executions) == (inside, outside)


@pytest.mark.parametrize(
    "tf_op, name, label",
    [
        (f"{FWD}/moe/moe_dispatch/while/body/jit(_take)/gather:", "%fusion.2", "moe_dispatch/_take"),
        ("jit(train_step)/transpose(jvp(M))/head_loss/loss_chunks/while/body/closed_call/transpose(jvp(ce_chunk))/dot_general:", "%f", "loss_chunks/ce_chunk bwd"),
        ("jit(train_step)/optimizer/cond/branch_1_fun/add:", "%f", "optimizer"),
        ("jit(train_step)/add:", "%f", "add"),
        ("", "%copy-done.12 = bf16[8]{0} copy-done(%copy-start.12)", "copy-done"),
    ],
)
def test_scope_label(tf_op, name, label):
    assert idle_trace.scope_label(op(name, 0, 1, tf_op)) == label


@pytest.mark.parametrize(
    "spans, shares",
    [
        ([], {"(no host span)": 100}),
        ([("a", 0, 100)], {"a": 100}),
        ([("outer", -50, 150), ("inner", 20, 60)], {"inner": 40, "outer": 60}),  # the parent gets what is left
        ([("a", 0, 30), ("b", 30, 90)], {"a": 30, "b": 60, "(no host span)": 10}),  # by overlap, not by the middle
        ([("a", 0, 60), ("b", 40, 140)], {"a": 60, "b": 40}),  # crossing: the shorter first
    ],
)
def test_share_by_overlap(spans, shares):
    got = reader("idle_between_programs_ms.train").share_by_overlap(0, 100, [Event(n, a, b - a, {}) for n, a, b in spans])
    assert got == pytest.approx(shares)


# ---- the tool

def test_read_layers_runs_named_readers_on_a_drivers_result(tmp_path, monkeypatch, capsys):
    from benchmark.tools import read_layers

    result = built_result()
    spec = read_layers.ReadingSpec.load()
    spec.readers = tuple(READERS)
    monkeypatch.setattr(Spec, "driver", lambda self, traffic: SimpleNamespace(run=lambda ctx: result))
    wrapped = spec.driver({"driver": "train_packed"})
    assert wrapped.run(SimpleNamespace(out_dir=str(tmp_path), cell=SimpleNamespace(name="a-cell"))) is result
    assert spec.read == {READERS[0]: pytest.approx(0.100), READERS[1]: pytest.approx(0.7475)}
    out = capsys.readouterr().out
    assert "READ idle_in_step_ms.train = 0.1" in out and "READ idle_between_programs_ms.train = 0.7475" in out
