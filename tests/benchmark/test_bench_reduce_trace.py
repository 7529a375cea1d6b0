"""The trace reduction on a small trace recorded on a TPU v5e (PR 23, chip run): four
executions each of two jitted programs (`train_like` with a `pallas_rmsnorm` and an `mlp`
scope, and `decode_impl`), with host annotations `train_step`, `bench.sleep` (10 ms) and
`bench.engine_step` around them."""

import os

import pytest

from benchmark import reduce_trace as rt
from benchmark.xplane import read_xplane

TRACE = os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary():
    return rt.reduce_trace(TRACE)


def test_planes_and_lines_of_the_recorded_trace():
    planes = {p.name: p for p in read_xplane(TRACE)}
    device = {line.name: line for line in planes["/device:TPU:0"].lines}
    assert len(device["XLA Modules"].events) == 8
    assert len(device["XLA Ops"].events) == 40
    scoped = [e for e in device["XLA Ops"].events if "/pallas_rmsnorm/" in e.stats.get("tf_op", "")]
    assert len(scoped) == 8  # reduce_sum and rsqrt, four executions
    host = {line.name for line in planes["/host:CPU"].lines}
    assert "python" in host


@pytest.mark.parametrize(
    "intervals, seconds",
    [
        ([], 0.0),
        ([(0, 1e9)], 1.0),
        ([(0, 1e9), (5e8, 2e9)], 2.0),  # overlap counts once
        ([(0, 1e9), (3e9, 4e9)], 2.0),  # a gap is not busy
        ([(3e9, 4e9), (0, 1e9), (0, 5e8)], 2.0),  # order and nesting do not matter
    ],
)
def test_union_seconds(intervals, seconds):
    assert rt.union_seconds(intervals) == pytest.approx(seconds)


def test_busy_union_and_idle_share(summary):
    # four train_like executions of ~126 us and four decode_impl of ~103 us: 0.918 ms busy
    assert summary.busy_s == pytest.approx(917.8e-6, rel=0.01)
    assert summary.window_s == pytest.approx(50.6e-3, rel=0.01)
    assert summary.idle_share == pytest.approx(1 - 917.8e-6 / 50.6e-3, rel=1e-3)
    # given the host's window, the share follows it
    longer = rt.reduce_trace(TRACE, window_s=0.1)
    assert longer.idle_share == pytest.approx(1 - longer.busy_s / 0.1)


def test_programs_and_their_gaps(summary):
    assert summary.program_names() == {"jit_train_like": 4, "jit_decode_impl": 4}
    durations = summary.program_durations("train_like")
    assert len(durations) == 4 and all(d == pytest.approx(126.6e-6, rel=0.01) for d in durations)
    by_program = summary.program_durations_by_program("decode")
    assert len(by_program) == 1 and len(next(iter(by_program.values()))) == 4
    gaps = summary.program_gaps()
    assert len(gaps) == 7
    # after a train_like the host sleeps 10 ms; after a decode_impl it launches at once
    assert sorted(g > 5e-3 for g in gaps) == [False] * 3 + [True] * 4


def test_kernel_sums_by_scope(summary):
    rmsnorm = summary.scope_seconds("pallas_rmsnorm")
    assert rmsnorm == pytest.approx(48.8e-6, rel=0.01)  # 4 x (12.2 us reduce + 0.02 us rsqrt)
    assert summary.scope_seconds("mlp") == pytest.approx(370.2e-6, rel=0.01)
    assert summary.scope_seconds("splash_mha") == 0.0


def test_breakdown_names_ops_and_attributes_gaps(summary):
    breakdown = summary.breakdown()
    ops = dict(breakdown["device_ops"])
    assert len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 10
    assert ops["mlp/dot_general"] == pytest.approx(370.2e-6, rel=0.01)
    gaps = dict(breakdown["idle_gaps"])
    # nearly all of the chip's idle time lies under the host's 10 ms sleeps
    assert gaps["bench.sleep"] > 0.9 * sum(gaps.values())


def test_a_trace_without_device_operations_is_an_error():
    planes = [p for p in read_xplane(TRACE) if not p.name.startswith("/device:TPU")]
    with pytest.raises(ValueError, match="no device plane"):
        rt.summarize(planes)
