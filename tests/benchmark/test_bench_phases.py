"""The phase reduction (``benchmark/phases.py``) and the seven readers of PR 24: self time on
a nested trace line, the rule that puts an operation into a phase (on framework names and HLO
texts recorded on a v5e from the 3B cell, my chip run, PR 24), each reader on a hand-built
``RunResult``, and each finding nothing to read on the trace recorded before the program
named its phases."""

import os

import pytest

from benchmark import phases
from benchmark import reduce_trace as rt
from benchmark.harness import RunResult
from benchmark.spec import Spec
from benchmark.xplane import Event

TRACE = os.path.join(os.path.dirname(rt.__file__), "testdata", "small.xplane.pb.gz")
PROGRAM = "4717001606996803062"
READERS = [
    "blocks_fwd_ms.train", "blocks_bwd_ms.train", "head_loss_ms.train", "optimizer_ms.train",
    "unattributed_device_share.train", "host_between_steps_ms.train", "device_programs_per_step.train",
]
FWD = "jit(train_step)/jvp(GPTDolomiteForCausalLM)"
BWD = "jit(train_step)/transpose(jvp(GPTDolomiteForCausalLM))"


def op(name, start_us, duration_us, tf_op="", category="fusion"):
    stats = {"program_id": PROGRAM, "hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return Event(name, start_us * 1e3, duration_us * 1e3, stats)


# ---- self time

def test_self_time_on_a_nested_line():
    events = [
        op("%while.1", 0, 100, category="while"),
        op("%fusion.a", 0, 40),  # starts with its parent
        op("%fusion.b", 40, 50),  # touches its sibling; the while's own 10 us follow
        op("%cond.1", 100, 30, category="conditional"),  # touches the while: a sibling
        op("%fusion.c", 105, 20),
        op("%fusion.d", 200, 7),
    ]
    selfs = {e.name: ns / 1e3 for e, ns in phases.self_times(list(reversed(events)))}
    assert selfs == pytest.approx(
        {"%while.1": 10, "%fusion.a": 40, "%fusion.b": 50, "%cond.1": 10, "%fusion.c": 20, "%fusion.d": 7}
    )
    assert sum(selfs.values()) == pytest.approx(137)  # the line's busy time, nothing twice


def test_self_time_two_levels_down():
    events = [op("%while.o", 0, 100), op("%while.i", 10, 60), op("%f", 20, 30), op("%g", 75, 5)]
    selfs = {e.name: ns / 1e3 for e, ns in phases.self_times(events)}
    assert selfs == pytest.approx({"%while.o": 35, "%while.i": 30, "%f": 30, "%g": 5})


# ---- the rule

@pytest.mark.parametrize(
    "tf_op, expected",
    [
        (f"{FWD}/transformer/blocks/while/body/closed_call/h_scan/b0/mlp/c_fc/dot_general:", ("blocks", "fwd")),
        (f"{BWD}/transformer/blocks/while/body/closed_call/checkpoint/h_scan/b1/attn/c_attn/dot_general:", ("blocks", "bwd")),
        (f"{BWD}/transformer/blocks/while/body/closed_call/checkpoint/h_scan/b0/ln_1/pallas_rmsnorm/reduce_sum:", ("blocks", "bwd")),
        (f"{FWD}/transformer/blocks/while/body/closed_call/h_scan/b0/attn/vmap(jit(_splash_attention))/splash_mha_fwd_segmented_residuals/pallas_call:", ("blocks", "fwd")),
        (f"{FWD}/head_loss/loss_chunks/while/body/closed_call/ce_chunk/dot_general:", ("head_loss", "fwd")),
        (f"{BWD}/head_loss/loss_chunks/while/body/closed_call/jvp(ce_chunk)/dot_general:", ("head_loss", "bwd")),
        (f"{BWD}/head_loss/loss_chunks/while/body/closed_call/transpose(jvp(ce_chunk))/dot_general:", ("head_loss", "bwd")),
        (f"{FWD}/transformer/embed/wte/convert_element_type:", ("embed", "fwd")),
        (f"{BWD}/transformer/embed/wte/scatter-add:", ("embed", "bwd")),
        (f"{BWD}/transformer/final_norm/ln_f/pallas_rmsnorm/convert_element_type:", ("final_norm", "bwd")),
        ("jit(train_step)/grad_clip/reduce_sum:", ("grad_clip", "fwd")),
        ("jit(train_step)/optimizer/cond/branch_1_fun/add:", ("optimizer", "fwd")),
        # the innermost phase scope wins: the blocks inside an accumulated micro-batch
        (f"jit(train_step)/accumulate/while/body/closed_call/transpose(jvp(GPTDolomiteForCausalLM))/transformer/blocks/while/body/closed_call/checkpoint/h_scan/b0/mlp/c_proj/dot_general:", ("blocks", "bwd")),
        ("jit(train_step)/accumulate/while/body/closed_call/add:", ("accumulate", "fwd")),
        ("jit(train_step)/jvp(head_loss)/mul:", ("head_loss", "fwd")),  # a scope first under a transform
        # the names of the program before PR 24, and of other programs: no phase
        ("jit(train_step)/transpose(jvp(GPTDolomiteForCausalLM))/while/body/closed_call/transpose(jvp())/dot_general:", None),
        ("jit(train_step)/jvp(GPTDolomiteForCausalLM)/transformer/while/body/closed_call/h_scan/b0/mlp/c_fc/dot_general:", None),
        ("jit(train_step)/cond/branch_1_fun/add:", None),
        ("jit(train_like)/pallas_rmsnorm/reduce_sum:", None),
        ("", None),
    ],
)
def test_phase_rule_on_recorded_names(tf_op, expected):
    assert phases.phase_of(tf_op) == expected


def test_phase_rule_reads_the_state_leaf_of_an_unnamed_operation():
    cast = (
        "%convert.252 = bf16[2,2560,20480]{2,1,0:T(8,128)(2,1)} convert(f32[2,2560,20480]{2,1,0:T(8,128)} "
        "%state_params__transformer____h_scan____b0____mlp____c_fc____kernel__.1)"
    )
    assert phases.phase_of("", cast) == ("blocks", "fwd")
    assert phases.phase_of("", cast.replace("h_scan____b0", "h_7")) == ("blocks", "fwd")
    assert phases.phase_of("", cast.replace("h_scan____b0____mlp____c_fc____kernel", "wte____embedding")) == ("embed", "fwd")
    assert phases.phase_of("", cast.replace("h_scan____b0____mlp____c_fc____kernel", "ln_f____weight")) == ("final_norm", "fwd")
    # a zero fill names nothing: it stays unattributed; a framework name, where there is one, decides
    assert phases.phase_of("", "%broadcast.96 = f32[2,2560,2560]{2,1,0:T(8,128)} broadcast(f32[]{:T(128)} %constant.259), dimensions={}") is None
    assert phases.phase_of("jit(train_step)/cond/branch_1_fun/add:", cast) is None


# ---- the readers on a hand-built result

def built_result(named: bool = True, split: bool = True) -> RunResult:
    """Two traced steps of 1000 us each. A step: an unnamed cast of a block weight (20), the
    embedding (10), the forward scan (a while of 200 over 150 + 40), the loss forward scan
    (60 over 55), the loss backward scan (150 over a replay of 30 and 110), the backward
    scan (400 over 390), the embedding's backward (15), a zero fill (10), clipping (25), the
    update's cond (100 over 95). A program of the loop's own follows each step."""
    ops, modules = [], []
    for step in range(2):
        t = step * 2000
        scope = (lambda s: s) if named else (lambda s: "")
        blocks = "/transformer/blocks" if named else "/transformer"
        head = "/head_loss/loss_chunks" if named else ""
        chunk = ("ce_chunk", "jvp(ce_chunk)", "transpose(jvp(ce_chunk))") if named else ("", "jvp()", "transpose(jvp())")
        ops += [
            op("%convert.1 = bf16[2,8,8] convert(f32[2,8,8] %state_params__transformer____h_scan____b0____mlp____c_fc____kernel__.1)", t, 20, category="non-fusion elementwise"),
            op("%fusion.e", t + 20, 10, f"{FWD}/transformer{scope('/embed')}/wte/gather:"),
            op("%while.22", t + 30, 200, category="while"),
            op("%fusion.1", t + 30, 150, f"{FWD}{blocks}/while/body/closed_call/h_scan/b0/mlp/c_fc/dot_general:"),
            op("%fusion.2", t + 180, 40, f"{FWD}{blocks}/while/body/closed_call/h_scan/b0/attn/splash_mha_fwd/pallas_call:"),
            op("%while.19", t + 230, 60, category="while"),
            op("%fusion.3", t + 232, 55, f"{FWD}{head}/while/body/closed_call/{chunk[0]}/dot_general:"),
            op("%while.21", t + 290, 150, category="while"),
            op("%fusion.4", t + 290, 30, f"{BWD}{head}/while/body/closed_call/{chunk[1]}/dot_general:"),
            op("%fusion.5", t + 320, 110, f"{BWD}{head}/while/body/closed_call/{chunk[2]}/dot_general:"),
            op("%while.20", t + 440, 400, category="while"),
            op("%fusion.6", t + 445, 390, f"{BWD}{blocks}/while/body/closed_call/checkpoint/h_scan/b0/mlp/c_fc/dot_general:"),
            op("%fusion.f", t + 840, 15, f"{BWD}/transformer{scope('/embed')}/wte/scatter-add:"),
            op("%broadcast.9 = f32[2,8,8] broadcast(f32[] %constant.1)", t + 855, 10, category="broadcast"),
            op("%fusion.7", t + 865, 25, f"jit(train_step){scope('/grad_clip')}/reduce_sum:"),
            op("%cond.49", t + 890, 100, category="conditional"),
            op("%fusion.8", t + 892, 95, f"jit(train_step){scope('/optimizer')}/cond/branch_1_fun/add:"),
        ]
        modules.append(Event(f"jit_train_step({PROGRAM})", t * 1e3, 1000e3, {}))
        other = Event("jit__threefry_split(77)", (t + 1500) * 1e3, 5e3, {})
        modules.append(other)
        ops.append(Event("%fusion.x", other.start_ns, 5e3, {"program_id": "77", "tf_op": "jit(_threefry_split)/threefry2x32:"}))
    trace = rt.TraceSummary([rt.DeviceTrace("/device:TPU:0", modules, ops)], [], window_s=4e-3)
    telemetry = []
    for step in range(6, 11):  # steps 7..10 are the window; 6 is the warm-up's last
        t = {"data": 1e-5, "step": 0.18}
        if split:
            t["split"] = {
                "loop.record": 0.0001, "loop.window": 0.0002, "loop.data_wait": 0.0003, "loop.rng": 0.0004,
                "train_step": 0.0010, "loop.sync": 0.1700, "loop.account": 0.0005, "loop.log": 0.0030 + 0.001 * (step == 9),
                "loop.checkpoint": 0.0006, "loop.poll": 0.0001,
            }
            t["wall"] = sum(t["split"].values()) + 0.00005
        telemetry.append({"kind": "step", "step": step, "t": t})
    facts = dict(traced_steps=2, first_measured_step=7, last_measured_step=10)
    return RunResult(attempted=4, failed=0, end_to_end={}, checks=[], trace=trace, telemetry=telemetry, facts=facts)


def read(name: str, result: RunResult):
    return Spec.load().layer_metric(name).read(result, None)


def test_readers_on_a_built_result(capsys):
    result = built_result()
    assert read("blocks_fwd_ms.train", result) == pytest.approx(0.210)  # 150 + 40 + the hoisted cast's 20
    assert read("blocks_bwd_ms.train", result) == pytest.approx(0.390)
    assert read("head_loss_ms.train", result) == pytest.approx(0.220)  # 55 + 30 + 110 + the embedding's 10 + 15
    assert "head_loss.bwd_replay 0.030" in capsys.readouterr().out
    assert read("optimizer_ms.train", result) == pytest.approx(0.120)
    # the zero fill's 10 us and the containers' own 40 us (a `while` event carries no framework
    # name) of a step's 990 us busy: the parts sum to the busy time
    assert read("unattributed_device_share.train", result) == pytest.approx(100 * 50 / 990)
    out = capsys.readouterr().out
    assert "sum 0.990" in out and "broadcast 0.010" in out and "while 0.035" in out
    table = phases.train_step_phases(result.trace)
    assert table["total_s"] == pytest.approx(2 * 990e-6) and table["steps"] == 2
    assert read("device_programs_per_step.train", result) == 2.0
    # after a step's sync 0.0042 s (0.0052 after step 9's), before the next dispatch returns 0.0020 s
    assert read("host_between_steps_ms.train", result) == pytest.approx(6.2)
    out = capsys.readouterr().out
    assert "slowest iteration: step 9" in out and "loop.log 4.000" in out and "0.050 ms" in out


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_phase_names(name):
    """The parent of PR 24: the same operations under the names they had (the layer scan a
    bare `while`, the loss's matmuls `transpose(jvp())`), step records without a split."""
    result = built_result(named=False, split=False)
    if name == "device_programs_per_step.train":
        assert read(name, result) == 2.0  # the programs of a step are there to count on any tree
    else:
        assert read(name, result) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_on_the_recorded_small_trace(name):
    """``small.xplane.pb.gz`` (PR 23) has no phase names, no `jit_train_step` and no step
    records: nothing to read, and nothing raised."""
    recorded = RunResult(
        attempted=1, failed=0, end_to_end={}, checks=[], trace=rt.reduce_trace(TRACE),
        facts=dict(traced_steps=4, first_measured_step=1, last_measured_step=4),
    )
    assert read(name, recorded) is None
    assert read(name, RunResult(attempted=1, failed=0, end_to_end={}, checks=[])) is None  # an untraced run


def test_new_entries_name_their_layers_as_the_accepted_ones_do():
    data = Spec.load().data
    layers = {m["layer"] for m in data["per_layer"][:5]}
    for metric in data["per_layer"]:
        if metric["name"] in READERS:
            assert metric["layer"] in layers and metric["moves"] == "train_tokens_per_s_per_chip"
            assert metric["workloads"] == ["train-3b-packed4k", "train-8b-packed4k"]
    assert [m["name"] for m in data["per_layer"][-7:]] == READERS
