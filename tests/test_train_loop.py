"""The one training loop (`train_loop.run_loop`) as each entry point really drives it: a
short run of `pretrain.main` and of `finetune.main` through the telemetry sink. The synthetic
loop of `test_telemetry.py::test_step_record_split_sums_to_wall_time` holds the span primitive
to its contract; this holds the loop both entries share to it."""

import json

import pytest

from . import test_e2e_finetune, test_e2e_pretrain

# the loop thread's outermost spans, in the order an iteration enters them (`loop.record` and
# `loop.window` are the tail of the iteration before: the step record's own write, and the
# window record after a logging step)
SPAN_ORDER = [
    "loop.record",
    "loop.window",
    "loop.data_wait",
    "loop.rng",
    "train_step",
    "loop.sync",
    "loop.account",
    "loop.log",
    "loop.eval",
    "loop.checkpoint",
    "loop.poll",
]
EVERY_STEP = {"loop.data_wait", "loop.rng", "train_step", "loop.sync", "loop.account", "loop.log", "loop.poll"}


def _run_pretrain(tmp_path):
    from dolomite_engine_tpu import pretrain

    prefix = test_e2e_pretrain._write_corpus(tmp_path)
    pretrain.main(args=test_e2e_pretrain._training_args(tmp_path, prefix, num_steps=4))


def _run_finetune(tmp_path):
    from dolomite_engine_tpu import finetune

    finetune.main(args=test_e2e_finetune._training_args(tmp_path, num_steps=4))


# the spans nested in `loop.sync` and `loop.log` (the record's `t.inner`), by their parent
INNER_OF = {
    "loop.sync": ["sync.step", "sync.read"],
    "loop.log": ["log.read", "log.track", "log.progress"],
}


@pytest.fixture(scope="module", params=[_run_pretrain, _run_finetune], ids=["pretrain", "finetune"])
def sink_records(request, tmp_path_factory, eight_devices):
    """One real run of an entry point (4 steps; log_interval 1, save_interval 2): its sink."""
    from dolomite_engine_tpu.model_wrapper import base as mw_base
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    def _setup(self, tokenizer_name, additional_special_tokens):
        self.tokenizer = test_e2e_finetune._StubTokenizer()

    tmp_path = tmp_path_factory.mktemp("loop")
    with pytest.MonkeyPatch.context() as monkeypatch:
        monkeypatch.setattr(mw_base.ModelWrapper, "_setup_tokenizer", _setup)
        MeshManager.destroy()
        request.param(tmp_path)
    with open(tmp_path / "ckpt" / "telemetry" / "rank-00000.jsonl") as f:
        return [json.loads(line) for line in f]


def test_entry_points_share_the_span_vocabulary_and_the_split_tiles_the_step(sink_records):
    records = sink_records
    steps = [r for r in records if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3, 4]
    for record in steps:
        t = record["t"]
        names = list(t["split"])
        assert names == [n for n in SPAN_ORDER if n in names], names  # nothing else, in order
        assert EVERY_STEP <= set(names)
        assert abs(sum(t["split"].values()) - t["wall"]) < max(0.02 * t["wall"], 1e-3)
    # save_interval 2, log_interval 1 in both: the save is a part of the step that paid for it,
    # the window's write the head of the next one
    assert "loop.checkpoint" in steps[1]["t"]["split"]
    assert "loop.checkpoint" not in steps[0]["t"]["split"]
    assert all(list(r["t"]["split"])[:2] == ["loop.record", "loop.window"] for r in steps[1:])
    assert records[-1]["kind"] == "run_end" and records[-1]["status"] == "ok"


def test_sync_and_log_are_cut_where_the_devices_state_changes(sink_records):
    """Every step of these runs syncs and logs: `t.inner` carries the five nested spans, in
    the order they ran, and the parts of one parent sum to it to within call overhead."""
    steps = [r for r in sink_records if r["kind"] == "step"]
    for record in steps:
        t = record["t"]
        inner = t["inner"]
        ours = [n for n in inner if n.split(".")[0] in ("sync", "log")]
        assert ours == INNER_OF["loop.sync"] + INNER_OF["loop.log"], list(inner)
        for parent, parts in INNER_OF.items():
            summed = sum(inner[n] for n in parts)
            assert summed <= t["split"][parent] + 1e-6
            assert t["split"][parent] - summed < max(0.02 * t["split"][parent], 2e-3), (parent, t)
        assert not set(inner) & set(t["split"])  # a nested span is no part of the tiling
    # the save's device-to-host copy is nested in `loop.checkpoint` on the steps that save
    assert "checkpoint_save" in steps[1]["t"]["inner"] and "checkpoint_save" not in steps[0]["t"]["inner"]


def test_every_step_record_says_what_else_the_host_did(sink_records):
    steps = [r for r in sink_records if r["kind"] == "step"]
    for record in steps:
        t = record["t"]
        assert set(t["host"]) == {"nivcsw", "majflt", "cpu"}
        assert t["host"]["nivcsw"] >= 0 and t["host"]["majflt"] >= 0 and 0 <= t["host"]["cpu"]
        assert set(t) <= {"data", "step", "compile", "wall", "split", "inner", "off_loop", "gc", "host"}
        if "gc" in t:
            assert t["gc"]["count"] >= 1 and 0 <= t["gc"]["seconds"] <= t["wall"] + 1e-3
    # the prefetch worker assembles batches beside the loop: its spans reach some step's record
    off_loop = {name for r in steps for name in r["t"].get("off_loop", ())}
    assert off_loop <= {"data_fetch", "prefetch_assemble", "dataloader_assemble", "checkpoint_save"}
    assert "prefetch_assemble" in off_loop


# --------------------------------------------------------------------------- a step that does not sync


class _Batches:
    description = "test batches"
    last_wait_seconds = 0.0

    def __init__(self, n):
        self.left = n

    def __iter__(self):
        return self

    def __next__(self):
        if not self.left:
            raise StopIteration
        self.left -= 1
        return {"x": 1.0}

    def close(self):
        pass


@pytest.mark.parametrize(
    "skip_nonfinite, health_interval, synced",
    [
        (False, 0, {3, 6}),  # only the logging steps sync
        (True, 0, {1, 2, 3, 4, 5, 6}),  # the non-finite count reads `skipped` every step
        (False, 1, {1, 2, 3, 4, 5, 6}),  # the health monitor wants the step's metrics
    ],
    ids=["log_steps_only", "skip_nonfinite", "health"],
)
def test_a_step_that_does_not_sync_opens_no_sync_span(tmp_path, skip_nonfinite, health_interval, synced):
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu import train_loop
    from dolomite_engine_tpu.utils.diagnostics import HealthMonitor
    from dolomite_engine_tpu.utils.telemetry import Telemetry

    args = SimpleNamespace(
        training_parameters=SimpleNamespace(num_training_steps=6, eval_interval=None),
        save_args=SimpleNamespace(save_interval=100),
        logging_args=SimpleNamespace(log_interval=3, torch_profiler_trace_path=None),
        fault_tolerance_args=SimpleNamespace(
            dataloader_stall_timeout_seconds=None, preemption_checkpointing=False,
            skip_nonfinite_steps=skip_nonfinite, max_consecutive_nonfinite_steps=3,
        ),
    )
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    run = train_loop.TrainingRun(telemetry, HealthMonitor(telemetry, interval=health_interval))
    step = jax.jit(lambda state, batch, rng: (state + 1, {"loss": state * 0.5, "grad_norm": state, "skipped": state < 0}))
    logged = []

    def log(**kwargs):
        logged.append(kwargs["step"])
        return {}

    state, last = train_loop.run_loop(
        run, args, jnp.zeros(()), step, _Batches(6), starting_iteration=0, jax_rng=jax.random.PRNGKey(0),
        save=lambda *a: None, evaluate=None, log=log,
    )
    telemetry.close()
    assert last == 6 and float(state) == 6.0 and logged == [3, 6]
    with open(sink) as f:
        steps = [r for r in map(json.loads, f) if r["kind"] == "step"]
    for record in steps:
        inner = record["t"].get("inner", {})
        assert ({"sync.step", "sync.read"} <= set(inner)) == (record["step"] in synced), record
        assert not ({"sync.step", "sync.read"} & set(inner)) or record["step"] in synced
        assert ({"log.read", "log.track", "log.progress"} <= set(inner)) == (record["step"] in (3, 6))
        assert "loop.sync" in record["t"]["split"] and "loop.log" in record["t"]["split"]  # the outer names stay
