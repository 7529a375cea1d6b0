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


@pytest.mark.parametrize("run_entry", [_run_pretrain, _run_finetune], ids=["pretrain", "finetune"])
def test_entry_points_share_the_span_vocabulary_and_the_split_tiles_the_step(
    run_entry, tmp_path, monkeypatch, eight_devices
):
    from dolomite_engine_tpu.model_wrapper import base as mw_base
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    def _setup(self, tokenizer_name, additional_special_tokens):
        self.tokenizer = test_e2e_finetune._StubTokenizer()

    monkeypatch.setattr(mw_base.ModelWrapper, "_setup_tokenizer", _setup)
    MeshManager.destroy()
    run_entry(tmp_path)

    with open(tmp_path / "ckpt" / "telemetry" / "rank-00000.jsonl") as f:
        records = [json.loads(line) for line in f]
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
