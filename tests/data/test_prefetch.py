"""Async input pipeline tests (ISSUE 5 tentpole, `data/prefetch.py`).

Covers: depth-0 synchronous equivalence (byte-identical batch sequence), async == sync
sequence, resume-exactness with a NON-EMPTY prefetch queue at checkpoint time (prefetcher
level and through the real `finetune.train` preemption path), worker-exception
re-raising at the consuming `next()`, the StallWatchdog firing through the prefetcher's
queue get, clean shutdown with a full queue, the restartable eval-pass wrapper, and the
acceptance criterion: with a deliberately slow loader, the steady-state `data` goodput
bucket in the JSONL sink at `prefetch_depth>=2` is <10%% of its depth-0 value.

Everything runs on unsharded pytree paths (the sharded-model construction path has the
known seed logical-axis skew)."""

import json
import threading
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dolomite_engine_tpu import finetune
from dolomite_engine_tpu.arguments import TrainingArgs
from dolomite_engine_tpu.checkpointing import load_checkpoint_for_training
from dolomite_engine_tpu.data.prefetch import PrefetchingIterable, StepPrefetcher
from dolomite_engine_tpu.finetune import _stack_micro_batches
from dolomite_engine_tpu.train_utils import TrainState
from dolomite_engine_tpu.utils import (
    StallWatchdog,
    install_telemetry,
    request_preemption,
    reset_preemption,
    uninstall_preemption_handler,
    uninstall_telemetry,
)
from dolomite_engine_tpu.utils.telemetry import Telemetry


# --------------------------------------------------------------------------- harness


class _SeqLoader:
    """Deterministic resumable loader: micro-batch k is full((2, 2), k). The cursor
    advances monotonically across epochs (epoch = `n` batches), so every batch in an
    infinite stream is unique and the consumed sequence pins the loader position."""

    def __init__(self, n=4, sleep=0.0, fail_at=None):
        self.n = n
        self.sleep = sleep
        self.fail_at = fail_at
        self.cursor = 0

    def __iter__(self):
        for _ in range(self.n):
            if self.fail_at is not None and self.cursor == self.fail_at:
                raise RuntimeError("poisoned shard")
            if self.sleep:
                time.sleep(self.sleep)
            value = self.cursor
            self.cursor += 1
            yield {"x": np.full((2, 4), value, np.float32)}

    def __len__(self):
        return self.n

    def state_dict(self):
        return {"cursor": self.cursor}

    def load_state_dict(self, sd):
        self.cursor = sd["cursor"]


def _values(batches):
    """One scalar per consumed step batch (all elements of a batch are equal)."""
    return [int(np.asarray(b["x"]).flat[0]) for b in batches]


def _consume(prefetcher, steps):
    return [next(prefetcher) for _ in range(steps)]


def _make(loader, depth, micros=1, loop=True):
    return StepPrefetcher(
        loader,
        depth=depth,
        micros_per_step=micros,
        assemble_fn=_stack_micro_batches,
        loop=loop,
        description="test loader",
    )


# --------------------------------------------------------------------------- equivalence


def test_depth0_matches_manual_synchronous_loop():
    """depth=0 is the pre-prefetch loops verbatim: same micro order, same stacking."""
    prefetcher = _make(_SeqLoader(), depth=0, micros=2)
    got = _consume(prefetcher, 6)

    reference_loader = _SeqLoader()

    def infinite(loader):
        while True:
            yield from iter(loader)

    it = infinite(reference_loader)
    for batch in got:
        expected = _stack_micro_batches([next(it) for _ in range(2)])
        np.testing.assert_array_equal(np.asarray(batch["x"]), np.asarray(expected["x"]))
        assert batch["x"].shape == (2, 2, 4)  # [accum, micro...]


@pytest.mark.parametrize("micros", [1, 3])
def test_async_sequence_matches_depth0(micros):
    sync = _make(_SeqLoader(), depth=0, micros=micros)
    async_ = _make(_SeqLoader(), depth=3, micros=micros)
    try:
        for a, b in zip(_consume(sync, 8), _consume(async_, 8)):
            np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
    finally:
        async_.close()


def test_finite_source_stop_iteration_propagates():
    prefetcher = _make(_SeqLoader(n=5), depth=2, micros=1, loop=False)
    try:
        assert _values(list(prefetcher)) == [0, 1, 2, 3, 4]
        with pytest.raises(StopIteration):
            next(prefetcher)  # stays exhausted
    finally:
        prefetcher.close()


# --------------------------------------------------------------------------- resume exactness


def test_resume_exact_with_nonempty_queue():
    """Tentpole: checkpoint while batches sit in the prefetch queue; the restored stream
    continues with exactly the first unconsumed batch — bit-for-bit the synchronous
    sequence, and the state survives the JSON round-trip checkpointing uses."""
    loader = _SeqLoader(sleep=0.002)
    prefetcher = _make(loader, depth=3, micros=2)
    try:
        consumed = _values(_consume(prefetcher, 3))
        deadline = time.time() + 5
        while prefetcher.queue_depth == 0 and time.time() < deadline:
            time.sleep(0.005)
        assert prefetcher.queue_depth > 0  # the loader ran AHEAD of consumption
        state = json.loads(json.dumps(prefetcher.state_dict()))
    finally:
        prefetcher.close()

    resumed = _make(_SeqLoader(sleep=0.002), depth=3, micros=2)
    resumed.load_state_dict(state)
    try:
        tail = _values(_consume(resumed, 5))
    finally:
        resumed.close()

    reference = _make(_SeqLoader(), depth=0, micros=2)
    expected = _values(_consume(reference, 8))
    assert consumed + tail == expected


def test_depth0_state_dict_roundtrip():
    prefetcher = _make(_SeqLoader(), depth=0, micros=2)
    head = _values(_consume(prefetcher, 2))
    state = prefetcher.state_dict()
    assert state["skip_batches"] == 1  # snapshot precedes the last consumed batch

    resumed = _make(_SeqLoader(), depth=0, micros=2)
    resumed.load_state_dict(state)
    tail = _values(_consume(resumed, 3))

    reference = _make(_SeqLoader(), depth=0, micros=2)
    assert head + tail == _values(_consume(reference, 5))


def test_load_accepts_legacy_bare_loader_state():
    """Checkpoints written before the prefetcher existed hold bare loader state."""
    prefetcher = _make(_SeqLoader(), depth=0, micros=1)
    prefetcher.load_state_dict({"cursor": 4})
    assert _values(_consume(prefetcher, 2)) == [4, 5]


def test_stateless_source_yields_empty_state():
    """Bare iterators (megatron pretrain loaders) wrap statelessly: resume rides the
    loop's consumed_samples metadata instead."""
    prefetcher = StepPrefetcher(iter([{"x": np.zeros((1,))}]), depth=0)
    assert prefetcher.state_dict() == {}


# --------------------------------------------------------------------------- failure transparency


def test_worker_exception_reraised_at_next():
    prefetcher = _make(_SeqLoader(n=8, fail_at=2), depth=2, micros=1, loop=False)
    try:
        assert _values(_consume(prefetcher, 2)) == [0, 1]
        with pytest.raises(RuntimeError, match="poisoned shard"):
            next(prefetcher)
        with pytest.raises(RuntimeError, match="poisoned shard"):
            next(prefetcher)  # the failure is sticky, not swallowed
    finally:
        prefetcher.close()


def test_stall_watchdog_fires_through_prefetcher():
    """A wedged worker looks exactly like a stalled dataloader: the watchdog bounds the
    prefetcher's queue get and aborts the run."""
    release = threading.Event()

    class _WedgedLoader(_SeqLoader):
        def __iter__(self):
            yield {"x": np.zeros((2, 4), np.float32)}
            release.wait(30)

    prefetcher = _make(_WedgedLoader(), depth=2, micros=1, loop=False)
    watchdog = StallWatchdog(prefetcher, timeout_seconds=0.3, description="train dataloader")
    try:
        next(watchdog)
        with pytest.raises(RuntimeError, match="train dataloader stalled"):
            next(watchdog)
    finally:
        release.set()
        watchdog.close()
        prefetcher.close()


def test_close_with_full_queue_stops_worker():
    prefetcher = _make(_SeqLoader(n=100), depth=1, micros=1)
    try:
        next(prefetcher)  # start the worker; it then blocks offering into the full queue
        deadline = time.time() + 5
        while prefetcher.queue_depth == 0 and time.time() < deadline:
            time.sleep(0.005)
    finally:
        prefetcher.close()
    assert not prefetcher._thread.is_alive()


# --------------------------------------------------------------------------- telemetry


def test_prefetch_telemetry_gauge_and_stall_counter(tmp_path):
    telemetry = Telemetry(sink_path=str(tmp_path / "sink.jsonl"))
    install_telemetry(telemetry)
    try:
        prefetcher = _make(_SeqLoader(sleep=0.02), depth=2, micros=1)
        try:
            _consume(prefetcher, 4)  # consumer outruns the 20ms/batch worker
        finally:
            prefetcher.close()
        assert "prefetch/queue_depth" in telemetry.gauges
        assert telemetry.counters.get("prefetch_stalls", 0) >= 1
    finally:
        uninstall_telemetry()
        telemetry.close()


# --------------------------------------------------------------------------- eval wrapper


def test_prefetching_iterable_restartable_passes():
    loader = _SeqLoader(n=5)
    wrapped = PrefetchingIterable(loader, depth=2)
    assert len(wrapped) == 5
    first = _values(list(wrapped))
    second = _values(list(wrapped))
    assert first == [0, 1, 2, 3, 4]
    assert second == [5, 6, 7, 8, 9]  # the cursor-advancing loader, second epoch

    # abandoning a pass mid-way tears the worker down and a fresh pass still works
    for i, _ in enumerate(wrapped):
        if i == 1:
            break
    assert len(_values(list(wrapped))) == 5


def test_prefetching_iterable_propagates_exceptions():
    wrapped = PrefetchingIterable(_SeqLoader(n=8, fail_at=1), depth=2)
    with pytest.raises(RuntimeError, match="poisoned shard"):
        list(wrapped)


# --------------------------------------------------------------------------- real-loop resume


class _RecordingPrefetcher(StepPrefetcher):
    """Records every consumed step batch and the queue depth at each state_dict call, so
    the loop-level test can assert the checkpoint was taken with a non-empty buffer."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.seen = []
        self.depth_at_save = []

    def __next__(self):
        batch = super().__next__()
        self.seen.append(int(np.asarray(batch["x"]).flat[0]))
        return batch

    def state_dict(self):
        self.depth_at_save.append(self.queue_depth)
        return super().state_dict()


class _Model:
    def loss(self, params, batch, rngs=None, train=True, fp8_state=None):
        return jnp.mean(params["w"] * batch["x"])


def _train_args(tmp_path, num_steps, load_path=None, prefetch_depth=2, log_interval=1):
    cfg = dict(
        model_args=dict(
            model_class="AutoModelForCausalLM",
            pretrained_config=dict(model_type="gpt_dolomite", vocab_size=8, n_positions=8,
                                   n_embd=4, n_layer=1, n_head=1),
        ),
        tuning_args=dict(tuning_method="full_finetuning"),
        training_parameters=dict(
            num_training_steps=num_steps,
            micro_batch_size=2,
            gradient_accumulation_steps=1,
            eval_during_training=False,
            prefetch_depth=prefetch_depth,
        ),
        datasets=[dict(class_name="DebugDataset", data_name="debug", class_args={})],
        save_args=dict(save_path=str(tmp_path / "ckpt"), save_interval=100),
        logging_args=dict(log_interval=log_interval),
        random_args=dict(seed=3),
    )
    if load_path is not None:
        cfg["load_args"] = dict(load_path=load_path)
    return TrainingArgs(**cfg)


def _fresh_state():
    params = {"w": jnp.ones((4,), jnp.float32), "b": jnp.zeros((2,), jnp.float32)}
    optimizer = optax.adam(1e-2)
    return (
        TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params)),
        optimizer,
    )


def _run_train(args, prefetcher, monkeypatch=None, preempt_at=None, state=None, start=0):
    if state is None:
        state, optimizer = _fresh_state()
    else:
        _, optimizer = _fresh_state()
    if preempt_at is not None:
        from dolomite_engine_tpu.train_utils import track_train_metrics as real_track

        def tracked(**kwargs):
            real_track(**kwargs)
            if kwargs["global_step"] == preempt_at:
                request_preemption()

        monkeypatch.setattr(finetune, "track_train_metrics", tracked)
    finetune.train(
        args, _Model(), state, optimizer, lambda step: 1e-2, prefetcher, None,
        experiments_tracker=None, starting_iteration=start,
    )


@pytest.fixture(autouse=True)
def _clean_preemption_state():
    reset_preemption()
    yield
    uninstall_preemption_handler()


def test_real_loop_preemption_resume_is_batch_exact(tmp_path, monkeypatch):
    """ISSUE acceptance: preempt the real finetune.train mid-run with a non-empty prefetch
    queue, restore from the checkpoint, and the consumed batch sequence across both runs
    is identical to one uninterrupted synchronous (depth 0) run."""
    # slow loader so the checkpoint reliably catches buffered-but-unconsumed batches
    run_a = _RecordingPrefetcher(
        _SeqLoader(sleep=0.01), depth=3, micros_per_step=1,
        assemble_fn=_stack_micro_batches, loop=True, description="train dataloader",
    )
    _run_train(_train_args(tmp_path, num_steps=9), run_a, monkeypatch, preempt_at=3)
    assert run_a.seen == [0, 1, 2]
    assert run_a.depth_at_save and run_a.depth_at_save[-1] > 0  # queue was non-empty

    # resume: a FRESH loader restored through the prefetcher, run to completion
    run_b = _RecordingPrefetcher(
        _SeqLoader(sleep=0.01), depth=3, micros_per_step=1,
        assemble_fn=_stack_micro_batches, loop=True, description="train dataloader",
    )
    args2 = _train_args(tmp_path, num_steps=9, load_path=str(tmp_path / "ckpt"))
    state, _ = _fresh_state()
    state, start, _, _ = load_checkpoint_for_training(args2, state, run_b)
    assert start == 3
    monkeypatch.setattr(finetune, "track_train_metrics", lambda **kwargs: None)
    _run_train(args2, run_b, state=state, start=start)

    # reference: one uninterrupted run on the synchronous path
    reference = _RecordingPrefetcher(
        _SeqLoader(), depth=0, micros_per_step=1,
        assemble_fn=_stack_micro_batches, loop=True, description="train dataloader",
    )
    _run_train(_train_args(tmp_path / "ref", num_steps=9, prefetch_depth=0), reference)

    assert run_a.seen + run_b.seen == reference.seen == list(range(9))


# --------------------------------------------------------------------------- goodput acceptance


def _read_sink(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_steady_state_data_bucket_shrinks_under_prefetch(tmp_path, monkeypatch):
    """ISSUE acceptance: slow fake loader (50 ms/batch) + fixed per-step compute budget;
    at prefetch_depth>=2 the steady-state `data` goodput bucket in the JSONL sink drops
    to <10%% of its depth-0 value in the same test."""

    @contextmanager
    def slow_profiler_context(path, step, last_outputs=None):
        # a deterministic stand-in for the jitted step's wall time: 80 ms the prefetch
        # worker can overlap, independent of CI machine speed
        time.sleep(0.08)
        yield

    monkeypatch.setattr("dolomite_engine_tpu.train_loop.get_profiler_context", slow_profiler_context)

    def run(depth, where):
        prefetcher = StepPrefetcher(
            _SeqLoader(sleep=0.05), depth=depth, micros_per_step=1,
            assemble_fn=_stack_micro_batches, loop=True, description="train dataloader",
        )
        _run_train(_train_args(where, num_steps=10, prefetch_depth=depth, log_interval=5), prefetcher)
        records = _read_sink(where / "ckpt" / "telemetry" / "rank-00000.jsonl")
        windows = [r for r in records if r["kind"] == "window"]
        assert len(windows) == 2
        return windows[1]["goodput"]["data"]  # steps 6-10: past compile + queue warmup

    sync_data = run(0, tmp_path / "sync")
    async_data = run(2, tmp_path / "async")

    assert sync_data >= 0.2  # 5 steady steps x 50 ms actually measured on the sync path
    assert async_data < 0.1 * sync_data
