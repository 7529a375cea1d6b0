"""The training iteration, written once. `pretrain.train` and `finetune.train` build what
differs between them and hand it to :func:`run_loop`, inside a :func:`training_run`. This
module knows no entry point, no dataset and no model.
"""

from __future__ import annotations

import logging
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from .checkpointing import finish_pending_checkpoint
from .train_utils import get_profiler_context, handle_nonfinite_step
from .utils import ProgressBar, log_rank_0
from .utils.diagnostics import HealthMonitor, build_health_monitor, crash_reason
from .utils.fault_tolerance import (
    StallWatchdog,
    install_preemption_handler,
    preemption_requested,
    register_crash_hook,
    uninstall_preemption_handler,
    unregister_crash_hook,
)
from .utils.telemetry import Telemetry, build_telemetry, install_telemetry, uninstall_telemetry


@dataclass
class TrainingRun:
    """What :func:`training_run` holds open. `run_loop` sets `preempted`; the run's exit status
    and the entry points' final evaluations read it."""

    telemetry: Telemetry
    monitor: HealthMonitor
    preempted: bool = False


@contextmanager
def training_run(args, experiments_tracker, **telemetry_kwargs):
    """Always-on telemetry (docs/OBSERVABILITY.md) for one training run: goodput breakdown
    per logging window into the per-host JSONL sink, counters from the fault-tolerance and
    checkpoint layers, on-demand profiling. The health monitor rides the same sink:
    per-group tensor stats in the jitted step (when health.interval > 0), anomaly detection,
    crash flight recorder. `telemetry_kwargs` go to `build_telemetry` (pretrain's analytic
    FLOPs, for MFU). On exit the sink is flushed and statused on every path."""
    telemetry = build_telemetry(args, experiments_tracker, **telemetry_kwargs)
    install_telemetry(telemetry)
    monitor = build_health_monitor(args, telemetry)
    register_crash_hook(monitor.dump_flight_record)
    run = TrainingRun(telemetry, monitor)
    exit_status = "ok"
    try:
        yield run
    except BaseException as error:
        exit_status = f"error:{type(error).__name__}"
        # crash path: preserve the last-N-steps flight record before unwinding (no-op if a
        # fault-tolerance hook — stall watchdog, preemption — already dumped)
        monitor.dump_flight_record(crash_reason(error), error=error)
        raise
    finally:
        unregister_crash_hook(monitor.dump_flight_record)
        telemetry.close("preempted" if run.preempted else exit_status)
        uninstall_telemetry()


def run_loop(
    run: TrainingRun,
    args,
    state,
    train_step: Callable,
    prefetcher,
    *,
    starting_iteration: int,
    jax_rng: jax.Array,
    save: Callable[[int, Any, jax.Array], None],
    evaluate: Callable[[int, Any], Any] | None,
    log: Callable[..., dict],
):
    """Steps `starting_iteration + 1 .. num_training_steps` (or up to a preemption notice).

    `train_step(state, batch, rng) -> (state, metrics)` is the jitted step; `prefetcher`
    yields its batches and is closed on every exit path. `save(step, state, jax_rng)` writes
    a checkpoint; `evaluate(step, state)`, when given, runs before a run's first step and
    every `eval_interval` steps; `log(step=, loss=, grad_norm=, loss_running_mean=,
    step_time=)` writes a logging step's line and returns the progress bar's postfix.
    Returns `(state, last step)`.
    """
    telemetry, monitor = run.telemetry, run.monitor
    num_training_steps = args.training_parameters.num_training_steps
    eval_interval = args.training_parameters.eval_interval
    save_interval = args.save_args.save_interval
    log_interval = args.logging_args.log_interval
    ft_args = args.fault_tolerance_args

    # the watchdog wraps the prefetcher's next() — in async mode that bounds the queue
    # get, so a wedged prefetch worker still trips the stall abort
    batch_iter = prefetcher
    if ft_args.dataloader_stall_timeout_seconds is not None:
        batch_iter = StallWatchdog(
            batch_iter,
            ft_args.dataloader_stall_timeout_seconds,
            description=prefetcher.description,
        )
    if ft_args.preemption_checkpointing:
        install_preemption_handler()

    # running mean folds EVERY step (reference `train_utils.py:130-141`): the steps' device
    # scalars are kept as they are and read on the host only at log time — no device
    # program of the loop's own per step
    loss_running_sum = 0.0
    loss_running_count = 0
    unread_losses: list = []
    progress = ProgressBar(starting_iteration, num_training_steps)

    global_step = starting_iteration
    last_saved_step = None
    consecutive_nonfinite = 0
    metrics = None  # the newest dispatched step's outputs (what a profiler capture waits for)

    try:
        if evaluate is not None and starting_iteration == 0:
            with telemetry.span("loop.eval", bucket="eval"):
                evaluate(0, state)

        # Every boundary of an iteration is one `telemetry.span`: the loop thread's spans
        # tile the iteration (docs/OBSERVABILITY.md "Spans of a training iteration"), so
        # the step record's split sums to its wall time and a profile attributes every
        # idle gap of the device to a part of the loop; the spans nested in `loop.sync` and
        # `loop.log` (the record's `t.inner`) say which part of those.
        telemetry.begin_iterations()
        while global_step < num_training_steps:
            global_step += 1

            # the prefetcher yields the full step batch (micros pre-stacked, on device);
            # the data bucket charges only the time the loop truly waited on data —
            # residual queue wait in async mode, the raw micro fetch at prefetch_depth=0
            # (assembly is excluded in both modes and lands in the `other` bucket)
            with telemetry.span("loop.data_wait"):
                batch = next(batch_iter)
            data_seconds = prefetcher.last_wait_seconds

            step_start = time.perf_counter()

            with telemetry.span("loop.rng"):  # an eager device program (threefry split)
                jax_rng, step_rng = jax.random.split(jax_rng)
            with get_profiler_context(
                args.logging_args.torch_profiler_trace_path, global_step, lambda: metrics
            ), telemetry.span("train_step", step=global_step):
                state, metrics = train_step(state, batch, step_rng)

            logging_step = global_step % log_interval == 0
            sync_step = logging_step or monitor.wants_step_metrics
            with telemetry.span("loop.sync"):
                # Cut where the device's state changes: while `sync.step` waits the step's
                # program is still running (or has not begun); in `sync.read` it has ended
                # and the device waits for the host. A step that does not sync opens neither.
                step_skipped = False
                if sync_step or ft_args.skip_nonfinite_steps:
                    with telemetry.span("sync.step"):
                        jax.block_until_ready(metrics)
                    with telemetry.span("sync.read"):
                        if ft_args.skip_nonfinite_steps:
                            # host sync per step — the price of counting consecutive skips promptly
                            step_skipped = bool(metrics["skipped"])
                        if sync_step:
                            # syncing here puts the outstanding device work in the step bucket
                            # below, so window goodput stays honest without a per-step host sync
                            loss = float(metrics["loss"])
                            grad_norm = float(metrics["grad_norm"])
                            if "counters" in metrics:
                                # what the step's forward pass counted (a layer of experts each
                                # entry), read where the loss is read: no program of its own
                                telemetry.event(
                                    "step_counters",
                                    step=global_step,
                                    **{k: v.tolist() for k, v in jax.device_get(metrics["counters"]).items()},
                                )

                if not step_skipped:  # a skipped step's loss is non-finite; keep the mean clean
                    unread_losses.append(metrics["loss"])
            step_seconds = time.perf_counter() - step_start

            with telemetry.span("loop.account"):
                # feeds the flight recorder + anomaly detectors BEFORE the nonfinite abort
                # can fire, so a NaN-abort's flight record contains the offending step
                monitor.observe_step(
                    global_step,
                    loss=loss if sync_step else None,
                    grad_norm=grad_norm if sync_step else None,
                    step_seconds=step_seconds,
                    data_seconds=data_seconds,
                    skipped=step_skipped,
                )
                if monitor.health_due(global_step) and "health" in metrics:
                    monitor.emit_health(global_step, metrics["health"])

                if ft_args.skip_nonfinite_steps:
                    consecutive_nonfinite = handle_nonfinite_step(
                        step_skipped,
                        consecutive_nonfinite,
                        global_step,
                        ft_args.max_consecutive_nonfinite_steps,
                    )

            with telemetry.span("loop.log"):
                if logging_step:
                    with telemetry.span("log.read"):  # one read of the steps' loss scalars
                        loss_running_sum += float(np.sum(jax.device_get(unread_losses)))
                        loss_running_count += len(unread_losses)
                        unread_losses.clear()
                    with telemetry.span("log.track"):  # the entry point's line: eager programs
                        postfix = log(
                            step=global_step,
                            loss=loss,
                            grad_norm=grad_norm,
                            loss_running_mean=loss_running_sum / max(loss_running_count, 1),
                            step_time=data_seconds + step_seconds,
                        )
                    with telemetry.span("log.progress"):
                        progress.set_postfix(**postfix)
                        progress.track(global_step)
                else:
                    progress.track(global_step)

            if evaluate is not None and eval_interval and global_step % eval_interval == 0:
                with telemetry.span("loop.eval", bucket="eval"):
                    evaluate(global_step, state)

            if global_step % save_interval == 0 or global_step == num_training_steps:
                with telemetry.span("loop.checkpoint", bucket="checkpoint"):
                    save(global_step, state, jax_rng)
                last_saved_step = global_step

            with telemetry.span("loop.poll"):
                telemetry.poll_profiler(global_step, metrics)
                run.preempted = preemption_requested()
                if run.preempted:
                    log_rank_0(
                        logging.WARNING,
                        f"preemption notice: saving final checkpoint at step {global_step} "
                        "and exiting",
                    )
            if run.preempted and last_saved_step != global_step:
                with telemetry.span("loop.checkpoint", bucket="checkpoint"):
                    save(global_step, state, jax_rng)

            # The iteration ends here: the step record carries its whole split, and the
            # window record — written after eval/checkpoint so their buckets land in the
            # window of the step that paid for them — is the first of the next one's.
            telemetry.record_step(global_step, data_seconds, step_seconds)
            if logging_step:
                with telemetry.span("loop.window"):
                    telemetry.emit_window(global_step)
            if run.preempted:
                break

        finish_pending_checkpoint()  # commit an in-flight async save before exiting
    finally:
        if ft_args.preemption_checkpointing:
            uninstall_preemption_handler()
        if isinstance(batch_iter, StallWatchdog):
            batch_iter.close()
        prefetcher.close()  # every exit path shuts the prefetch worker down

    return state, global_step
