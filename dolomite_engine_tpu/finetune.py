"""Finetuning trainer entry point: `python -m dolomite_engine_tpu.finetune --config cfg.yml`.

Parity: reference `dolomite_engine/finetune.py` (315 LoC): `main` (214-311) builds args ->
distributed init -> model -> dataloaders -> wrap -> optimizer/scheduler -> resume -> train;
`train` (49-153) loops `infinite_iterator(train_dataloader)` for num_training_steps with
periodic eval/save; `evaluate` (156-211) is a full pass over the val loader.

TPU deltas: the train step is ONE jitted function over the whole global-step batch (micro-batch
grad accumulation via `lax.scan` inside, see `train_utils.make_train_step`); there is no
torch-profiler/no_sync/clip plumbing in the loop body — those live inside the jitted step.
The reference's `infinite_iterator(train_dataloader)` is subsumed by the async input pipeline
(`data/prefetch.py` StepPrefetcher, `training_parameters.prefetch_depth`): a background worker
cycles the loader, stacks each step's micros and places them on device ahead of the loop.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from .arguments import TrainingArgs, get_args
from .checkpointing import (
    get_experiments_tracker_checkpoint_metadata,
    load_checkpoint_for_training,
    finish_pending_checkpoint,
    save_checkpoint,
)
from .data import PrefetchingIterable, StepPrefetcher, get_dataloader
from .distributed import build_mesh_from_args, create_sharded_train_state
from .enums import DatasetSplit, Mode, TuningMethod
from .model_wrapper import get_model, log_model
from .optimization import get_optimizer, get_scheduler
from .train_utils import (
    get_profiler_context,
    handle_nonfinite_step,
    make_eval_step,
    make_train_step,
    offload_jit_kwargs as _offload_jit_kwargs,
    resolve_cpu_offload as _resolve_cpu_offload,
    track_train_metrics,
)
from .utils import (
    ExperimentsTracker,
    ProgressBar,
    StallWatchdog,
    build_health_monitor,
    build_telemetry,
    crash_reason,
    emit_model_report,
    init_distributed,
    install_preemption_handler,
    install_telemetry,
    log_rank_0,
    preemption_requested,
    register_crash_hook,
    uninstall_preemption_handler,
    uninstall_telemetry,
    unregister_crash_hook,
)


def build_optimizer_from_args(args: TrainingArgs, model):
    lr_scheduler_args = args.lr_scheduler_args
    lr_schedule = get_scheduler(
        num_warmup_steps=lr_scheduler_args.num_warmup_steps,
        num_constant_steps=lr_scheduler_args.num_constant_steps,
        num_decay_steps=lr_scheduler_args.num_decay_steps,
        num_training_steps=args.training_parameters.num_training_steps,
        lr_decay_style=lr_scheduler_args.lr_decay_style,
        lr_decay_factor=lr_scheduler_args.lr_decay_factor,
        extra_lr_scheduler_args=lr_scheduler_args.extra_lr_scheduler_args,
        base_lr=args.optimizer_args.class_args.get("lr", 1e-5),
    )
    optimizer = get_optimizer(
        optimizer_class_name=args.optimizer_args.class_name,
        optimizer_class_args=args.optimizer_args.class_args,
        lr_schedule=lr_schedule,
        params_group_method=args.optimizer_args.params_group_method,
        model_config=model.config,
        params=model.abstract_params(),
    )
    return optimizer, lr_schedule


def _stack_micro_batches(batches: list[dict]) -> dict:
    """[grad_accum] leading axis on every leaf (all micro-batches of one global step)."""
    out = {}
    for k in batches[0].keys():
        vals = [b[k] for b in batches]
        if vals[0] is None:
            continue
        out[k] = jnp.stack(vals)
    return out


def train(
    args: TrainingArgs,
    model,
    state,
    optimizer,
    lr_schedule,
    train_dataloader,
    val_dataloader,
    experiments_tracker: ExperimentsTracker | None,
    starting_iteration: int = 0,
    jax_rng: jax.Array | None = None,
) -> None:
    """Main finetuning loop (reference `finetune.py:49-153`)."""
    num_training_steps = args.training_parameters.num_training_steps
    gradient_accumulation_steps = args.training_parameters.gradient_accumulation_steps
    eval_during_training = args.training_parameters.eval_during_training
    eval_interval = args.training_parameters.eval_interval
    save_interval = args.save_args.save_interval
    log_interval = args.logging_args.log_interval
    ft_args = args.fault_tolerance_args

    def loss_fn(params, micro_batch, rng, fp8_state=None):
        rngs = None if rng is None else {"dropout": rng, "neft": rng}
        return model.loss(params, micro_batch, rngs=rngs, train=True, fp8_state=fp8_state)

    # always-on telemetry (docs/OBSERVABILITY.md): goodput breakdown per logging window into
    # the per-host JSONL sink, counters from the fault-tolerance/checkpoint layers,
    # on-demand profiling. No analytic FLOPs model for variable-length finetune batches, so
    # MFU is omitted here (pretrain reports it). The health monitor rides the same sink:
    # per-group tensor stats in the jitted step (when health.interval > 0), anomaly
    # detection, crash flight recorder.
    telemetry = build_telemetry(args, experiments_tracker)
    install_telemetry(telemetry)
    monitor = build_health_monitor(args, telemetry)
    register_crash_hook(monitor.dump_flight_record)
    # batch shapes come from data here, so no analytic activation-bytes estimate —
    # the report still records which remat policy is active
    from .train_utils import resolve_checkpointing_args

    ckpt_every, ckpt_policy = resolve_checkpointing_args(
        args.distributed_args.gradient_checkpointing_method,
        args.distributed_args.gradient_checkpointing_args,
    )
    emit_model_report(
        telemetry,
        state,
        remat={"checkpoint_every": ckpt_every, "policy": ckpt_policy} if ckpt_every else None,
    )

    offload = _resolve_cpu_offload(args)
    jit_kwargs = _offload_jit_kwargs(state) if offload else {}
    train_step = jax.jit(
        make_train_step(
            loss_fn,
            optimizer,
            gradient_accumulation_steps=gradient_accumulation_steps,
            gradient_clipping=args.training_parameters.gradient_clipping,
            offload_optimizer=offload,
            skip_nonfinite=ft_args.skip_nonfinite_steps,
            collect_health=monitor.wants_step_metrics,
        ),
        donate_argnums=(0,),
        **jit_kwargs,
    )
    eval_step = jax.jit(
        make_eval_step(
            lambda params, batch, rng, fp8_state=None: model.loss(
                params, batch, rngs=None, train=False, fp8_state=fp8_state
            )
        )
    )

    if jax_rng is None:
        jax_rng = jax.random.PRNGKey(args.random_args.seed)

    if eval_during_training and starting_iteration == 0:
        with telemetry.span("loop.eval", bucket="eval"):
            evaluate(
                val_dataloader, model, state, starting_iteration, experiments_tracker, eval_step
            )

    # async input pipeline (data/prefetch.py): a background worker drains the dataloader,
    # stacks each step's micros and places them on device up to prefetch_depth batches
    # ahead, so host data work overlaps the previous jitted step. finetune.main wraps
    # BEFORE checkpoint load so resume state flows through the prefetcher; callers that
    # pass a bare loader (tests driving train() directly) get wrapped here
    prefetcher = train_dataloader
    if not isinstance(prefetcher, StepPrefetcher):
        prefetcher = StepPrefetcher(
            train_dataloader,
            depth=args.training_parameters.prefetch_depth,
            micros_per_step=gradient_accumulation_steps,
            assemble_fn=_stack_micro_batches,
            loop=True,
            description="train dataloader",
        )
    # the watchdog wraps the prefetcher's next() — in async mode that bounds the queue
    # get, so a wedged prefetch worker still trips the stall abort
    batch_iter = prefetcher
    if ft_args.dataloader_stall_timeout_seconds is not None:
        batch_iter = StallWatchdog(
            batch_iter,
            ft_args.dataloader_stall_timeout_seconds,
            description="train dataloader",
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
    preempted = False
    exit_status = "ok"
    metrics = None  # the newest dispatched step's outputs (what a profiler capture waits for)

    def save(step: int) -> None:
        with telemetry.span("loop.checkpoint", bucket="checkpoint"):
            # the PREFETCHER's state, not the loader's: the loader runs ahead of
            # consumption, the prefetcher's snapshot+skip accounts for batches
            # buffered but not yet consumed (resume-exact at any depth)
            save_checkpoint(
                args,
                model,
                state,
                prefetcher,
                experiments_tracker,
                step,
                jax_rng=jax_rng,
            )

    try:
        # Every boundary of an iteration is one `telemetry.span`: the loop thread's spans
        # tile the iteration (docs/OBSERVABILITY.md "Spans of a training iteration"), so
        # the step record's split sums to its wall time and a profile attributes every
        # idle gap of the device to a part of the loop.
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
                step_skipped = False
                if ft_args.skip_nonfinite_steps:
                    # host sync per step — the price of counting consecutive skips promptly
                    step_skipped = bool(metrics["skipped"])

                if not step_skipped:  # a skipped step's loss is non-finite; keep the mean clean
                    unread_losses.append(metrics["loss"])

                if sync_step:
                    # syncing here puts the outstanding device work in the step bucket
                    # below, so window goodput stays honest without a per-step host sync
                    loss = float(metrics["loss"])
                    grad_norm = float(metrics["grad_norm"])
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
                    loss_running_sum += float(np.sum(jax.device_get(unread_losses)))
                    loss_running_count += len(unread_losses)
                    unread_losses.clear()
                    track_train_metrics(
                        global_step=global_step,
                        train_loss_step=loss,
                        grad_norm=grad_norm,
                        # the schedule is eager jax: a few small device programs a log
                        current_lr=float(lr_schedule(global_step)),
                        experiments_tracker=experiments_tracker,
                        loss_running_mean=loss_running_sum / max(loss_running_count, 1),
                        step_time=data_seconds + step_seconds,
                    )
                    progress.set_postfix(loss=loss, step_s=data_seconds + step_seconds)

                progress.track(global_step)

            if eval_during_training and eval_interval and global_step % eval_interval == 0:
                with telemetry.span("loop.eval", bucket="eval"):
                    evaluate(
                        val_dataloader, model, state, global_step, experiments_tracker, eval_step
                    )

            if global_step % save_interval == 0 or global_step == num_training_steps:
                save(global_step)
                last_saved_step = global_step

            with telemetry.span("loop.poll"):
                telemetry.poll_profiler(global_step, metrics)
                preempted = preemption_requested()
                if preempted:
                    log_rank_0(
                        logging.WARNING,
                        f"preemption notice: saving final checkpoint at step {global_step} "
                        "and exiting",
                    )
            if preempted and last_saved_step != global_step:
                save(global_step)

            # The iteration ends here: the step record carries its whole split, and the
            # window record — written after eval/checkpoint so their buckets land in the
            # window of the step that paid for them — is the first of the next one's.
            telemetry.record_step(global_step, data_seconds, step_seconds)
            if logging_step:
                with telemetry.span("loop.window"):
                    telemetry.emit_window(global_step)
            if preempted:
                break

        finish_pending_checkpoint()  # commit an in-flight async save before exiting
    except BaseException as error:
        exit_status = f"error:{type(error).__name__}"
        # crash path: preserve the last-N-steps flight record before unwinding (no-op if a
        # fault-tolerance hook — stall watchdog, preemption — already dumped)
        monitor.dump_flight_record(crash_reason(error), error=error)
        raise
    finally:
        if ft_args.preemption_checkpointing:
            uninstall_preemption_handler()
        unregister_crash_hook(monitor.dump_flight_record)
        if isinstance(batch_iter, StallWatchdog):
            batch_iter.close()
        prefetcher.close()  # every exit path shuts the prefetch worker down
        telemetry.close("preempted" if preempted else exit_status)
        uninstall_telemetry()

    # final eval only when the loop didn't just run one at this step (reference finetune.py
    # evaluates only in-loop); a preempted run skips it — the grace window is for saving
    if (
        not preempted
        and eval_during_training
        and (not eval_interval or global_step % eval_interval != 0)
    ):
        evaluate(val_dataloader, model, state, global_step, experiments_tracker, eval_step)


def evaluate(
    val_dataloader,
    model,
    state,
    global_step: int,
    experiments_tracker: ExperimentsTracker | None,
    eval_step=None,
) -> float | None:
    """Full pass over the val loader (reference `finetune.py:156-211`). Pass a pre-jitted
    `eval_step` to avoid recompiling on every eval interval."""
    if val_dataloader is None:
        return None

    if eval_step is None:
        eval_step = jax.jit(
            make_eval_step(
                lambda params, batch, rng, fp8_state=None: model.loss(
                    params, batch, rngs=None, train=False, fp8_state=fp8_state
                )
            )
        )

    loss_sum, count = 0.0, 0
    for batch in val_dataloader:
        batch = {k: v for k, v in batch.items() if v is not None}
        loss_sum += float(eval_step(state.params, batch, state.fp8))
        count += 1
    if count == 0:
        return None

    loss = loss_sum / count
    if experiments_tracker is not None:
        experiments_tracker.track({"loss": loss}, step=global_step, context="val")
    log_rank_0(logging.INFO, f"step = {global_step}, val loss = {loss:.4f}")
    return loss


def main(mode: Mode = Mode.training, args: TrainingArgs | None = None) -> None:
    """Reference `finetune.py:214-311`."""
    if args is None:
        args = get_args(mode)

    assert args.tuning_args.tuning_method in (
        TuningMethod.full_finetuning,
        TuningMethod.prompt_tuning,
        TuningMethod.lora,
    ), "finetune requires a finetuning tuning method"

    # kernel-backend selection must be installed before any model trace (Pallas tier)
    args.kernel_args.install()

    init_distributed(timeout_minutes=args.distributed_args.timeout_minutes)

    import transformers

    transformers.set_seed(args.random_args.seed)
    np.random.seed(args.random_args.seed)

    model = get_model(args, mode)
    log_model(model)

    mesh = build_mesh_from_args(args)

    train_dataloader = get_dataloader(
        args,
        DatasetSplit.train,
        mode,
        model.tokenizer,
        is_encoder_decoder=model.is_encoder_decoder,
        mesh=mesh,
    )
    val_dataloader = None
    if args.training_parameters.eval_during_training:
        val_dataloader = get_dataloader(
            args,
            DatasetSplit.val,
            mode,
            model.tokenizer,
            is_encoder_decoder=model.is_encoder_decoder,
            mesh=mesh,
        )

    # async input pipeline: wrap BEFORE checkpoint load so dataloader resume state flows
    # through the prefetcher (its state accounts for batches buffered but not consumed);
    # assembly runs on the worker thread under this mesh, overlapping the jitted step
    prefetch_depth = args.training_parameters.prefetch_depth
    if train_dataloader is not None:
        train_dataloader = StepPrefetcher(
            train_dataloader,
            depth=prefetch_depth,
            micros_per_step=args.training_parameters.gradient_accumulation_steps,
            assemble_fn=_stack_micro_batches,
            loop=True,
            mesh=mesh,
            description="train dataloader",
        )
    if val_dataloader is not None:
        # restartable per-pass prefetch: evaluate() does one full pass per interval
        val_dataloader = PrefetchingIterable(
            val_dataloader, prefetch_depth, description="val dataloader"
        )

    optimizer, lr_schedule = build_optimizer_from_args(args, model)

    rng = jax.random.PRNGKey(args.random_args.seed)
    offload = _resolve_cpu_offload(args)
    state, _ = create_sharded_train_state(
        model, optimizer, mesh, rng, offload_optimizer=offload
    )

    starting_iteration = 0
    metadata = None
    jax_rng = None
    if args.load_args is not None:
        state, starting_iteration, metadata, jax_rng = load_checkpoint_for_training(
            args, state, train_dataloader, experiments_tracker=None
        )

    experiments_tracker = ExperimentsTracker(
        experiment_name="dolomite-tpu-finetune",
        tracker_name=args.logging_args.experiments_tracker_name,
        aim_args=args.logging_args.aim_args,
        wandb_args=args.logging_args.wandb_args,
        checkpoint_metadata=get_experiments_tracker_checkpoint_metadata(args),
    )
    experiments_tracker.log_args(args)

    with mesh:
        train(
            args,
            model,
            state,
            optimizer,
            lr_schedule,
            train_dataloader,
            val_dataloader,
            experiments_tracker,
            starting_iteration=starting_iteration,
            jax_rng=jax_rng,
        )

    experiments_tracker.finish()


if __name__ == "__main__":
    main()
