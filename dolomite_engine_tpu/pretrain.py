"""Pretraining trainer entry point: `python -m dolomite_engine_tpu.pretrain --config cfg.yml`.

Parity: reference `dolomite_engine/pretrain.py` (375 LoC): `main` (283-371) wires args ->
distributed -> model -> megatron dataloaders -> train; `train` (60-219) is a step-driven loop
with consumed-samples accounting, FLOPs + billion-tokens/day throughput reporting, profiler
hook, periodic eval (222-280) and checkpointing with consumed-samples metadata (195-210).

TPU deltas: the global step is ONE jitted function (grad accumulation via `lax.scan`); the val
"is loader None" TP broadcast (pretrain.py:245-258) is unnecessary — every host builds its own
loader shard deterministically.
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
from .data import StepPrefetcher
from .data.megatron import get_megatron_gpt_dataloaders
from .distributed import (
    build_mesh_from_args,
    create_sharded_train_state,
    get_data_parallel_world_size,
)
from .enums import Mode, TuningMethod
from .finetune import build_optimizer_from_args
from .model_wrapper import get_model, log_model
from .train_utils import (
    get_model_tflops,
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


def track_val_metrics(
    global_step: int,
    val_loss: float,
    experiments_tracker: ExperimentsTracker | None,
    group_name: str | None = None,
) -> None:
    """Reference `pretrain.py:38-57`."""
    message = f"step = {global_step}, val_loss = {val_loss:.4f}"
    if group_name is not None:
        message += f", group_name = {group_name}"
    log_rank_0(logging.INFO, message)

    if experiments_tracker is not None:
        key = "loss" if group_name is None else f"loss-{group_name}"
        experiments_tracker.track({key: val_loss}, step=global_step, context="val")


def evaluate(
    val_dataloaders: list,
    model,
    state,
    global_step: int,
    experiments_tracker: ExperimentsTracker | None,
    eval_steps: int,
    eval_step_fn,
    group_names: list | None = None,
) -> float | None:
    """eval_steps batches from each val group (reference `pretrain.py:222-280`); groups are
    reported under their `*_weighted_split_paths` key names (reference `pretrain.py:96-98`),
    falling back to the numeric index."""
    if not val_dataloaders or all(dl is None for dl in val_dataloaders):
        return None

    group_loss = None
    for group_index, loader in enumerate(val_dataloaders):
        if loader is None:
            continue
        loss_sum, count = 0.0, 0
        for _ in range(eval_steps):
            try:
                batch = next(loader)
            except StopIteration:
                break
            loss_sum += float(eval_step_fn(state.params, batch["text"], state.fp8))
            count += 1
        if count == 0:
            continue
        group_loss = loss_sum / count
        name = (
            group_names[group_index]
            if group_names and group_index < len(group_names)
            else str(group_index)
        )
        track_val_metrics(
            global_step,
            group_loss,
            experiments_tracker,
            group_name=name if len(val_dataloaders) > 1 else None,
        )
    return group_loss


def get_group_names(args: TrainingArgs, key: str) -> list | None:
    """Validation/test group names from the dataset's `val_weighted_split_paths` /
    `test_weighted_split_paths` keys (reference `pretrain.py:96-98` derives report names
    from the same structure: a list of single-key {group_name: entries} dicts)."""
    paths = args.datasets[0].class_args.get(key) if args.datasets else None
    if not paths:
        return None
    return [list(group.keys())[0] for group in paths if isinstance(group, dict) and group]


def train(
    args: TrainingArgs,
    model,
    state,
    optimizer,
    lr_schedule,
    train_dataloader,
    val_dataloaders: list,
    test_dataloaders: list,
    experiments_tracker: ExperimentsTracker | None,
    starting_iteration: int = 0,
    consumed_samples: int = 0,
    jax_rng: jax.Array | None = None,
    mesh=None,
) -> None:
    """Main pretraining loop (reference `pretrain.py:60-219`)."""
    num_training_steps = args.training_parameters.num_training_steps
    gradient_accumulation_steps = args.training_parameters.gradient_accumulation_steps
    micro_batch_size = args.training_parameters.micro_batch_size
    sequence_length = args.datasets[0].class_args.get("sequence_length")
    eval_during_training = args.training_parameters.eval_during_training
    eval_interval = args.training_parameters.eval_interval
    eval_steps = args.datasets[0].class_args.get("eval_steps", 0) or 0
    save_interval = args.save_args.save_interval
    log_interval = args.logging_args.log_interval
    ft_args = args.fault_tolerance_args

    dp_world_size = get_data_parallel_world_size(args)
    samples_per_step = micro_batch_size * gradient_accumulation_steps * dp_world_size
    tokens_per_step = samples_per_step * sequence_length

    # analytic TFLOPs for the whole global batch, reported per model-parallel device group
    # (reference get_model_tflops is per GPU; under SPMD we divide by dp_world)
    step_tflops = get_model_tflops(
        model.config,
        batch_size=micro_batch_size * gradient_accumulation_steps,
        sequence_length=sequence_length,
        gradient_checkpointing_method=args.distributed_args.gradient_checkpointing_method,
        gradient_checkpointing_args=args.distributed_args.gradient_checkpointing_args,
    )

    def loss_fn(params, text, rng, fp8_state=None):
        rngs = None if rng is None else {"dropout": rng}
        return model.loss(params, text, rngs=rngs, train=True, fp8_state=fp8_state)

    # always-on telemetry (docs/OBSERVABILITY.md): goodput breakdown + MFU per logging
    # window into the per-host JSONL sink, counters from the fault-tolerance/checkpoint
    # layers, on-demand profiling. MFU needs the per-group analytic FLOPs and how many
    # devices share one model-parallel group under SPMD. The health monitor rides the same
    # sink: per-group tensor stats in the jitted step (when health.interval > 0), anomaly
    # detection, crash flight recorder.
    telemetry = build_telemetry(
        args,
        experiments_tracker,
        model_tflops_per_step=step_tflops,
        devices_per_group=max(jax.device_count() // dp_world_size, 1),
    )
    install_telemetry(telemetry)
    monitor = build_health_monitor(args, telemetry)
    register_crash_hook(monitor.dump_flight_record)
    from .ops.attention import splash_expected
    from .train_utils import estimate_remat_activation_bytes

    emit_model_report(
        telemetry,
        state,
        model_tflops_per_step=step_tflops,
        remat=estimate_remat_activation_bytes(
            model.config,
            batch_size=micro_batch_size,
            sequence_length=sequence_length,
            gradient_checkpointing_method=args.distributed_args.gradient_checkpointing_method,
            gradient_checkpointing_args=args.distributed_args.gradient_checkpointing_args,
            dtype_bytes=jnp.dtype(model.dtype).itemsize,
            attention_kernel=splash_expected(model.attention_implementation),
        ),
    )

    offload = _resolve_cpu_offload(args)
    jit_kwargs = _offload_jit_kwargs(state) if offload else {}
    train_step = jax.jit(
        make_train_step(
            lambda params, micro, rng, fp8_state=None: loss_fn(
                params, micro["text"], rng, fp8_state
            ),
            optimizer,
            gradient_accumulation_steps=gradient_accumulation_steps,
            gradient_clipping=args.training_parameters.gradient_clipping,
            offload_optimizer=offload,
            skip_nonfinite=ft_args.skip_nonfinite_steps,
            collect_health=monitor.wants_step_metrics,
            has_aux=bool(model.step_counter_names),
        ),
        donate_argnums=(0,),
        **jit_kwargs,
    )
    if hasattr(model.config, "layout_record"):
        # a model cut to a chip's share says once a run what it holds of what was published
        telemetry.event_once("model_layout", **model.config.layout_record())
    eval_step_fn = jax.jit(
        make_eval_step(
            lambda params, text, rng, fp8_state=None: model.loss(
                params, text, rngs=None, train=False, fp8_state=fp8_state
            )
        )
    )

    if args.logging_args.telemetry.program_signatures:
        # self-report what compiled (docs/OBSERVABILITY.md "Perf ledger"): AOT-compile
        # the train step on the run's exact batch shape/sharding and write its perf
        # signature — temp-HBM high water, donation, cost flops, HLO features — as a
        # `program_signature` record. One extra compile, hence behind the flag.
        import contextlib

        from .parallel.mesh import named_sharding
        from .utils.program_signature import (
            capture_jit_signature,
            emit_program_signature_record,
        )

        rng_example = (
            jax_rng if jax_rng is not None else jax.random.PRNGKey(args.random_args.seed)
        )
        with mesh if mesh is not None else contextlib.nullcontext():
            # the loader's step batch: accum stacked GLOBAL micros (rows = micro_bs x
            # dp world, the shape `samples_per_step` accounts), batch dim over the data
            # axes — the same layout DispatchingDataLoader places
            batch_struct = {
                "text": jax.ShapeDtypeStruct(
                    (
                        gradient_accumulation_steps,
                        micro_batch_size * dp_world_size,
                        sequence_length + 1,
                    ),
                    jnp.int32,
                    sharding=(
                        named_sharding(None, ("dp", "fsdp")) if mesh is not None else None
                    ),
                )
            }
            signature = capture_jit_signature(
                train_step, (state, batch_struct, rng_example), name="train_step"
            )
        emit_program_signature_record(telemetry, "pretrain", {"train_step": signature})

    if jax_rng is None:
        jax_rng = jax.random.PRNGKey(args.random_args.seed)

    # async input pipeline (data/prefetch.py): the step batch ({"text": [accum, ...]}) is
    # assembled and device-placed by a background worker up to prefetch_depth ahead.
    # Megatron loaders resume via consumed_samples metadata (no dataloader state in the
    # checkpoint), so buffered-but-unconsumed batches are simply regenerated on restart —
    # consumed_samples only advances per consumed step
    prefetch_depth = args.training_parameters.prefetch_depth
    prefetcher = train_dataloader
    if not isinstance(prefetcher, StepPrefetcher):
        prefetcher = StepPrefetcher(
            train_dataloader,
            depth=prefetch_depth,
            micros_per_step=gradient_accumulation_steps,
            assemble_fn=lambda micros: {"text": jnp.stack([m["text"] for m in micros])},
            mesh=mesh,
            description="megatron train dataloader",
        )
    # eval loaders are consumed incrementally (eval_steps batches per interval): a
    # persistent single-pass prefetcher per group keeps the next eval's batches warm
    val_dataloaders = [
        dl
        if dl is None or isinstance(dl, StepPrefetcher)
        else StepPrefetcher(dl, depth=prefetch_depth, description="val dataloader")
        for dl in val_dataloaders
    ]
    test_dataloaders = [
        dl
        if dl is None or isinstance(dl, StepPrefetcher)
        else StepPrefetcher(dl, depth=prefetch_depth, description="test dataloader")
        for dl in test_dataloaders
    ]

    val_group_names = get_group_names(args, "val_weighted_split_paths")

    if eval_during_training and starting_iteration == 0 and eval_steps:
        with telemetry.span("loop.eval", bucket="eval"):
            evaluate(
                val_dataloaders,
                model,
                state,
                0,
                experiments_tracker,
                eval_steps,
                eval_step_fn,
                group_names=val_group_names,
            )

    # the watchdog wraps the prefetcher's next() — in async mode that bounds the queue
    # get, so a wedged prefetch worker still trips the stall abort
    batch_iter = prefetcher
    if ft_args.dataloader_stall_timeout_seconds is not None:
        batch_iter = StallWatchdog(
            batch_iter,
            ft_args.dataloader_stall_timeout_seconds,
            description="megatron train dataloader",
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
            save_checkpoint(
                args,
                model,
                state,
                None,  # megatron loaders resume via consumed_samples metadata
                experiments_tracker,
                step,
                jax_rng=jax_rng,
                metadata={"consumed_samples": consumed_samples},
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

            consumed_samples += samples_per_step

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
                    if "counters" in metrics:
                        # what the step's forward pass counted (a layer of experts each
                        # entry), read where the loss is read: no program of its own
                        telemetry.event(
                            "step_counters",
                            step=global_step,
                            **{k: v.tolist() for k, v in jax.device_get(metrics["counters"]).items()},
                        )
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
                    step_time = data_seconds + step_seconds
                    track_train_metrics(
                        global_step=global_step,
                        train_loss_step=loss,
                        grad_norm=grad_norm,
                        # the schedule is eager jax: a few small device programs a log
                        current_lr=float(lr_schedule(global_step)),
                        experiments_tracker=experiments_tracker,
                        loss_running_mean=loss_running_sum / max(loss_running_count, 1),
                        flops=step_tflops / step_time,
                        billion_tokens_per_day=tokens_per_step * 86400 / step_time / 1e9,
                        step_time=step_time,
                        mfu=telemetry.current_mfu(),
                    )
                    progress.set_postfix(
                        loss=loss,
                        tok_day_B=tokens_per_step * 86400 / step_time / 1e9,
                        step_s=step_time,
                    )

                progress.track(global_step)

            if (
                eval_during_training
                and eval_interval
                and eval_steps
                and global_step % eval_interval == 0
            ):
                with telemetry.span("loop.eval", bucket="eval"):
                    evaluate(
                        val_dataloaders,
                        model,
                        state,
                        global_step,
                        experiments_tracker,
                        eval_steps,
                        eval_step_fn,
                        group_names=val_group_names,
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
        # every exit path shuts the prefetch workers down (test loaders stay open for the
        # final evaluation below and are closed after it)
        prefetcher.close()
        for dl in val_dataloaders:
            if isinstance(dl, StepPrefetcher):
                dl.close()
        telemetry.close("preempted" if preempted else exit_status)
        uninstall_telemetry()

    # final test-set evaluation (reference `pretrain.py:216` evaluates test loaders after
    # training; val was already evaluated in-loop at this step when the interval divides);
    # a preempted run skips it — the grace window is for saving
    if not preempted and eval_during_training and eval_steps:
        try:
            test_loss = evaluate(
                test_dataloaders,
                model,
                state,
                global_step,
                None,
                eval_steps,
                eval_step_fn,
                group_names=get_group_names(args, "test_weighted_split_paths"),
            )
        finally:
            for dl in test_dataloaders:
                if isinstance(dl, StepPrefetcher):
                    dl.close()
        if test_loss is not None:
            if experiments_tracker is not None:
                experiments_tracker.track({"loss": test_loss}, step=global_step, context="test")
            log_rank_0(logging.INFO, f"step = {global_step}, test_loss = {test_loss:.4f}")


def main(mode: Mode = Mode.training, args: TrainingArgs | None = None) -> None:
    """Reference `pretrain.py:283-371`."""
    if args is None:
        args = get_args(mode)

    assert (
        args.tuning_args.tuning_method == TuningMethod.pretraining
    ), "pretraining requires tuning_method = pretraining"

    # kernel-backend selection must be installed before any model trace (Pallas tier)
    args.kernel_args.install()

    init_distributed(timeout_minutes=args.distributed_args.timeout_minutes)

    import transformers

    transformers.set_seed(args.random_args.seed)
    np.random.seed(args.random_args.seed)

    model = get_model(args, mode)
    log_model(model)

    mesh = build_mesh_from_args(args)

    optimizer, lr_schedule = build_optimizer_from_args(args, model)

    rng = jax.random.PRNGKey(args.random_args.seed)
    offload = _resolve_cpu_offload(args)
    state, _ = create_sharded_train_state(
        model, optimizer, mesh, rng, offload_optimizer=offload
    )

    starting_iteration = 0
    consumed_samples = 0
    jax_rng = None
    if args.load_args is not None:
        state, starting_iteration, metadata, jax_rng = load_checkpoint_for_training(
            args, state, None, experiments_tracker=None
        )
        if metadata is not None:
            consumed_samples = metadata.get("consumed_samples", 0)

    train_dataloader, val_dataloaders, test_dataloaders = get_megatron_gpt_dataloaders(
        args, model.tokenizer, consumed_samples, mesh=mesh
    )

    experiments_tracker = ExperimentsTracker(
        experiment_name="dolomite-tpu-pretrain",
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
            val_dataloaders,
            test_dataloaders,
            experiments_tracker,
            starting_iteration=starting_iteration,
            consumed_samples=consumed_samples,
            jax_rng=jax_rng,
            mesh=mesh,
        )

    experiments_tracker.finish()


if __name__ == "__main__":
    main()
