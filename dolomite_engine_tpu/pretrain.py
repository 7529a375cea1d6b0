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

import contextlib
import logging

import jax
import jax.numpy as jnp
import numpy as np

from .arguments import TrainingArgs, get_args
from .checkpointing import (
    get_experiments_tracker_checkpoint_metadata,
    load_checkpoint_for_training,
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
from .model_wrapper import get_model, log_model
from .ops.attention import splash_expected
from .optimization import build_optimizer_from_args
from .parallel.mesh import named_sharding
from .train_loop import run_loop, training_run
from .train_utils import (
    estimate_remat_activation_bytes,
    get_model_tflops,
    make_eval_step,
    make_train_step,
    offload_jit_kwargs as _offload_jit_kwargs,
    resolve_cpu_offload as _resolve_cpu_offload,
    track_train_metrics,
)
from .utils import ExperimentsTracker, emit_model_report, init_distributed, log_rank_0
from .utils.program_signature import capture_jit_signature, emit_program_signature_record

# Seams. `benchmark/drivers/train_packed*.py`, `chip_smoke.py` and `tests/benchmark/` replace
# `create_sharded_train_state`, `StepPrefetcher`, `save_checkpoint`, `track_train_metrics` and
# `make_train_step` as attributes of THIS module for a run. Each is looked up here when it is
# called: so the state, the prefetchers, the jitted step and the `save` / `log` closures are
# built in this file and handed to `train_loop.run_loop`. The benchmark reads the step as
# `save_checkpoint`'s sixth positional and `track_train_metrics`' keywords `global_step` and
# `train_loss_step`, once a logging step.


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


def _prefetched(loaders: list, depth: int, description: str) -> list:
    """Eval loaders are consumed incrementally (eval_steps batches per interval): a persistent
    single-pass prefetcher per group keeps the next eval's batches warm."""
    return [
        dl
        if dl is None or isinstance(dl, StepPrefetcher)
        else StepPrefetcher(dl, depth=depth, description=description)
        for dl in loaders
    ]


def _close(loaders: list) -> None:
    for dl in loaders:
        if isinstance(dl, StepPrefetcher):
            dl.close()


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
    """Pretraining (reference `pretrain.py:60-219`): builds the jitted step, the prefetchers
    and what a save, an evaluation and a log line are here; `train_loop.run_loop` iterates."""
    gradient_accumulation_steps = args.training_parameters.gradient_accumulation_steps
    micro_batch_size = args.training_parameters.micro_batch_size
    sequence_length = args.datasets[0].class_args.get("sequence_length")
    eval_steps = args.datasets[0].class_args.get("eval_steps", 0) or 0

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
        attention_kernel=splash_expected(model.attention_implementation),
    )

    def loss_fn(params, micro, rng, fp8_state=None):
        rngs = None if rng is None else {"dropout": rng}
        return model.loss(params, micro["text"], rngs=rngs, train=True, fp8_state=fp8_state)

    if jax_rng is None:
        jax_rng = jax.random.PRNGKey(args.random_args.seed)

    # MFU needs the per-group analytic FLOPs and how many devices share one model-parallel
    # group under SPMD
    with training_run(
        args,
        experiments_tracker,
        model_tflops_per_step=step_tflops,
        devices_per_group=max(jax.device_count() // dp_world_size, 1),
    ) as run:
        telemetry, monitor = run.telemetry, run.monitor
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
                loss_fn,
                optimizer,
                gradient_accumulation_steps=gradient_accumulation_steps,
                gradient_clipping=args.training_parameters.gradient_clipping,
                offload_optimizer=offload,
                skip_nonfinite=args.fault_tolerance_args.skip_nonfinite_steps,
                collect_health=monitor.wants_step_metrics,
                has_aux=bool(model.step_counter_names),
            ),
            donate_argnums=(0,),
            **jit_kwargs,
        )
        if hasattr(model.config, "layout_record"):
            # a model cut to a chip's share says once a run what it holds of what was published
            telemetry.event_once("model_layout", **model.config.layout_record())
        eval_step_fn = jax.jit(make_eval_step(model))

        if args.logging_args.telemetry.program_signatures:
            # self-report what compiled (docs/OBSERVABILITY.md "Perf ledger"): AOT-compile
            # the train step on the run's exact batch shape/sharding and write its perf
            # signature — temp-HBM high water, donation, cost flops, HLO features — as a
            # `program_signature` record. One extra compile, hence behind the flag.
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
                    train_step, (state, batch_struct, jax_rng), name="train_step"
                )
            emit_program_signature_record(telemetry, "pretrain", {"train_step": signature})

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
        val_dataloaders = _prefetched(val_dataloaders, prefetch_depth, "val dataloader")
        test_dataloaders = _prefetched(test_dataloaders, prefetch_depth, "test dataloader")

        val_group_names = get_group_names(args, "val_weighted_split_paths")

        def evaluate_val(step: int, state) -> None:
            evaluate(
                val_dataloaders,
                state,
                step,
                experiments_tracker,
                eval_steps,
                eval_step_fn,
                group_names=val_group_names,
            )

        def save(step: int, state, jax_rng) -> None:
            save_checkpoint(
                args,
                model,
                state,
                None,  # megatron loaders resume via consumed_samples metadata
                experiments_tracker,
                step,
                jax_rng=jax_rng,
                metadata={
                    "consumed_samples": consumed_samples
                    + (step - starting_iteration) * samples_per_step
                },
            )

        def log(step: int, loss, grad_norm, loss_running_mean, step_time) -> dict:
            billion_tokens_per_day = tokens_per_step * 86400 / step_time / 1e9
            track_train_metrics(
                global_step=step,
                train_loss_step=loss,
                grad_norm=grad_norm,
                # the schedule is eager jax: a few small device programs a log
                current_lr=float(lr_schedule(step)),
                experiments_tracker=experiments_tracker,
                loss_running_mean=loss_running_mean,
                flops=step_tflops / step_time,
                billion_tokens_per_day=billion_tokens_per_day,
                step_time=step_time,
                mfu=telemetry.current_mfu(),
            )
            return dict(loss=loss, tok_day_B=billion_tokens_per_day, step_s=step_time)

        evaluating = bool(args.training_parameters.eval_during_training and eval_steps)
        try:
            state, global_step = run_loop(
                run,
                args,
                state,
                train_step,
                prefetcher,
                starting_iteration=starting_iteration,
                jax_rng=jax_rng,
                save=save,
                evaluate=evaluate_val if evaluating else None,
                log=log,
            )
        finally:
            # test loaders stay open for the final evaluation below and are closed after it
            _close(val_dataloaders)

    # final test-set evaluation (reference `pretrain.py:216` evaluates test loaders after
    # training; val was already evaluated in-loop at this step when the interval divides);
    # a preempted run skips it — the grace window is for saving
    if not run.preempted and evaluating:
        try:
            test_loss = evaluate(
                test_dataloaders,
                state,
                global_step,
                None,
                eval_steps,
                eval_step_fn,
                group_names=get_group_names(args, "test_weighted_split_paths"),
            )
        finally:
            _close(test_dataloaders)
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
