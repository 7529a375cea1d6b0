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

import jax
import jax.numpy as jnp
import numpy as np

from .arguments import TrainingArgs, get_args
from .checkpointing import (
    get_experiments_tracker_checkpoint_metadata,
    load_checkpoint_for_training,
    save_checkpoint,
)
from .data import PrefetchingIterable, StepPrefetcher, get_dataloader
from .distributed import build_mesh_from_args, create_sharded_train_state
from .enums import DatasetSplit, Mode, TuningMethod
from .model_wrapper import get_model, log_model
from .optimization import build_optimizer_from_args
from .train_loop import run_loop, training_run
from .train_utils import (
    make_eval_step,
    make_train_step,
    offload_jit_kwargs as _offload_jit_kwargs,
    resolve_checkpointing_args,
    resolve_cpu_offload as _resolve_cpu_offload,
    track_train_metrics,
)
from .utils import ExperimentsTracker, emit_model_report, init_distributed, log_rank_0

# Seams. `tests/test_fault_tolerance.py` and `tests/data/test_prefetch.py` replace
# `save_checkpoint` and `track_train_metrics` as attributes of THIS module; both are looked up
# here when called, so the `save` and `log` closures are defined in this file
# (`get_profiler_context` is `train_loop`'s).


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
    """Finetuning (reference `finetune.py:49-153`): builds the jitted step, the prefetcher and
    what a save, an evaluation and a log line are here; `train_loop.run_loop` iterates."""
    gradient_accumulation_steps = args.training_parameters.gradient_accumulation_steps
    eval_during_training = args.training_parameters.eval_during_training
    eval_interval = args.training_parameters.eval_interval

    def loss_fn(params, micro_batch, rng, fp8_state=None):
        rngs = None if rng is None else {"dropout": rng, "neft": rng}
        return model.loss(params, micro_batch, rngs=rngs, train=True, fp8_state=fp8_state)

    if jax_rng is None:
        jax_rng = jax.random.PRNGKey(args.random_args.seed)

    # no analytic FLOPs model for variable-length finetune batches, so MFU is omitted here
    # (pretrain reports it)
    with training_run(args, experiments_tracker) as run:
        # batch shapes come from data here, so no analytic activation-bytes estimate —
        # the report still records which remat policy is active
        ckpt_every, ckpt_policy = resolve_checkpointing_args(
            args.distributed_args.gradient_checkpointing_method,
            args.distributed_args.gradient_checkpointing_args,
        )
        emit_model_report(
            run.telemetry,
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
                skip_nonfinite=args.fault_tolerance_args.skip_nonfinite_steps,
                collect_health=run.monitor.wants_step_metrics,
            ),
            donate_argnums=(0,),
            **jit_kwargs,
        )
        eval_step = jax.jit(make_eval_step(model))

        def evaluate_val(step: int, state) -> None:
            evaluate(val_dataloader, state, step, experiments_tracker, eval_step)

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

        def save(step: int, state, jax_rng) -> None:
            # the PREFETCHER's state, not the loader's: the loader runs ahead of
            # consumption, the prefetcher's snapshot+skip accounts for batches
            # buffered but not yet consumed (resume-exact at any depth)
            save_checkpoint(
                args, model, state, prefetcher, experiments_tracker, step, jax_rng=jax_rng
            )

        def log(step: int, loss, grad_norm, loss_running_mean, step_time) -> dict:
            track_train_metrics(
                global_step=step,
                train_loss_step=loss,
                grad_norm=grad_norm,
                # the schedule is eager jax: a few small device programs a log
                current_lr=float(lr_schedule(step)),
                experiments_tracker=experiments_tracker,
                loss_running_mean=loss_running_mean,
                step_time=step_time,
            )
            return dict(loss=loss, step_s=step_time)

        state, global_step = run_loop(
            run,
            args,
            state,
            train_step,
            prefetcher,
            starting_iteration=starting_iteration,
            jax_rng=jax_rng,
            save=save,
            evaluate=evaluate_val if eval_during_training else None,
            log=log,
        )

    # final eval only when the loop didn't just run one at this step (reference finetune.py
    # evaluates only in-loop); a preempted run skips it — the grace window is for saving
    if (
        not run.preempted
        and eval_during_training
        and (not eval_interval or global_step % eval_interval != 0)
    ):
        evaluate_val(global_step, state)


def evaluate(
    val_dataloader,
    state,
    global_step: int,
    experiments_tracker: ExperimentsTracker | None,
    eval_step,
) -> float | None:
    """Full pass over the val loader (reference `finetune.py:156-211`) through the jitted
    `eval_step` (`train_utils.make_eval_step`)."""
    if val_dataloader is None:
        return None

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
    jax_rng = None
    if args.load_args is not None:
        state, starting_iteration, _, jax_rng = load_checkpoint_for_training(
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
