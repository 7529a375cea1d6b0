"""Checkpoint save/load/resume.

Parity: reference `dolomite_engine/checkpointing.py` (485 LoC). The reference juggles three
backends (DeepSpeed engine checkpoints, FSDP1 rank-0 full torch.save, FSDP2 dcp sharded save,
lines 82-109) plus TP unshard fixups at inference load (326-362); here one orbax sharded save
covers every parallelism layout, and "unsharding" is just restoring with replicated shardings.

Layout (reference `checkpointing.py:448-485`):

    <save_path>/global_step<N>/
        state/                      orbax pytree: TrainState(step, params, opt_state)
        rng_state.json              jax PRNG key + numpy/python RNG, per process
        dataloader/process-<i>.json dataloader+sampler state per data-parallel process (125-128)
        experiments_tracker.json    tracker resume info (130-133)
        metadata.json               consumed samples etc (pretrain.py:195-210)
        training_config.yml         full args snapshot -> self-describing checkpoint (138, 405-416)
    <save_path>/latest_checkpointed_iteration.json

Per-piece load toggles mirror `LoadArgs` (arguments.py:176-207 in the reference):
load_optimizer / load_lr_scheduler / load_rng_state / load_dataloader_state /
load_experiments_tracker_state / load_starting_iteration / resume_learning_rate.

`resume_learning_rate` (reference `_resume_learning_rate` 419-445): optax schedules are pure
functions of the step count inside opt_state; resuming the LR = restoring opt_state + step
(default), NOT resuming it = zeroing the schedule step after restore.

Fault tolerance (docs/FAULT_TOLERANCE.md): every durable-path operation — orbax
save/restore, the `latest` pointer read/write, metadata probes — retries transient I/O
errors with bounded backoff (`FaultToleranceArgs.checkpoint_io_*`); the pointer only
advances after an integrity check of the written state dir; `SaveArgs.keep_last_n` prunes
old checkpoints at commit time, never the `latest`-pointed one.
"""

from __future__ import annotations

import json
import logging
import os
import random
import re
import shutil
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from .arguments import InferenceArgs, TrainingArgs, UnshardingArgs, args_from_dict
from .enums import Mode
from .train_utils import TrainState
from .utils import ExperimentsTracker, get_telemetry, load_yaml, log_rank_0, retry_io

_TRAINING_CONFIG = "training_config.yml"
_LATEST = "latest_checkpointed_iteration.json"
_CHECKPOINT_DIR_RE = re.compile(r"global_step(\d+)")


def _retry_kwargs(args) -> dict:
    """Checkpoint-I/O retry policy from FaultToleranceArgs; defaults when the args tree has
    none (InferenceArgs/UnshardingArgs, or config snapshots predating the block)."""
    ft = getattr(args, "fault_tolerance_args", None)
    if ft is None:
        return {}
    return dict(
        attempts=ft.checkpoint_io_attempts,
        base_delay_seconds=ft.checkpoint_io_backoff_seconds,
        max_delay_seconds=ft.checkpoint_io_max_backoff_seconds,
    )


def _read_latest_iteration(path: str, retry_kwargs: dict | None = None) -> int:
    """The `latest`-pointer read, retried: on network filesystems this tiny read is the
    single point of failure for EVERY resume."""

    def _read() -> int:
        with open(os.path.join(path, _LATEST)) as f:
            return json.load(f)["latest_checkpointed_iteration"]

    return retry_io(_read, description=f"read {_LATEST}", **(retry_kwargs or {}))


def _get_checkpoint_tag(iteration: int) -> str:
    return f"global_step{iteration}"


def _get_base_path(path: str, iteration: int) -> str:
    return os.path.join(path, _get_checkpoint_tag(iteration))


def _state_path(base: str) -> str:
    return os.path.join(base, "state")


def _is_primary() -> bool:
    return jax.process_index() == 0


# --------------------------------------------------------------------------------- rng


def get_rng_state(jax_rng: jax.Array | None) -> dict:
    state = {
        "random_rng_state": list(random.getstate()[1]),
        "random_rng_version": random.getstate()[0],
        "np_rng_state": np.random.get_state()[1].tolist(),
        "np_rng_pos": list(np.random.get_state()[2:]),
    }
    if jax_rng is not None:
        state["jax_rng_key"] = np.asarray(jax.random.key_data(jax_rng)).tolist()
    return state


def set_rng_state(state: dict) -> jax.Array | None:
    random.setstate(
        (state["random_rng_version"], tuple(state["random_rng_state"]), None)
    )
    np.random.set_state(
        (
            "MT19937",
            np.array(state["np_rng_state"], dtype=np.uint32),
            int(state["np_rng_pos"][0]),
            int(state["np_rng_pos"][1]),
            float(state["np_rng_pos"][2]),
        )
    )
    if "jax_rng_key" in state:
        return jax.random.wrap_key_data(np.array(state["jax_rng_key"], dtype=np.uint32))
    return None


# --------------------------------------------------------------------------------- save

# one process-wide async-capable checkpointer: orbax's StandardCheckpointer copies
# device->host synchronously inside save() and runs serialization + disk writes on a
# background thread; reusing one instance lets consecutive saves pipeline
_CHECKPOINTER: ocp.StandardCheckpointer | None = None
# (save_path, iteration, retry_kwargs, keep_last_n) of a started-but-not-yet-committed
# async save; its `latest` pointer is written by finish_pending_checkpoint() once the
# write is durable
_PENDING: tuple[str, int, dict, int | None] | None = None


def _get_checkpointer() -> ocp.StandardCheckpointer:
    global _CHECKPOINTER
    if _CHECKPOINTER is None:
        _CHECKPOINTER = ocp.StandardCheckpointer()
    return _CHECKPOINTER


def finish_pending_checkpoint() -> None:
    """Block until an in-flight async save commits, then advance the `latest` pointer.

    Called at the start of the next save (so at most one save is in flight), at the end of
    training, and before any in-process restore. Crash-safety: the pointer is only written
    after `wait_until_finished` AND an integrity check of the written state dir, so `latest`
    can never name a torn checkpoint — a crash mid-write loses at most the in-flight save,
    never the previous one.
    """
    global _PENDING
    if _PENDING is None:
        return
    save_path, iteration, retry_kwargs, keep_last_n = _PENDING
    _PENDING = None
    retry_io(
        _get_checkpointer().wait_until_finished,
        description="async checkpoint write",
        **retry_kwargs,
    )
    _commit_checkpoint(save_path, iteration, retry_kwargs, keep_last_n)


def _validate_checkpoint(base: str) -> None:
    """Integrity gate before the `latest` pointer may name `base`: the orbax state dir must
    exist and its metadata must be readable (i.e. the checkpoint is restorable-shaped).
    Catches torn/partial writes that a crash or flaky mount left behind."""
    state_path = _state_path(base)
    if not os.path.isdir(state_path):
        raise FileNotFoundError(
            f"checkpoint state dir missing at {state_path} — torn or incomplete save"
        )
    tree = _checkpoint_tree_metadata(os.path.abspath(state_path))
    if tree is None or (hasattr(tree, "__len__") and len(tree) == 0):
        raise ValueError(
            f"checkpoint at {base} has unreadable/empty state metadata — refusing to "
            "advance the latest pointer to it"
        )


def _commit_checkpoint(
    save_path: str, iteration: int, retry_kwargs: dict, keep_last_n: int | None
) -> None:
    """Validate -> advance `latest` -> prune old checkpoints, each with bounded retry."""
    retry_io(
        lambda: _validate_checkpoint(_get_base_path(save_path, iteration)),
        description=f"validate global_step{iteration}",
        **retry_kwargs,
    )
    retry_io(
        lambda: _write_latest(save_path, iteration),
        description=f"write {_LATEST}",
        **retry_kwargs,
    )
    # counted at commit time (not save start): the durable-checkpoint truth, async included
    get_telemetry().count("checkpoints_saved")
    _prune_old_checkpoints(save_path, keep_last_n)


def _prune_old_checkpoints(save_path: str, keep_last_n: int | None) -> None:
    """Retention: keep the newest `keep_last_n` global_step* dirs, NEVER deleting the one
    named by `latest` (which may be older after a rollback-resume). Best-effort — a prune
    failure must not kill training over disk housekeeping."""
    if keep_last_n is None or not _is_primary():
        return
    try:
        latest_iteration = None
        if os.path.isfile(os.path.join(save_path, _LATEST)):
            latest_iteration = _read_latest_iteration(save_path, {"attempts": 1})
        iterations = sorted(
            int(m.group(1))
            for name in os.listdir(save_path)
            if (m := _CHECKPOINT_DIR_RE.fullmatch(name))
            and os.path.isdir(os.path.join(save_path, name))
        )
        keep = set(iterations[-keep_last_n:])
        if latest_iteration is not None:
            keep.add(latest_iteration)
        for iteration in iterations:
            if iteration not in keep:
                shutil.rmtree(_get_base_path(save_path, iteration), ignore_errors=True)
                get_telemetry().count("checkpoints_pruned")
                log_rank_0(
                    logging.INFO,
                    f"pruned checkpoint global_step{iteration} (keep_last_n={keep_last_n})",
                )
    except OSError as error:
        log_rank_0(logging.WARNING, f"checkpoint pruning skipped: {error!r}")


def _write_latest(save_path: str, iteration: int) -> None:
    if _is_primary():
        # tmp + rename: a crash mid-write must never leave a torn pointer file — that would
        # break resume from EVERY checkpoint, not just lose the in-flight one
        target = os.path.join(save_path, _LATEST)
        tmp = target + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"latest_checkpointed_iteration": iteration}, f)
            f.flush()
            os.fsync(f.fileno())  # machine crash: the rename must not survive with torn content
        os.replace(tmp, target)
        # fsync the directory so the rename itself is durable
        dir_fd = os.open(save_path, os.O_RDONLY)
        try:
            os.fsync(dir_fd)
        finally:
            os.close(dir_fd)


def save_checkpoint(
    args: TrainingArgs,
    model,
    state: TrainState,
    train_dataloader,
    experiments_tracker: ExperimentsTracker | None,
    iteration: int,
    metadata: dict | None = None,
    jax_rng: jax.Array | None = None,
) -> None:
    """Save a full training checkpoint (reference `save_checkpoint`, checkpointing.py:50-146)."""
    save_path = args.save_args.save_path
    is_async = bool(getattr(args.save_args, "async_checkpointing", False))
    keep_last_n = getattr(args.save_args, "keep_last_n", None)
    retry_kwargs = _retry_kwargs(args)
    finish_pending_checkpoint()  # at most one save in flight
    base = _get_base_path(save_path, iteration)
    os.makedirs(base, exist_ok=True)

    to_save = state
    if not args.save_args.save_optimizer:
        to_save = TrainState(step=state.step, params=state.params, opt_state=(), fp8=state.fp8)

    checkpointer = _get_checkpointer()
    # labeled scope: in captured traces the checkpoint device->host copy (and the sync wait)
    # shows up under the same name as the goodput bucket
    with get_telemetry().span("checkpoint_save"):
        retry_io(
            lambda: checkpointer.save(os.path.abspath(_state_path(base)), to_save, force=True),
            description=f"start checkpoint save global_step{iteration}",
            **retry_kwargs,
        )
        if not is_async:
            retry_io(
                checkpointer.wait_until_finished,
                description=f"checkpoint write global_step{iteration}",
                **retry_kwargs,
            )

    rng_path = os.path.join(base, f"rng_state-{jax.process_index()}.json")
    with open(rng_path, "w") as f:
        json.dump(get_rng_state(jax_rng), f)

    if train_dataloader is not None:
        dl_dir = os.path.join(base, "dataloader")
        os.makedirs(dl_dir, exist_ok=True)
        with open(os.path.join(dl_dir, f"process-{jax.process_index()}.json"), "w") as f:
            json.dump(train_dataloader.state_dict(), f)

    if _is_primary():
        if experiments_tracker is not None:
            with open(os.path.join(base, "experiments_tracker.json"), "w") as f:
                json.dump(experiments_tracker.state_dict(), f)

        if metadata is not None:
            with open(os.path.join(base, "metadata.json"), "w") as f:
                json.dump(metadata, f)

        save_args(args, base)

    if is_async:
        global _PENDING
        # `latest` advances (and old checkpoints are pruned) once the write commits
        _PENDING = (save_path, iteration, retry_kwargs, keep_last_n)
    else:
        _commit_checkpoint(save_path, iteration, retry_kwargs, keep_last_n)

    log_rank_0(logging.INFO, f"checkpoint saved at {base}" + (" (async)" if is_async else ""))


def save_args(args, base: str, mode: Mode = Mode.training) -> None:
    """Snapshot full args into the checkpoint (reference checkpointing.py:405-416)."""
    if not _is_primary():
        return
    import yaml

    prefix = _TRAINING_CONFIG if mode == Mode.training else "inference_config.yml"
    with open(os.path.join(base, prefix), "w") as f:
        yaml.safe_dump(args.to_dict(), f, sort_keys=False)


# --------------------------------------------------------------------------------- load


def _checkpoint_tree_metadata(state_path: str):
    meta = ocp.StandardCheckpointer().metadata(state_path)
    tree = getattr(meta, "item_metadata", meta)
    return getattr(tree, "tree", tree)


def _tree_subtree_keys(tree, subtree: str) -> list:
    node = tree.get(subtree) if isinstance(tree, dict) else getattr(tree, subtree, None)
    if node is None:
        return []
    return jax.tree.leaves(node, is_leaf=lambda x: hasattr(x, "shape"))


def _partial_restore(state_path: str, abstract_subtree: dict):
    """Restore only the given subtrees of a saved TrainState (orbax partial restore)."""
    checkpointer = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
    try:
        restore_args = ocp.args.PyTreeRestore(item=abstract_subtree, partial_restore=True)
    except TypeError:
        # pre-partial_restore orbax (0.7.x): an empty transforms dict selects the
        # transformation path, where `item`'s structure defines the output and checkpoint
        # keys without a counterpart are dropped — same partial-restore semantics. That
        # path requires explicit per-leaf restore args (sharding/shape/dtype).
        restore_args = ocp.args.PyTreeRestore(
            item=abstract_subtree,
            transforms={},
            restore_args=ocp.checkpoint_utils.construct_restore_args(abstract_subtree),
        )
    return checkpointer.restore(state_path, args=restore_args)


def _zero_schedule_step(opt_state):
    """Reset every schedule step counter (optax ScaleByScheduleState / step counts) to 0."""
    import optax

    def reset(x):
        if isinstance(x, optax.ScaleByScheduleState):
            return optax.ScaleByScheduleState(count=jnp.zeros_like(x.count))
        return x

    return jax.tree.map(reset, opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByScheduleState))


def load_checkpoint_for_training(
    args: TrainingArgs,
    state: TrainState,
    train_dataloader=None,
    experiments_tracker: ExperimentsTracker | None = None,
    iteration: int | None = None,
) -> tuple[TrainState, int, dict | None, jax.Array | None]:
    """Restore training state in place of `state` (same shardings).

    Returns (state, starting_iteration, metadata, jax_rng). Mirrors reference
    `load_checkpoint_for_training` (checkpointing.py:149-263) incl. per-piece toggles.
    """
    load_args = args.load_args
    if load_args is None:
        return state, 0, None, None

    finish_pending_checkpoint()  # an in-flight async save may be the one being restored
    retry_kwargs = _retry_kwargs(args)
    load_path = load_args.load_path
    if iteration is None:
        iteration = load_args.iteration
    if iteration is None:
        iteration = _read_latest_iteration(load_path, retry_kwargs)

    base = _get_base_path(load_path, iteration)

    state_path = os.path.abspath(_state_path(base))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), state
    )
    tree_meta = _checkpoint_tree_metadata(state_path)  # one metadata read serves all probes
    checkpoint_has_optimizer = len(_tree_subtree_keys(tree_meta, "opt_state")) > 0
    # fp8 state may be absent from the checkpoint (bf16 run / pre-fp8 save) or absent from
    # the live state (bf16 resume of an fp8 save) — restore it only when both sides have it
    restore_fp8 = state.fp8 is not None and len(_tree_subtree_keys(tree_meta, "fp8")) > 0

    def _restore_with_retry(fn, what: str):
        return retry_io(fn, description=what, **retry_kwargs)

    if not load_args.load_optimizer:
        # params-only partial restore; keep the freshly-initialized opt_state
        want = {"step": abstract.step, "params": abstract.params}
        if restore_fp8:
            want["fp8"] = abstract.fp8
        restored_sub = _restore_with_retry(
            lambda: _partial_restore(state_path, want), "params-only checkpoint restore"
        )
        restored = TrainState(
            step=restored_sub["step"],
            params=restored_sub["params"],
            opt_state=state.opt_state,
            fp8=restored_sub.get("fp8", state.fp8),
        )
    else:
        if not checkpoint_has_optimizer:
            raise ValueError(
                f"checkpoint at {base} was saved with save_optimizer=False; "
                "resume it with load_args.load_optimizer=false"
            )
        if state.fp8 is None or restore_fp8:
            restored = _restore_with_retry(
                lambda: ocp.StandardCheckpointer().restore(state_path, abstract),
                "full checkpoint restore",
            )
        else:
            # checkpoint has no fp8 subtree: restore the rest, keep the fresh fp8 state
            restored_sub = _restore_with_retry(
                lambda: _partial_restore(
                    state_path,
                    {
                        "step": abstract.step,
                        "params": abstract.params,
                        "opt_state": abstract.opt_state,
                    },
                ),
                "no-fp8 checkpoint restore",
            )
            restored = TrainState(
                step=restored_sub["step"],
                params=restored_sub["params"],
                opt_state=restored_sub["opt_state"],
                fp8=state.fp8,
            )

    # the LR schedule's only state here is the schedule step inside opt_state (optax), so
    # "don't load the lr scheduler" and "don't resume the learning rate" both mean: restore
    # the moments but restart the schedule from step 0
    if load_args.load_optimizer and not (
        load_args.resume_learning_rate and load_args.load_lr_scheduler
    ):
        restored = TrainState(
            step=restored.step,
            params=restored.params,
            opt_state=_zero_schedule_step(restored.opt_state),
            fp8=restored.fp8,
        )

    jax_rng = None
    if load_args.load_rng_state:
        rng_path = os.path.join(base, f"rng_state-{jax.process_index()}.json")
        if not os.path.isfile(rng_path):
            rng_path = os.path.join(base, "rng_state-0.json")
        with open(rng_path) as f:
            jax_rng = set_rng_state(json.load(f))

    if load_args.load_dataloader_state and train_dataloader is not None:
        dl_path = os.path.join(base, "dataloader", f"process-{jax.process_index()}.json")
        if os.path.isfile(dl_path):
            with open(dl_path) as f:
                train_dataloader.load_state_dict(json.load(f))

    if (
        load_args.load_experiments_tracker_state
        and experiments_tracker is not None
        and hasattr(experiments_tracker, "load_state_dict")
    ):
        tracker_path = os.path.join(base, "experiments_tracker.json")
        if os.path.isfile(tracker_path):
            with open(tracker_path) as f:
                experiments_tracker.load_state_dict(json.load(f))

    metadata = None
    metadata_path = os.path.join(base, "metadata.json")
    if os.path.isfile(metadata_path):
        with open(metadata_path) as f:
            metadata = json.load(f)

    starting_iteration = iteration if load_args.load_starting_iteration else 0
    if not load_args.load_starting_iteration:
        restored = TrainState(
            step=jnp.zeros_like(restored.step),
            params=restored.params,
            opt_state=restored.opt_state,
            fp8=restored.fp8,
        )

    log_rank_0(logging.INFO, f"checkpoint loaded from {base}")
    return restored, starting_iteration, metadata, jax_rng


def get_experiments_tracker_checkpoint_metadata(args: TrainingArgs) -> dict:
    """Read the saved tracker resume info (aim run-hash / wandb run-id) so the tracker can be
    constructed resuming the original run (reference tracking.py:131-149 + checkpointing 130-133)."""
    load_args = args.load_args
    if load_args is None or not load_args.load_experiments_tracker_state:
        return {}
    iteration = load_args.iteration
    if iteration is None:
        if not os.path.isfile(os.path.join(load_args.load_path, _LATEST)):
            return {}
        iteration = _read_latest_iteration(load_args.load_path, _retry_kwargs(args))
    tracker_path = os.path.join(
        _get_base_path(load_args.load_path, iteration), "experiments_tracker.json"
    )
    if not os.path.isfile(tracker_path):
        return {}
    with open(tracker_path) as f:
        return json.load(f)


def load_checkpoint_for_inference(
    args: InferenceArgs | UnshardingArgs, mode: Mode, use_meta: bool = False
):
    """Rebuild model from the checkpoint's own training config and restore params replicated.

    Mirrors reference `load_checkpoint_for_inference` (checkpointing.py:266-402): reads the
    saved `training_config.yml`, reconstructs the model wrapper, loads weights. The reference
    needs backend-specific merge paths (DeepSpeed zero-to-fp32, FSDP1 torch.load, dcp no-dist,
    TP unshard + fused-weight fixups); orbax restore with replicated shardings subsumes all.

    Returns (model_wrapper, params, training_args).
    """
    from .model_wrapper import get_model
    from .parallel.mesh import MeshManager

    finish_pending_checkpoint()  # an in-flight async save may be the one being restored
    load_args = args.load_args
    load_path = load_args.load_path
    iteration = load_args.iteration
    if iteration is None:
        iteration = _read_latest_iteration(load_path, _retry_kwargs(args))
    base = _get_base_path(load_path, iteration)

    training_args = args_from_dict(load_yaml(os.path.join(base, _TRAINING_CONFIG)), Mode.training)

    model = get_model(training_args, mode)

    if not MeshManager.is_initialized():
        MeshManager()
    mesh = MeshManager.get_mesh()

    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    # the checkpoint is self-describing: build the abstract params subtree from its metadata
    # and restore ONLY params, replicated (never materializes mu/nu optimizer moments)
    state_path = os.path.abspath(_state_path(base))
    tree_meta = _checkpoint_tree_metadata(state_path)
    params_meta = tree_meta["params"] if isinstance(tree_meta, dict) else tree_meta.params

    def _abstract(m):
        return jax.ShapeDtypeStruct(tuple(m.shape), m.dtype, sharding=replicated)

    abstract_params = jax.tree.map(
        _abstract, params_meta, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
    )
    restored = retry_io(
        lambda: _partial_restore(state_path, {"params": abstract_params}),
        description="inference params restore",
        **_retry_kwargs(args),
    )

    return model, restored["params"], training_args
