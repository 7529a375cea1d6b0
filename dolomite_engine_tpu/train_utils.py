"""Shared training step + metrics + analytic FLOPs model.

Parity: reference `dolomite_engine/train_utils.py` (236 LoC):
  - `train_step` (18-116): grad-accum loop with FSDP no_sync on non-final micro-steps, grad
    clip, loss all-reduce AVG over dp. TPU design: ONE jitted step takes the whole global-step
    batch with a leading [grad_accum] axis and `lax.scan`s over micro-batches accumulating
    fp32 grads — communication "deferral" is automatic (GSPMD reduces once, at use), and the
    loss mean needs no explicit all-reduce (the batch axis is sharded over dp, reductions are
    global under SPMD).
  - metric formatting (119-179) -> `track_train_metrics`.
  - torch profiler factory (182-194) -> `get_profiler_context` using jax.profiler.
  - analytic TFLOPs model (197-236) -> `get_model_tflops` (same formula, checkpoint-aware).
"""

from __future__ import annotations

import logging
from contextlib import contextmanager, nullcontext
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from .models.gpt_dolomite import names_kept_on_device, resolve_remat_policy
from .models.modeling_utils import ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME
from .utils import ExperimentsTracker, get_telemetry, log_rank_0, profiler_call_at_step_boundary
from .utils.diagnostics import per_group_health


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    # fp8 delayed-scaling state (scales + amax histories, ops/fp8.py). None when fp8 is off.
    # Updated by gradient OVERWRITE, never by the optimizer.
    fp8: Any = None


def clip_grad_norm(grads, max_norm: float | None):
    """Global-norm clip; returns (clipped_grads, grad_norm)."""
    leaves = jax.tree.leaves(grads)
    grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    if max_norm is None:
        return grads, grad_norm
    scale = jnp.minimum(1.0, max_norm / (grad_norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads), grad_norm


_CPU_OFFLOAD_WARNED = False


def resolve_cpu_offload(args) -> bool:
    """cpu_offload needs in-jit memory-space transfers — TPU only; warn-and-ignore elsewhere
    (the reference's DeepSpeed cpu_offload is likewise backend-conditional)."""
    if not args.distributed_args.cpu_offload:
        return False
    if jax.default_backend() != "tpu":
        global _CPU_OFFLOAD_WARNED
        if not _CPU_OFFLOAD_WARNED:
            _CPU_OFFLOAD_WARNED = True
            log_rank_0(
                logging.WARNING,
                f"cpu_offload ignored on backend '{jax.default_backend()}' (pinned-host "
                "optimizer streaming requires TPU)",
            )
        return False
    return True


def offload_jit_kwargs(state) -> dict:
    """Extra `jax.jit` kwargs for an offloaded train step: pin the output TrainState to the
    live state's shardings (opt state -> pinned_host) so the update streams back to host.
    Shared by pretrain/finetune (and the offload tests)."""
    return {"out_shardings": (jax.tree.map(lambda x: x.sharding, state), None)}


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    gradient_accumulation_steps: int = 1,
    gradient_clipping: float | None = 1.0,
    rng_per_step: bool = True,
    offload_optimizer: bool = False,
    skip_nonfinite: bool = False,
    collect_health: bool = False,
    has_aux: bool = False,
):
    """Build the jitted train step.

    `loss_fn(params, micro_batch, rng) -> scalar loss` — or, with `has_aux`, ``(loss,
    counters)``: a dict of what the forward pass counted — integer arrays, summed over the
    micro-batches, and float scalars (a loss's parts), averaged over them — returned as
    ``metrics["counters"]`` by the same program. `batch` passed to the returned step has
    a leading [gradient_accumulation_steps] axis on every leaf.

    `offload_optimizer` (cpu_offload, TPU only): the incoming opt state lives in pinned host
    memory — stream it to device for the update; the caller's jit `out_shardings` (the state
    shardings from `create_sharded_train_state`) pin the new opt state back to host.

    `skip_nonfinite` (FaultToleranceArgs.skip_nonfinite_steps): when the loss or the global
    grad-norm is non-finite, a `lax.cond` returns params/opt-state/fp8 UNCHANGED instead of
    poisoning them with NaN updates; `metrics["skipped"]` reports it (0/1) so the loop can
    count consecutive skips and abort past a threshold. `step` still advances — a skipped
    step consumes its batch and keeps host/device step counters aligned.

    `collect_health` (logging_args.telemetry.health.interval > 0): additionally return
    `metrics["health"]` — per-top-level-group grad/param norms and update/param ratios
    (`utils/diagnostics.per_group_health`), computed on device so the host only syncs them
    at the health-record cadence. Off (default) the traced program is bit-identical to the
    pre-health step.
    """

    def train_step(state: TrainState, batch, rng: jax.Array):
        if offload_optimizer:
            state = state.replace(
                opt_state=jax.device_put(state.opt_state, jax.memory.Space.Device)
            )
        use_fp8 = state.fp8 is not None

        def micro_loss(params, fp8_state, micro_batch, micro_rng):
            if use_fp8:
                return loss_fn(params, micro_batch, micro_rng, fp8_state=fp8_state)
            return loss_fn(params, micro_batch, micro_rng)

        # fp8 state is differentiated too: its "gradient" is the NEXT delayed-scaling state
        # (flax overwrite-with-gradient contract, ops/fp8.py) — overwritten, never optimized
        grad_fn = jax.value_and_grad(
            micro_loss, argnums=(0, 1) if use_fp8 else 0, has_aux=has_aux
        )

        # Phase scopes (docs/OBSERVABILITY.md "Phases of the train step"): with the model's
        # own (`embed`, `blocks`, `final_norm`, `head_loss`) they give every operation of
        # the step a phase in a profile; JAX's `transpose(...)` wrapper tells backward from
        # forward. Scopes are metadata: the compiled operations are what they were.
        new_fp8 = state.fp8
        if gradient_accumulation_steps == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            loss, grads = grad_fn(state.params, state.fp8, micro, rng)
            counters = None
            if has_aux:
                loss, counters = loss
            if use_fp8:
                grads, new_fp8 = grads
            with jax.named_scope("grad_clip"):
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:

            def accum_fn(carry, xs):
                grads_acc, loss_acc, fp8_carry = carry
                micro_batch, micro_rng = xs
                # thread the scaling state through the micro-steps so every micro-batch's
                # amax observation enters the history (not just the last one's)
                loss, grads = grad_fn(state.params, fp8_carry, micro_batch, micro_rng)
                micro_counters = None
                if has_aux:
                    loss, micro_counters = loss
                if use_fp8:
                    grads, fp8_carry = grads
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / gradient_accumulation_steps,
                    grads_acc,
                    grads,
                )
                return (
                    grads_acc,
                    loss_acc + loss / gradient_accumulation_steps,
                    fp8_carry,
                ), micro_counters

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            rngs = jax.random.split(rng, gradient_accumulation_steps)
            with jax.named_scope("accumulate"):
                (grads, loss, new_fp8), counters = jax.lax.scan(
                    accum_fn, (zero_grads, jnp.zeros((), jnp.float32), state.fp8), (batch, rngs)
                )
                if has_aux:
                    # counts add up over the micro-batches; a loss's part is their mean, as the loss is
                    counters = jax.tree.map(
                        lambda c: (jnp.sum if jnp.issubdtype(c.dtype, jnp.integer) else jnp.mean)(c, axis=0),
                        counters,
                    )

        with jax.named_scope("grad_clip"):
            grads, grad_norm = clip_grad_norm(grads, gradient_clipping)

        def apply_update(operand):
            grads, opt_state, params, old_fp8, stepped_fp8 = operand
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt_state, stepped_fp8

        operand = (grads, state.opt_state, state.params, state.fp8, new_fp8)
        with jax.named_scope("optimizer"):
            if skip_nonfinite:
                step_ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
                new_params, new_opt_state, new_fp8 = jax.lax.cond(
                    step_ok,
                    apply_update,
                    # identity: params/opt-state flow through untouched and fp8 reverts to
                    # its PRE-step scaling state (the stepped one saw the non-finite amax)
                    lambda operand: (operand[2], operand[1], operand[3]),
                    operand,
                )
            else:
                step_ok = None
                new_params, new_opt_state, new_fp8 = apply_update(operand)

        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state, fp8=new_fp8
        )
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if has_aux:
            metrics["counters"] = counters
        if step_ok is not None:
            metrics["skipped"] = (~step_ok).astype(jnp.int32)
        if collect_health:
            with jax.named_scope("health"):
                metrics["health"] = per_group_health(state.params, grads, new_params)
        return new_state, metrics

    return train_step


def handle_nonfinite_step(
    skipped: bool, consecutive: int, global_step: int, max_consecutive: int
) -> int:
    """Host-side consecutive-skip accounting shared by the pretrain/finetune loops.

    Returns the updated consecutive-skip count; raises RuntimeError once `max_consecutive`
    non-finite steps occur back to back (true divergence or a poisoned data shard — more
    skipping only burns accelerator time)."""
    if not skipped:
        return 0
    consecutive += 1
    get_telemetry().count("nan_skips", event=True, step=global_step)
    log_rank_0(
        logging.WARNING,
        f"non-finite loss/grad-norm at step {global_step}: optimizer update skipped "
        f"({consecutive} consecutive, abort at {max_consecutive})",
    )
    if consecutive >= max_consecutive:
        raise RuntimeError(
            f"aborting: {consecutive} consecutive non-finite training steps (threshold "
            f"fault_tolerance_args.max_consecutive_nonfinite_steps={max_consecutive}) — "
            "loss has diverged or a data shard is poisoned; resume from the last "
            "checkpoint with a lower LR or different data skip"
        )
    return consecutive


def make_eval_step(model):
    """`eval_step(params, batch, fp8_state=None)`: the model's loss with dropout off."""

    def eval_step(params, batch, fp8_state=None):
        loss = model.loss(params, batch, rngs=None, train=False, fp8_state=fp8_state)
        # a family whose forward pass counts returns (loss, counters): `step_counter_names`
        return loss[0] if isinstance(loss, tuple) else loss

    return eval_step


def track_train_metrics(
    global_step: int,
    train_loss_step: float,
    grad_norm: float,
    current_lr: float,
    experiments_tracker: ExperimentsTracker | None,
    loss_running_mean: float,
    flops: float | None = None,
    billion_tokens_per_day: float | None = None,
    step_time: float | None = None,
    mfu: float | None = None,
) -> None:
    """Parity: reference `train_utils.py:119-179` metric names kept identical; `mfu` (percent
    of detected per-device peak, utils/telemetry.py) is reported next to the raw FLOPS."""
    metrics = {
        "loss_step": train_loss_step,
        "loss_running_mean": loss_running_mean,
        "learning_rate": current_lr,
    }
    if grad_norm is not None:
        metrics["grad_norm"] = grad_norm
    if flops is not None:
        metrics["FLOPS"] = flops
    if mfu is not None:
        metrics["MFU (%)"] = mfu
    if billion_tokens_per_day is not None:
        metrics["throughput (B tokens/day)"] = billion_tokens_per_day
    if step_time is not None:
        metrics["step time (sec)"] = step_time

    if experiments_tracker is not None:
        experiments_tracker.track(metrics, step=global_step, context="train")

    message = f"step = {global_step}, " + ", ".join(
        f"{k} = {v:.4g}" if isinstance(v, float) else f"{k} = {v}" for k, v in metrics.items()
    )
    log_rank_0(logging.INFO, message)


# set once the fixed-schedule trace window has been captured (or skipped past) this process
_PROFILER_SCHEDULE_DONE = False


def reset_profiler_schedule() -> None:
    """Re-arm the fixed-schedule profiler (tests; back-to-back train() calls in one process)."""
    global _PROFILER_SCHEDULE_DONE
    _PROFILER_SCHEDULE_DONE = False


@contextmanager
def _whole_steps_trace(trace_path: str, last_outputs):
    """`jax.profiler.trace` around the dispatch of a step, entered once the step before has
    finished on the device and left once this one has: the capture holds whole steps."""
    trace = jax.profiler.trace(trace_path)
    started = profiler_call_at_step_boundary(trace.__enter__, last_outputs(), "start")
    try:
        yield
    finally:
        if started:
            profiler_call_at_step_boundary(
                lambda: trace.__exit__(None, None, None), last_outputs(), "stop"
            )


def get_profiler_context(
    trace_path: str | None,
    global_step: int,
    last_outputs: Callable[[], Any] = lambda: None,
    wait: int = 5,
    active: int = 1,
):
    """jax.profiler trace of ABSOLUTE global steps (wait, wait + active], one-shot per run.

    `last_outputs()` returns the outputs of the newest dispatched step (None before the
    first): the context calls it on entry — the step before — and on exit — the step
    dispatched inside it — and starts and stops the trace only after each is ready
    (`utils/telemetry.profiler_call_at_step_boundary`, shared with the on-demand path).

    The reference torch-profiler schedule (`train_utils.py:182-194`) is wait 5 / warmup 5 /
    active 1; XLA has no warmup notion (the first step compiled already), so the explicit
    schedule here is: skip the first `wait` global steps (compile + cache warmup), then trace
    the next `active` steps. Absolute steps mean a RESUMED run past the window never
    re-captures (the old relative-step schedule re-traced on every resume); the one-shot
    latch makes that guarantee explicit even if the caller's step accounting moves backwards.

    On-demand mid-run captures are the telemetry layer's job
    (`logging_args.telemetry.on_demand_profiling`, utils/telemetry.py) — this context only
    serves the fixed start-of-run schedule.
    """
    global _PROFILER_SCHEDULE_DONE
    if trace_path is None or _PROFILER_SCHEDULE_DONE:
        return nullcontext()
    if global_step > wait + active:  # resumed past the window: never capture this run
        _PROFILER_SCHEDULE_DONE = True
        return nullcontext()
    if wait < global_step <= wait + active and jax.process_index() == 0:
        if global_step == wait + active:
            _PROFILER_SCHEDULE_DONE = True  # window fully traced: one-shot per run
        return _whole_steps_trace(trace_path, last_outputs)
    return nullcontext()


def resolve_checkpointing_args(
    gradient_checkpointing_method, gradient_checkpointing_args: dict | None
) -> tuple[int, str]:
    """Normalize the gradient-checkpointing knobs to ``(checkpoint_every, policy)``.

    One parser for `get_model_tflops`, `estimate_remat_activation_bytes`, and the
    `model_report` remat line, mirroring model_wrapper/base.py's key precedence
    (``checkpoint_every`` | legacy ``block_frequency``; named ``policy`` | legacy
    ``checkpoint_policy``). Remat is active whenever EITHER a method is set or args
    were given — the old reader keyed on the method alone and silently reported
    full-recompute MFU for a `None`-method run that passed args."""
    args = gradient_checkpointing_args or {}
    every = 0
    if gradient_checkpointing_method is not None or args:
        every = max(int(args.get("checkpoint_every", args.get("block_frequency", 1))), 1)
    policy = args.get("policy", args.get("checkpoint_policy")) or "full"
    return every, policy


def _keeps_kernel_residuals(policy: str, config) -> bool:
    """Whether `policy` keeps the attention kernel's output and log-sum-exp in this family's
    stack, asked of the policy the stack itself resolves (a stack that applies its blocks more
    than once a step keeps nothing under ``full``)."""
    applications_per_block = getattr(config, "block_applications", config.n_layer) // config.n_layer
    return ATTENTION_KERNEL_RESIDUALS_CHECKPOINT_NAME in names_kept_on_device(
        resolve_remat_policy(policy, applications_per_block)
    )


def get_model_tflops(
    config,
    batch_size: int,
    sequence_length: int,
    gradient_checkpointing_method=None,
    gradient_checkpointing_args: dict | None = None,
    attention_kernel: bool = False,
) -> float:
    """Analytic model TFLOPs per step per device-group (reference `train_utils.py:197-236`):
    attn = 4bsh(h(1+k/n) + s), mlp = 4bshf (+2bshf GLU), lm_head = 6bshv, bwd = 2x fwd.
    A family that applies its blocks or reads its head more than once a step says how often
    (`block_applications`, `head_readings` of its config: `OuroConfig`, a looped model's
    passes x blocks and passes); without them a block and the head count once, as they did.

    The recompute term is derived from the SELECTED remat policy, not just
    `checkpoint_every`: ``full`` adds one forward per checkpointed block — less, where
    attention lowers through the Pallas kernel (``attention_kernel``:
    `ops.attention.splash_expected`) and the stack keeps the kernel's residuals, the score
    and value products, which the backward pass then does not run again —,
    ``save_dots``/``offload_dots`` add ~0 (only elementwise ops replay),
    ``save_attention_out`` discounts the saved out-projection dot — so reported MFU
    tracks the actual recompute a policy buys instead of flattering partial-remat runs.
    A family whose layers attend under a window counts that layer's score and value products
    over ``min(s, window)`` keys a token (`AfmoeConfig.forward_block_flops`).
    """
    from .ops.activations import is_glu

    b = batch_size
    s = sequence_length
    h = config.n_embd
    f = config.n_inner
    n = config.n_head
    k = config.num_key_value_heads
    v = config.vocab_size
    l = getattr(config, "block_applications", config.n_layer)

    attention_flops = 4 * b * s * h * (h * (1 + k / n) + s)
    product_flops = 4 * b * s * h * s  # the score and value products, of `attention_flops`
    mlp_flops = 4 * b * s * h * f
    if is_glu(config.activation_function):
        mlp_flops += 2 * b * s * h * f
    # MoE: each token runs num_experts_per_tok expert MLPs; DenseMoE runs ONE wide MLP of
    # num_experts * n_inner for every token (models/dense_moe.py:74). The reference formula
    # predates its MoE models and counts a single n_inner MLP; this keeps dense configs
    # bit-identical and makes MoE MFU honest — router FLOPs (bshE) are negligible and
    # left out.
    active_experts = getattr(config, "num_experts_per_tok", None)
    if getattr(config, "model_type", None) == "dense_moe":
        active_experts = config.num_experts
    if active_experts:
        mlp_flops *= active_experts

    if hasattr(config, "forward_block_flops"):
        # a family whose block is not attention + MLP counts its own blocks, all of them at
        # once, under this function's conventions (`NemotronHConfig.forward_block_flops`)
        attention_flops, mlp_flops, l = config.forward_block_flops(b, s), 0.0, 1
        product_flops = config.attention_product_flops(b, s)

    forward = l * (attention_flops + mlp_flops)
    backward = 2 * forward

    every, policy = resolve_checkpointing_args(
        gradient_checkpointing_method, gradient_checkpointing_args
    )
    recompute = 0.0
    if every:
        block = attention_flops + mlp_flops
        dots_saved = {
            "save_dots",
            "offload_dots",
            "dots_saveable",
            "checkpoint_dots",
            "dots_with_no_batch_dims_saveable",
            "checkpoint_dots_with_no_batch_dims",
            "everything_saveable",
        }
        if policy in dots_saved:
            # every dot output saved (or host-parked): only elementwise ops replay,
            # which this matmul-FLOPs model counts as ~0
            block_recompute = 0.0
        elif policy == "save_attention_out":
            # both sublayers still replay their internal dots for their own VJPs; the
            # saved attention out-projection (a plain h -> h dot = 4bsh*h under the
            # forward formula's conventions) is the dot that never re-executes
            block_recompute = block - 4 * b * s * h * h
        else:  # "full", nothing_saveable, and conservative fallback for raw names
            block_recompute = block
            if attention_kernel and policy == "full" and _keeps_kernel_residuals(policy, config):
                block_recompute -= product_flops
        recompute = l * block_recompute / max(every, 1)

    lm_head = 6 * b * s * h * v * getattr(config, "head_readings", 1)

    return (forward + backward + recompute + lm_head) / 1e12


def estimate_remat_activation_bytes(
    config,
    batch_size: int,
    sequence_length: int,
    gradient_checkpointing_method=None,
    gradient_checkpointing_args: dict | None = None,
    dtype_bytes: int = 4,
    attention_kernel: bool = False,
) -> dict:
    """Analytic per-replica estimate of the activation bytes each remat policy keeps
    live between forward and backward, and the delta vs the ``full`` policy.

    ``attention_kernel``: attention lowers through the Pallas kernel
    (`ops.attention.splash_expected`). Its score and context products are then no dots and
    no policy sees them; ``full``, ``save_dots`` and ``offload_dots`` keep, by name, the
    kernel's output and the rows' float32 log-sum-exp on the device (what the ``remat_plan``
    telemetry event counts a block and row; ``full`` only in a stack that applies its blocks
    once a step), the raw ``dots_saveable`` and ``nothing_saveable`` keep neither.

    Counts only what the policy SAVES (block-boundary carries plus the policy's
    selected residuals per checkpointed block); XLA scratch, attention workspace, and
    the non-checkpointed blocks' transients are workload-dependent and excluded, so
    treat the numbers as a floor and the DELTA — what choosing this policy costs or
    buys relative to ``full`` — as the robust signal. Rendered by `tools/doctor.py`
    and the ``model_report`` record next to the state-HBM estimate.
    """
    every, policy = resolve_checkpointing_args(
        gradient_checkpointing_method, gradient_checkpointing_args
    )
    b, s, h = batch_size, sequence_length, config.n_embd
    f = config.n_inner
    n = config.n_head
    kvh = config.num_key_value_heads
    # (a looped model keeps the input of every APPLICATION of a block: `OuroConfig`)
    l = getattr(config, "block_applications", config.n_layer)

    token_bytes = b * s * dtype_bytes
    boundary = l // max(every, 1) * token_bytes * h if every else l * token_bytes * h
    # the keys a query's kept scores span, a layer's mean: a layer under a window keeps a band of
    # `min(s, window)` (a family whose layers differ by mask says so a layer: `AfmoeConfig.layer_window`)
    keys = s
    if hasattr(config, "layer_window"):
        keys = sum(min(s, config.layer_window(i) or s) for i in range(config.n_layer)) / config.n_layer
    # the gate on attention's output is one more projection as wide as the heads' output
    gate_width = n * config.head_dim if getattr(config, "attention_output_gate", False) else 0

    kept_on_device = kept_under_full = per_block_extra = 0.0
    if every and attention_kernel:
        # the kernel's output [b, n, s, head] and log-sum-exp [b, n, s] float32, a checkpointed block
        # that attends (a family whose blocks do not all attend says how many do: `attention_blocks`)
        kernel_residuals = getattr(config, "attention_blocks", l) // every * (
            b * s * n * (getattr(config, "v_head_dim", config.head_dim) * dtype_bytes + 4)
        )
        kept_on_device = kernel_residuals if _keeps_kernel_residuals(policy, config) else 0.0
        kept_under_full = kernel_residuals if _keeps_kernel_residuals("full", config) else 0.0
    if every:
        if policy in ("save_dots", "offload_dots") or ("saveable" in policy and policy != "nothing_saveable"):
            # every dot output: fused qkv + attention scores + context + out proj +
            # c_fc (2f for GLU) + c_proj
            glu = 2 if "glu" in str(config.activation_function) else 1
            per_block_extra = token_bytes * (
                h * (1 + 2 * kvh / n)  # qkv projection output
                + (0 if attention_kernel else n * keys + h)  # scores [b, n, s, keys] + context
                + gate_width  # the gate's projection output (a config with `attention_output_gate`)
                + 2 * h  # attention out proj + mlp c_proj
                + glu * f  # c_fc output
            )
        elif policy == "save_attention_out":
            per_block_extra = token_bytes * h
    checkpointed_blocks = (l // max(every, 1)) if every else 0
    extra = checkpointed_blocks * per_block_extra

    # offload parks the saved dots in pinned host memory: device HBM sees only the
    # boundaries and the kernel's residuals, the host pays `extra`
    device_bytes = boundary + kept_on_device + (0.0 if policy == "offload_dots" else extra)
    host_bytes = extra if policy == "offload_dots" else 0.0
    full_bytes = float(boundary + kept_under_full)  # the boundaries, and the kernel's residuals

    return {
        "checkpoint_every": every,
        "policy": policy,
        "activation_bytes_per_replica": float(device_bytes),
        "host_offload_bytes_per_replica": float(host_bytes),
        "delta_vs_full_bytes": float(device_bytes - full_bytes),
    }
