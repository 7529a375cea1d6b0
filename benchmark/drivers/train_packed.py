"""Driver ``train_packed``: the trainer's own loop (``pretrain.main``) over a packed corpus.

Everything timed is the program's: ``pretrain.main`` builds the model, the mesh, the
optimizer, the sharded state, the Megatron loaders and the ``StepPrefetcher``, and runs its
loop. The benchmark stands at four seams of ``dolomite_engine_tpu.pretrain`` (module
attributes the loop looks up when it runs; the program has no option for any of this and
gets none):

- ``create_sharded_train_state``: the state's parameters are replaced by the benchmark's
  seeded weights (``benchmark/weights.py``), placed as the program placed its own;
- ``StepPrefetcher``: the first batches are copied to the host for the reference;
- ``save_checkpoint`` (``save_interval`` 1): never writes; it is where the loop hands out
  its state, read after step 1 (the optimizer's second moment gives the first gradient's
  per-leaf norms) and after the last checked step (the parameters' change);
- ``track_train_metrics`` (``log_interval`` 1): the benchmark's clock — one reading a step,
  after the loss reached the host — and the end of the window (a preemption request, which
  is how the loop is told to stop; its final checkpoint is the no-op above).

One object — the loop's compiled step with its state — runs the checked steps, the rest of
the warm-up and the window.
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

from benchmark import compare, traffic as traffic_lib, weights as W
from benchmark.program_layout import TINY, leaves_by_name, unrolled_program_tree
from benchmark.reduce_trace import reduce_trace
from benchmark.harness import Check, RunResult, fullest_memory_stats, say

def model_config(ctx) -> dict:
    cfg = dict(ctx.cell.config["pretrained_config"])
    cfg["n_layer"] = ctx.cell.config["train"]["n_layer"]
    if ctx.tiny:
        kv_ratio = cfg["n_head"] // (cfg.get("num_key_value_heads") or cfg["n_head"])
        cfg.update(TINY)
        if kv_ratio > 1:
            cfg["num_key_value_heads"] = max(cfg["n_head"] // kv_ratio, 1)
    return cfg


class Seams:
    """The benchmark's hands on ``pretrain``'s module attributes for one run."""

    def __init__(self, ctx, cfg: dict, optimizer: dict):
        self.ctx, self.cfg, self.optimizer = ctx, cfg, optimizer
        self.traffic = ctx.cell.traffic
        self.warmup = self.traffic["warmup_steps"]
        self.check_steps = self.traffic["check_steps"]
        self.batches: list = []  # host copies of the first check_steps batches
        self.clock: list = []  # (step, perf_counter, loss) at every step's end
        self.grad_norms: dict | None = None
        self.delta_norms: dict | None = None
        self.make_params = None  # jitted: () -> the seeded weights in the program's layout
        self.scanned = False
        self.window_open_t: float | None = None
        self.trace_window: list = []  # [t_start, t_stop] on the host clock
        self.trace_dir = os.path.join(ctx.out_dir, "trace")
        self.stopped = False

    # -- create_sharded_train_state
    def creating(self, original):
        import jax
        import jax.numpy as jnp

        from dolomite_engine_tpu.models.gpt_dolomite import scan_group_size, stack_block_params

        cfg, seed = self.cfg, self.ctx.seed

        def create(model, optimizer, mesh, rng, **kwargs):
            self.ctx.mark("model, mesh and optimizer built")
            state, shardings = original(model, optimizer, mesh, rng, **kwargs)
            self.ctx.mark("the program's state created")
            self.scanned = "h_scan" in state.params["transformer"]
            remat = self.ctx.cell.config["train"]["training_args"]["distributed_args"]
            every = (remat.get("gradient_checkpointing_args") or {}).get("checkpoint_every", 0)
            group = scan_group_size(cfg["n_layer"], every) if self.scanned else 1

            def make(key):
                tree = unrolled_program_tree(W.make_all(cfg, key, jnp.float32))
                return stack_block_params(tree, cfg["n_layer"], group) if self.scanned else tree

            key = W.base_key(seed)
            have = jax.eval_shape(make, key)
            shapes = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]  # noqa: E731
            if jax.tree.structure(state.params) != jax.tree.structure(have) or shapes(state.params) != shapes(have):
                raise RuntimeError(
                    "the benchmark's weights do not fit the program's parameter tree:\n"
                    f"program {jax.tree.map(lambda x: x.shape, state.params)}\nbenchmark {have}"
                )
            made = jax.jit(make, out_shardings=jax.tree.map(lambda x: x.sharding, state.params))
            self.make_params = lambda: made(key)  # the seed is an argument: one program for all
            state = state.replace(params=jax.block_until_ready(self.make_params()))
            self.ctx.mark("the seeded weights put in its place")
            return state, shardings

        return create

    # -- StepPrefetcher
    def prefetching(self, original):
        seams = self

        class Recording(original):
            def __next__(self):
                batch = super().__next__()
                if len(seams.batches) < seams.check_steps:
                    seams.batches.append(np.asarray(batch["text"]))
                return batch

        return Recording

    # -- save_checkpoint: the loop hands out its state here after every step
    def probing(self, args, model, state, *rest, **kwargs):
        import jax
        import jax.numpy as jnp

        step = rest[2] if len(rest) > 2 else kwargs.get("global_step")
        if step == 1:
            adam = [s for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "nu")) if hasattr(s, "nu")][0]
            b2 = self.optimizer["betas"][1]
            sums = self._leaf_sums(adam.nu, square=False)
            self.grad_norms = {k: math.sqrt(max(v, 0.0) / (1 - b2)) for k, v in sums.items()}
        if step == self.check_steps:
            delta = jax.jit(lambda p, p0: jax.tree.map(jnp.subtract, p, p0), donate_argnums=(1,))(
                state.params, self.make_params()
            )
            self.delta_norms = {k: math.sqrt(v) for k, v in self._leaf_sums(delta, square=True).items()}
            del delta

    def _leaf_sums(self, tree, square: bool) -> dict:
        """Per-leaf (and, for a scanned stack, per-layer) sums of the values or their squares."""
        import jax
        import jax.numpy as jnp

        from dolomite_engine_tpu.models.gpt_dolomite import unstack_block_params

        def reduce(path, x):
            x = x.astype(jnp.float32)
            x = jnp.square(x) if square else x
            stacked = any(getattr(p, "key", None) == "h_scan" for p in path)
            return jnp.sum(x, axis=tuple(range(1, x.ndim))) if stacked else jnp.sum(x)

        reduced = jax.jit(lambda t: jax.tree_util.tree_map_with_path(reduce, t))(tree)
        if self.scanned:
            reduced = unstack_block_params(reduced, self.cfg["n_layer"])
        return {k: float(v) for k, v in leaves_by_name(jax.device_get(reduced)).items()}

    # -- track_train_metrics: the clock
    def tracking(self, original):
        import jax

        from dolomite_engine_tpu.utils.fault_tolerance import request_preemption

        trace = self.traffic["trace"]

        def track(*args, **kwargs):
            now = time.perf_counter()
            step = kwargs["global_step"]
            self.clock.append((step, now, float(kwargs["train_loss_step"])))
            if step <= self.warmup:
                self.ctx.mark(f"step {step} done")
            if step == self.warmup:
                self.window_open_t = now
                self.ctx.compiles.open()
            if self.ctx.trace and self.window_open_t is not None:
                if step == self.warmup + trace["skip_steps"]:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0  # python frames slow the host they measure
                    jax.profiler.start_trace(self.trace_dir, profiler_options=options)
                    self.trace_window.append(time.perf_counter())
                elif step == self.warmup + trace["skip_steps"] + trace["steps"]:
                    self.trace_window.append(time.perf_counter())
                    jax.profiler.stop_trace()
            traced = not self.ctx.trace or len(self.trace_window) == 2
            if self.window_open_t is not None and now - self.window_open_t >= self.ctx.seconds and traced:
                if not self.stopped:
                    self.stopped = True
                    self.ctx.compiles.close()
                    request_preemption()
            return original(*args, **kwargs)

        return track


def build_training_args(ctx, cfg: dict, corpus_prefix: str, num_steps: int):
    import copy

    from dolomite_engine_tpu.arguments import TrainingArgs

    args = copy.deepcopy(ctx.cell.config["train"]["training_args"])
    args["model_args"]["pretrained_config"] = cfg
    args["datasets"][0]["class_args"].update(
        data_path=[corpus_prefix],
        data_cache_path=os.path.join(ctx.out_dir, "data_cache"),
        sequence_length=cfg["n_positions"],
    )
    args["training_parameters"]["num_training_steps"] = num_steps
    args["save_args"]["save_path"] = os.path.join(ctx.out_dir, "ckpt")
    args["random_args"] = {"seed": ctx.seed % (2**31 - 1)}  # numpy and PRNGKey take 32 bits
    if ctx.tiny:
        args["optimizer_args"]["class_args"]["lr"] = 1e-3  # toy widths move slower
    return TrainingArgs(**args)


def run(ctx) -> RunResult:
    import jax

    from dolomite_engine_tpu import pretrain
    from dolomite_engine_tpu.data.megatron import MMapIndexedDatasetBuilder
    from dolomite_engine_tpu.parallel.mesh import MeshManager
    from dolomite_engine_tpu.utils.fault_tolerance import reset_preemption

    ctx.mark("the trainer imported")
    traffic, config = ctx.cell.traffic, ctx.cell.config
    cfg = model_config(ctx)
    train = config["train"]["training_args"]
    sequence_length = cfg["n_positions"]
    chips = ctx.cell.chips
    dp_world = chips // train["distributed_args"]["tensor_parallel_size"]
    rows = train["training_parameters"]["micro_batch_size"] * dp_world
    accumulation = train["training_parameters"]["gradient_accumulation_steps"]
    tokens_per_step = rows * accumulation * sequence_length
    num_steps = traffic["warmup_steps"] + int(math.ceil(ctx.seconds * (500 if ctx.tiny else traffic["max_steps_per_second"]))) + 2
    optimizer = dict(train["optimizer_args"]["class_args"], gradient_clipping=train["training_parameters"]["gradient_clipping"])
    if ctx.tiny:
        optimizer["lr"] = 1e-3
    counts = W.count_parameters(cfg)
    say(
        f"train: n_layer {cfg['n_layer']} (published {config['pretrained_config']['n_layer']}), "
        f"{counts['total'] / 1e6:.0f}M parameters, {rows} row(s) x {accumulation} x {sequence_length} tokens a step, "
        f"{chips} chip(s), up to {num_steps} steps"
    )

    corpus = os.path.join(ctx.out_dir, "corpus")
    documents = traffic_lib.write_packed_corpus(
        corpus, traffic, ctx.seed, cfg["vocab_size"], cfg["eos_token_id"],
        num_tokens=(num_steps + 2) * rows * accumulation * (sequence_length + 1),
        builder_cls=MMapIndexedDatasetBuilder,
    )
    say(f"train: corpus of {documents} documents written from the seed")

    seams = Seams(ctx, cfg, optimizer)
    ctx.mark("corpus written")
    names = ("create_sharded_train_state", "StepPrefetcher", "save_checkpoint", "track_train_metrics")
    saved = {name: getattr(pretrain, name) for name in names}
    pretrain.create_sharded_train_state = seams.creating(saved["create_sharded_train_state"])
    pretrain.StepPrefetcher = seams.prefetching(saved["StepPrefetcher"])
    pretrain.save_checkpoint = seams.probing
    pretrain.track_train_metrics = seams.tracking(saved["track_train_metrics"])
    MeshManager.destroy()
    reset_preemption()
    try:
        pretrain.main(args=build_training_args(ctx, cfg, corpus, num_steps))
    finally:
        for name, value in saved.items():
            setattr(pretrain, name, value)
        MeshManager.destroy()
        reset_preemption()
        ctx.compiles.close()
    memory_stats = fullest_memory_stats(jax.devices())
    gc.collect()  # the train state is unreferenced now; the reference needs its room

    # ---- the window: steps after the warm-up, between the benchmark's clock readings
    clock = seams.clock
    opened = next(i for i, (step, _, _) in enumerate(clock) if step == seams.warmup)
    measured = clock[opened + 1 :]
    if not seams.stopped or len(measured) < 2:
        raise RuntimeError(f"the window did not close inside {num_steps} steps: raise max_steps_per_second")
    wall = measured[-1][1] - clock[opened][1]
    tokens_per_s_per_chip = len(measured) * tokens_per_step / wall / chips
    setup_s = clock[opened][1] - ctx.process_start
    losses = [loss for _, _, loss in clock]
    failed = sum(1 for _, _, loss in measured if not math.isfinite(loss))
    step_times = np.diff([t for _, t, _ in clock[opened:]])
    say(
        f"train: set-up {setup_s:.2f} s; window {wall:.3f} s, {len(measured)} steps, "
        f"{tokens_per_s_per_chip:.1f} tokens/s/chip; step median {np.median(step_times) * 1e3:.2f} ms, "
        f"min {step_times.min() * 1e3:.2f}, max {step_times.max() * 1e3:.2f}; "
        f"loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f}"
    )

    # ---- correct: the reference follows the first steps from the same seed and batches
    telemetry = read_telemetry(os.path.join(ctx.out_dir, "ckpt"))
    t0 = time.perf_counter()
    from benchmark.reference import gpt_dense

    reference = gpt_dense.train_steps(cfg, ctx.seed, [b[0] for b in seams.batches], optimizer)
    say(f"train: reference followed {len(seams.batches)} steps in {time.perf_counter() - t0:.1f} s (not set-up, not window)")
    checks = compare_with_reference(
        losses[: seams.check_steps], seams.grad_norms, seams.delta_norms, reference, ctx.cell.limits
    )
    if ctx.control:
        # the control: the reference itself in fp8, put in the program's place
        control = gpt_dense.train_steps(cfg, ctx.seed, [b[0] for b in seams.batches], optimizer, quant="fp8")
        for check in compare_with_reference(
            control["losses"], control["grad_norms"], control["delta_norms"], reference, ctx.cell.limits
        ):
            checks.append(Check("control_" + check.name, check.value, check.limit, not check.ok, "(the control should exceed the limit) " + check.note))
    tail = float(np.mean(losses[-5:]))
    checks.append(Check("loss_after_window_minus_first", tail - losses[0], 0.0, tail < losses[0]))
    checks.append(Check("nonfinite_losses", sum(not math.isfinite(x) for x in losses), 0, all(map(math.isfinite, losses))))

    result = RunResult(
        attempted=len(measured),
        failed=failed,
        end_to_end={"train_tokens_per_s_per_chip": tokens_per_s_per_chip, "setup_s": setup_s},
        checks=checks,
        memory_stats=memory_stats,
        telemetry=telemetry,
        facts=dict(
            cfg=cfg, tokens_per_step=tokens_per_step, sequence_length=sequence_length, rows=rows * accumulation,
            chips=chips, steps=len(measured), wall_s=wall, rate_steps=len(measured), rate_wall_s=wall, first_measured_step=seams.warmup + 1,
            last_measured_step=measured[-1][0], clock=clock,
        ),
    )
    if ctx.trace:
        window_s = seams.trace_window[1] - seams.trace_window[0]
        result.trace = reduce_trace(seams.trace_dir, window_s)
        first = seams.warmup + traffic["trace"]["skip_steps"] + 1
        # the rate of the traced steps alone: starting the profiler stalls the loop for a second
        result.facts.update(traced_steps=traffic["trace"]["steps"], traced_first_step=first, traced_window_s=window_s,
                            rate_steps=traffic["trace"]["steps"], rate_wall_s=window_s)
        say(f"train: traced {traffic['trace']['steps']} steps in {window_s:.3f} s; programs {result.trace.program_names()}")
    return result


def read_telemetry(save_path: str) -> list:
    import json

    path = os.path.join(save_path, "telemetry", "rank-00000.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


def compare_with_reference(losses, grad_norms, delta_norms, reference: dict, limits: dict) -> list:
    """The numbers of ``correct`` for a training cell, each beside its limit."""
    checks = []
    limit = limits.get("loss_gap", math.nan)
    for i, (mine, ref) in enumerate(zip(losses, reference["losses"])):
        gap = abs(mine - ref)
        checks.append(Check(f"loss_gap_step{i + 1}", gap, limit, gap <= limit, f"(program {mine:.5f}, reference {ref:.5f})"))
    # The first gradient is compared in two numbers. The blocks' leaves by the worst leaf: the
    # lower precision shows there. The embedding (tied head) apart: its gradient is a
    # scatter-add of thousands of bf16 rows — a token that fills a tenth of the batch sums
    # hundreds of them — and the program's norm of it falls short of float32's by up to a
    # hundredth, batch by batch, which no change of the matmuls' precision moves.
    def without_wte(norms):
        return {k: v for k, v in norms.items() if k != "wte"}

    pairs = [("param_change_norm_worst_leaf_gap", delta_norms, reference["delta_norms"])]
    if grad_norms is not None:
        pairs = [
            ("first_grad_norm_worst_block_leaf_gap", without_wte(grad_norms), without_wte(reference["grad_norms"])),
            ("first_grad_norm_wte_gap", {"wte": grad_norms["wte"]}, {"wte": reference["grad_norms"]["wte"]}),
        ] + pairs
    else:
        pairs = [("first_grad_norm_worst_block_leaf_gap", None, None), ("first_grad_norm_wte_gap", None, None)] + pairs
    for name, mine, ref in pairs:
        if mine is None:
            checks.append(Check(name, math.inf, limits.get(name, math.nan), False, "(the loop never handed out its state)"))
            continue
        gap, where = compare.worst_leaf_gap(mine, ref)
        limit = limits.get(name, math.nan)
        checks.append(Check(name, gap, limit, gap <= limit, f"(at {where}: program {mine.get(where)}, reference {ref.get(where)})"))
    return checks
