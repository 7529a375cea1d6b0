"""Driver ``train_packed_tower``: ``train_packed`` for a configuration that is not the dense GPT.

The same run — ``pretrain.main`` over a packed corpus, the benchmark standing at the same
four seams of ``dolomite_engine_tpu.pretrain`` (``drivers/train_packed.py`` says which and
why), one object running the checked steps, the rest of the warm-up and the window, the
same ``facts``, the same three checked steps, the same last line. What differs is named by
the configuration's file, not by this driver:

    "benchmark_modules": {"weights": "benchmark.weights_nemotron_h",
                          "reference": "benchmark.reference.nemotron_h_tower"}

- the weights module gives ``base_key``, ``make_all(cfg, key, dtype)``,
  ``count_parameters(cfg)["total"]``, ``unrolled_program_tree(weights, cfg)`` (the
  benchmark's weights in the program's parameter tree) and ``leaves_by_name(tree)``;
- the reference module gives ``train_steps(cfg, seed, batches, optimizer, quant=None)`` ->
  ``losses``, ``grad_norms``, ``delta_norms`` (and, for a model with experts, ``routing``);
- ``"tiny"`` in the file are the toy widths of the CPU rehearsal (``--tiny``);
- ``"layer_metrics_without_an_entry"`` (optional) names readers under ``layer_metrics/`` that
  ``BENCHMARK.json`` has no entry for yet: a traced run prints what they read to its log.

A further non-dense configuration reuses this driver by bringing those two modules, its
counts (``flops_<family>.py``, read by its own ``mfu`` metric) and a configuration file that
names them; a model that is trained unrolled needs nothing else. The model runs unrolled
(``scan_layers: false``), so the seeded weights go in as they are.

Beside the dense cells' comparisons this driver compares, for a model whose step returns
``step_counters``: the rows each held expert got in the checked steps against the
reference's count (``routed_rows_histogram_gap``: half the summed difference over the
routed rows, the least share of slots that went to another expert), and prints the
reference's own estimate of how many top-k choices bfloat16 rounding of the router's input
moves (``router_choices_moved_share``). The first gradient is compared in three groups —
the embedding apart (as in the dense cells), the routed experts' banks and routers apart
(a slot routed elsewhere moves them and nothing else), everything else by the worst leaf.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import time

import numpy as np

from benchmark import compare, traffic as traffic_lib
from benchmark.drivers.train_packed import Seams, build_training_args, read_telemetry
from benchmark.harness import Check, RunResult, fullest_memory_stats, say
from benchmark.reduce_trace import reduce_trace

ROUTED_LEAVES = ("gate", "c_fc", "c_proj")  # a layer of experts' router and banks


def modules_of(config: dict):
    names = config["benchmark_modules"]
    return importlib.import_module(names["weights"]), importlib.import_module(names["reference"])


def model_config(ctx) -> dict:
    cfg = dict(ctx.cell.config["pretrained_config"])
    if ctx.tiny:
        cfg.update(ctx.cell.config["tiny"])
    return cfg


class TowerSeams(Seams):
    """``Seams`` with the weights and the parameter tree of the configuration's own module."""

    def __init__(self, ctx, cfg: dict, optimizer: dict, weights_module):
        super().__init__(ctx, cfg, optimizer)
        self.W = weights_module

    def creating(self, original):
        import jax
        import jax.numpy as jnp

        cfg, W = self.cfg, self.W

        def create(model, optimizer, mesh, rng, **kwargs):
            self.ctx.mark("model, mesh and optimizer built")
            state, shardings = original(model, optimizer, mesh, rng, **kwargs)
            self.ctx.mark("the program's state created")

            def make(key):
                return W.unrolled_program_tree(W.make_all(cfg, key, jnp.float32), cfg)

            key = W.base_key(self.ctx.seed)
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

    def _leaf_sums(self, tree, square: bool) -> dict:
        import jax
        import jax.numpy as jnp

        def reduce(x):
            x = x.astype(jnp.float32)
            return jnp.sum(jnp.square(x) if square else x)

        reduced = jax.device_get(jax.jit(lambda t: jax.tree.map(reduce, t))(tree))
        return {k: float(v) for k, v in self.W.leaves_by_name(reduced).items()}


def run(ctx) -> RunResult:
    import jax

    from dolomite_engine_tpu import pretrain
    from dolomite_engine_tpu.data.megatron import MMapIndexedDatasetBuilder
    from dolomite_engine_tpu.parallel.mesh import MeshManager
    from dolomite_engine_tpu.utils.fault_tolerance import reset_preemption

    ctx.mark("the trainer imported")
    traffic, config = ctx.cell.traffic, ctx.cell.config
    W, reference_module = modules_of(config)
    cfg = model_config(ctx)
    from dolomite_engine_tpu.models import get_config_class

    get_config_class(cfg["model_type"])  # a program without the family fails here, before any work
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
    say(
        f"train: pattern {cfg.get('hybrid_override_pattern')} ({cfg['n_layer']} layers), "
        f"{W.count_parameters(cfg)['total'] / 1e6:.0f}M parameters, {rows} row(s) x {accumulation} x {sequence_length} tokens a step, "
        f"{chips} chip(s), up to {num_steps} steps"
    )

    corpus = os.path.join(ctx.out_dir, "corpus")
    documents = traffic_lib.write_packed_corpus(
        corpus, traffic, ctx.seed, cfg["vocab_size"], cfg["eos_token_id"],
        num_tokens=(num_steps + 2) * rows * accumulation * (sequence_length + 1),
        builder_cls=MMapIndexedDatasetBuilder,
    )
    say(f"train: corpus of {documents} documents written from the seed")

    seams = TowerSeams(ctx, cfg, optimizer, W)
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
    telemetry = read_telemetry(os.path.join(ctx.out_dir, "ckpt"))
    for record in telemetry:
        if record.get("kind") == "event" and record.get("event") == "model_layout":
            say(f"train: model_layout {record}")
    routed = np.asarray([
        r["routed_slots"] for r in telemetry
        if r.get("kind") == "event" and r.get("event") == "step_counters" and r.get("step", 0) > seams.warmup
    ])
    if routed.size:
        say(
            f"train: token-slots routed to the experts held here, a layer of experts, over the window's {len(routed)} steps: "
            f"mean {routed.mean(axis=0).round().astype(int).tolist()}, most {routed.max(axis=0).tolist()} "
            f"(even share {tokens_per_step * cfg['num_experts_per_tok'] * W.model_dims(cfg)['held'] // cfg['num_experts']})"
        )

    checks = []
    if not ctx.skip_check:
        # ---- correct: the reference follows the first steps from the same seed and batches
        t0 = time.perf_counter()
        batches = [b[0] for b in seams.batches]
        reference = reference_module.train_steps(cfg, ctx.seed, batches, optimizer)
        say(f"train: reference followed {len(batches)} steps in {time.perf_counter() - t0:.1f} s (not set-up, not window)")
        counters = {r["step"]: r for r in telemetry if r.get("kind") == "event" and r.get("event") == "step_counters"}
        program_rows = [counters[s + 1].get("held_expert_rows") if s + 1 in counters else None for s in range(seams.check_steps)]
        checks = compare_with_reference(
            losses[: seams.check_steps], seams.grad_norms, seams.delta_norms, program_rows, reference, ctx.cell.limits
        )
        if ctx.control:
            # the control: the reference itself in fp8, put in the program's place
            control = reference_module.train_steps(cfg, ctx.seed, batches, optimizer, quant="fp8")
            control_rows = [r.get("held_expert_rows") for r in control.get("routing", [])] or [None] * len(batches)
            for check in compare_with_reference(
                control["losses"], control["grad_norms"], control["delta_norms"], control_rows, reference, ctx.cell.limits
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
        # readers that no entry of BENCHMARK.json names yet (the configuration's file lists
        # them): what they read goes to the log, not into the result line
        for name in config.get("layer_metrics_without_an_entry", ()):
            say(f"train: {name} (no entry in BENCHMARK.json) = {ctx.spec.layer_metric(name).read(result, ctx)!r}")
    return result


def histogram_gap(program_rows, reference_rows) -> float:
    """The widest, over the layers of experts, of half the summed difference between the rows
    each held expert got in the program and in the reference, over the reference's routed
    rows: the least share of routed slots that went to another expert."""
    worst = 0.0
    for mine, ref in zip(program_rows, reference_rows):
        routed = max(sum(ref), 1)
        worst = max(worst, 0.5 * sum(abs(a - b) for a, b in zip(mine, ref)) / routed)
    return worst


def compare_with_reference(losses, grad_norms, delta_norms, program_rows, reference: dict, limits: dict) -> list:
    """The numbers of ``correct`` for a training cell of the tower, each beside its limit."""
    checks = []
    limit = limits.get("loss_gap", math.nan)
    for i, (mine, ref) in enumerate(zip(losses, reference["losses"])):
        gap = abs(mine - ref)
        checks.append(Check(f"loss_gap_step{i + 1}", gap, limit, gap <= limit, f"(program {mine:.5f}, reference {ref:.5f})"))

    def group(norms, which):
        routed = lambda k: k.split(".")[-1] in ROUTED_LEAVES and k.startswith("layer")  # noqa: E731
        if which == "wte":
            return {"wte": norms["wte"]}
        if which == "routed":
            return {k: v for k, v in norms.items() if routed(k)}
        return {k: v for k, v in norms.items() if k != "wte" and not routed(k)}

    pairs = [
        ("first_grad_norm_worst_block_leaf_gap", grad_norms, reference["grad_norms"], "block"),
        ("first_grad_norm_routed_experts_gap", grad_norms, reference["grad_norms"], "routed"),
        ("first_grad_norm_wte_gap", grad_norms, reference["grad_norms"], "wte"),
        ("param_change_norm_worst_leaf_gap", delta_norms, reference["delta_norms"], None),
    ]
    for name, mine, ref, which in pairs:
        limit = limits.get(name, math.nan)
        if mine is None:
            checks.append(Check(name, math.inf, limit, False, "(the loop never handed out its state)"))
            continue
        if which is not None:
            mine, ref = group(mine, which), group(ref, which)
            if not ref:
                continue  # a model without routed experts
        gap, where = compare.worst_leaf_gap(mine, ref)
        checks.append(Check(name, gap, limit, gap <= limit, f"(at {where}: program {mine.get(where)}, reference {ref.get(where)})"))

    routing = reference.get("routing") or []
    if routing:
        name, limit = "routed_rows_histogram_gap", limits.get("routed_rows_histogram_gap", math.nan)
        gaps = []
        for step, (mine, facts) in enumerate(zip(program_rows, routing)):
            if mine is None:
                checks.append(Check(name, math.inf, limit, False, f"(step {step + 1}: the step returned no counters)"))
                break
            gaps.append(histogram_gap(mine, facts["held_expert_rows"]))
        else:
            gap = max(gaps)
            checks.append(Check(name, gap, limit, gap <= limit, f"(by step: {[round(g, 5) for g in gaps]})"))
        moved = max(max(facts["moved_share"]) for facts in routing)
        limit = limits.get("router_choices_moved_share", math.nan)
        checks.append(Check(
            "router_choices_moved_share", moved, limit, moved <= limit,
            "(the reference's own: top-k choices that rounding the router's input to bfloat16 moves, worst layer and step)",
        ))
    return checks
