#!/usr/bin/env python3
"""Chip smoke: the trainer and the serving engine, end to end, on one TPU v5e chip.

    python chip_smoke.py              # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4    # four chips: the sharded paths against one device, only
    python chip_smoke.py --tiny       # CPU rehearsal at toy widths; can never print ok:true

Drives the two paths users pay for through their normal entry points, at the full width
of the repo's flagship model (`configs/pretraining-examples/pretrain-v5e-256-granite-3b.yml`:
gpt_dolomite, n_embd 2560, 32 heads of 80, n_inner 10240 swiglu, vocab 49152, 4096
positions, bf16, flash_attention_2, padding-free packed sequences), depth cut to what
16 GB holds, weights random from `--seed`:

- **train**: `dolomite_engine_tpu.pretrain.main(args=TrainingArgs(...))` — the real loop
  (kernel_args.install, init_distributed, create_sharded_train_state, StepPrefetcher,
  telemetry, one checkpoint save) over a Megatron .bin/.idx corpus this script writes
  from the seed. Every loss must be finite and the last below the first.
- **serve**: `ServingEngine` with its defaults (paged pool, chunked prefill) and
  `serve_batch` over token-id prompts of mixed length, greedy. `decode_compiles == 1`,
  all slots free after the drain, and every decoded token must be the top choice of a
  plain full forward of the same model (XLA kernels, sdpa) on the same tokens, within a
  stated bf16 tolerance on that forward's logits.
- for each program, every kernel family `active_kernel_backends()` reports as `pallas`
  must appear as a `tpu_custom_call` in the compiled text (`program_signature`
  `hlo.tpu_kernels`) — a family that says pallas and left no custom call is a failure.
- **`--chips 4`** runs only what exists across chips, each beside its one-device twin in
  the same process: the train phase on fsdp 2 x tp 2 (losses must agree step by step and
  params + optimizer bytes per device be a quarter), and one tp=4 `ServingEngine` replica
  (`serving/cluster/sharded.py`) answering the serve phase's requests beside a one-chip
  engine, both held to the same plain forward.

Times printed here carry the word "smoke": they are not benchmark numbers. One process
touches the chip. The last line of stdout is one JSON object,
`{"ok": ..., "device": {"platform", "kind", "count"}}`; the exit code is 0 only when
`ok` is true, and `ok` is true only on a TPU at full width with every phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SHIPPED_CONFIG = os.path.join(
    REPO, "configs", "pretraining-examples", "pretrain-v5e-256-granite-3b.yml"
)

# depth and batch: the widths are the shipped config's and are never cut. Chosen from the
# TPU compiler's memory analysis of these programs for one v5e (15.75 GiB usable); the
# numbers are in PERF.md "Cells".
TRAIN_LAYERS = 4  # 545M params (105M a layer + 126M embedding): 12.2 GiB with the step's temps
TRAIN_MICRO_BATCH = 1  # sequences of 4096 per micro step (each further one costs 3.5 GiB)
TRAIN_ACCUM = 1  # the accumulation scan holds a second set of fp32 grads (+3.9 GiB)
TRAIN_STEPS = 8
# the four-chip comparison: its one-device side must take the two sequences a dp world of 2
# consumes, in two accumulation steps, and at 4 layers that is 16.1 GiB — so depth 2
FOUR_CHIP_LAYERS = 2
FOUR_CHIP_SERVE_LAYERS = 4  # the tp=4 replica beside a one-chip engine: depth is compile time here
SERVE_LAYERS = 32  # the full depth: 3.48B params, 6.5 GiB of bf16 weights
SERVE_SLOTS = 8
SERVE_PAGES = 1024  # x 16 tokens x 320 KiB of K/V a token (MHA, 32 layers) = 5 GiB
SERVE_PROMPT_LENGTHS = (37, 64, 200, 700, 1500)
SERVE_NEW_TOKENS = 8

# the fraction of a logit row's standard deviation by which the engine's token may trail
# the reference forward's top choice. Both evaluations run the same bf16 weights, but in
# different orders (paged chunks + Pallas rmsnorm/rope vs one XLA pass): every op rounds to
# 8 mantissa bits (2^-8 relative), a few hundred roundings deep at 32 layers, so logits
# move by a few percent of their spread and near-ties can flip. A wrong cache position,
# page or mask yields an unrelated token, which sits ~4 standard deviations below the
# row's maximum at vocab 49152 — forty times this tolerance.
SERVE_LOGIT_TOLERANCE_STD = 0.1
# |loss(fsdp 2 x tp 2) - loss(one device)| per step, absolute at loss ~10: the sharded
# step splits matmul contractions over tp and reduces gradients over fsdp in another
# order, which in bf16 moves the loss in the fourth digit and compounds over the steps.
SHARDED_LOSS_TOLERANCE = 0.05

# which kernel families a program must contain when they resolve to pallas, and the name
# scope that marks each family's custom call in compiled HLO
TRAIN_STEP_FAMILIES = ("splash_attention", "rmsnorm", "fused_rope_qkv", "fused_ce")
DECODE_FAMILIES = ("paged_attention", "rmsnorm", "fused_rope_qkv")
CHUNK_FAMILIES = ("prefill_attention", "rmsnorm", "fused_rope_qkv")


def say(message: str) -> None:
    print(message, flush=True)


def kernel_scope(family: str) -> str:
    # jax's splash kernels sit in a scope of their own kernel name
    return "splash_mha" if family == "splash_attention" else f"pallas_{family}"


def check_kernels(program: str, families: tuple, backends: dict, tpu_kernels: dict) -> None:
    """Every family that resolves to pallas must have left a tpu_custom_call in `program`."""
    for family in families:
        if backends[family] != "pallas":
            continue
        calls = sum(n for name, n in tpu_kernels.items() if name.startswith(kernel_scope(family)))
        say(f"  {program}: {family}=pallas -> {calls} tpu_custom_call(s)")
        if calls == 0:
            raise AssertionError(
                f"{program}: {family} reports pallas but the compiled program has no "
                f"tpu_custom_call for it (found {tpu_kernels})"
            )


def bytes_per_device(tree) -> dict:
    """Where the arrays of `tree` really live: device id -> bytes of its addressable shards
    (so that "everything on the first device" cannot pass for sharded)."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
    return held


def memory_line(device) -> str:
    stats = device.memory_stats()  # None on the CPU backend
    if not stats:
        return "memory_stats: none on this backend"
    # on this runtime live arrays count as bytes_in_use and a loaded program's temporaries
    # as bytes_reserved: the peak HBM a phase needed is nearer the sum of the two peaks
    return (
        f"peak_bytes_in_use {stats['peak_bytes_in_use']} + peak_bytes_reserved "
        f"{stats['peak_bytes_reserved']} of bytes_limit {stats['bytes_limit']} "
        f"(process so far; bytes_in_use now {stats['bytes_in_use']})"
    )


# ------------------------------------------------------------------------------ train


def write_corpus(prefix: str, vocab: int, eos: int, num_tokens: int, seed: int) -> None:
    """A Megatron .bin/.idx pair of random documents. Tokens follow a Zipf law over the
    vocabulary, so there is something to learn in a few steps (a uniform stream has none:
    its loss starts at ln(vocab) and can only stay there)."""
    import numpy as np

    from dolomite_engine_tpu.data.megatron import MMapIndexedDatasetBuilder

    rng = np.random.RandomState(seed)
    ranks = np.arange(1, vocab, dtype=np.float64)
    probabilities = (1.0 / ranks) / np.sum(1.0 / ranks)
    tokens = rng.choice(np.arange(1, vocab), size=num_tokens, p=probabilities)
    builder = MMapIndexedDatasetBuilder(prefix + ".bin", dtype=np.uint16)
    start = 0
    while start < num_tokens:
        length = int(rng.randint(200, 3000))
        builder.add_item(np.append(tokens[start : start + length], eos))
        builder.end_document()
        start += length
    builder.finalize(prefix + ".idx")


def flagship_model_args(n_layer: int, tiny: bool) -> dict:
    """`model_args` of the shipped flagship config with the depth set; `tiny` shrinks the
    widths for the CPU rehearsal (which can never report ok)."""
    from dolomite_engine_tpu.utils import load_yaml

    model_args = load_yaml(SHIPPED_CONFIG)["model_args"]
    config = model_args["pretrained_config"]
    config["n_layer"] = n_layer
    if tiny:
        config.update(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_inner=256)
    return model_args


def training_args(
    out_dir: str,
    corpus_prefix: str,
    *,
    n_layer: int,
    accum: int,
    tensor_parallel_size: int,
    seed: int,
    tiny: bool,
):
    """The shipped flagship YAML with depth, batch, paths and mesh set for this machine."""
    from dolomite_engine_tpu.arguments import TrainingArgs
    from dolomite_engine_tpu.utils import load_yaml

    config = load_yaml(SHIPPED_CONFIG)
    config["model_args"] = flagship_model_args(n_layer, tiny)
    sequence_length = config["model_args"]["pretrained_config"]["n_positions"]
    config["tokenizer_args"] = {}  # token bins need no tokenizer, and there is no network
    config["datasets"][0]["class_args"] = dict(
        data_path=[corpus_prefix],
        data_cache_path=os.path.join(out_dir, "data_cache"),
        split="100,0,0",
        sequence_length=sequence_length,
        eval_steps=0,
    )
    config["training_parameters"] = dict(
        num_training_steps=TRAIN_STEPS,
        micro_batch_size=TRAIN_MICRO_BATCH,
        gradient_accumulation_steps=accum,
        gradient_clipping=1.0,
        eval_during_training=False,
        prefetch_depth=2,
    )
    # the shipped 3e-4 after 2000 warmup steps is for a global batch of two million tokens;
    # one 4096-token sequence a step, from step one, diverges there (loss 11.3 -> 19.6 at
    # step 2 on the chip) and falls steadily at a tenth of it
    config["optimizer_args"]["class_args"]["lr"] = 1e-3 if tiny else 3e-5  # toy widths move slower
    config["lr_scheduler_args"] = dict(lr_decay_style="constant", num_warmup_steps=0)
    config["save_args"] = dict(save_path=os.path.join(out_dir, "ckpt"), save_interval=TRAIN_STEPS)
    config["distributed_args"]["tensor_parallel_size"] = tensor_parallel_size
    if tensor_parallel_size == 1:  # the shipped tp flags are refused without tp
        config["distributed_args"].update(
            sequence_parallel=False, tensor_parallel_word_embeddings=False
        )
    config["logging_args"] = dict(
        log_interval=1,
        # the train step's program_signature record carries the compiled program's
        # tpu_custom_calls (one extra AOT compile, which the compile cache then serves)
        telemetry=dict(program_signatures=True),
    )
    config["random_args"] = dict(seed=seed)
    return TrainingArgs(**config)


def run_pretrain(args, label: str) -> dict:
    """One `pretrain.main` run. Returns its per-step losses, the per-device bytes of the
    train state it created, and the records of its telemetry sink."""
    from dolomite_engine_tpu import pretrain
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    losses: list[float] = []
    state_bytes: dict = {}
    track, create = pretrain.track_train_metrics, pretrain.create_sharded_train_state

    # main() returns nothing: the loop's own logging hook and state constructor are
    # wrapped to read the step losses at full precision and where the state really lives
    def tracking(*a, **kw):
        losses.append(float(kw["train_loss_step"]))
        return track(*a, **kw)

    def creating(*a, **kw):
        state, shardings = create(*a, **kw)
        state_bytes.update(bytes_per_device((state.params, state.opt_state)))
        return state, shardings

    pretrain.track_train_metrics, pretrain.create_sharded_train_state = tracking, creating
    MeshManager.destroy()
    t0 = time.perf_counter()
    try:
        pretrain.main(args=args)
    finally:
        pretrain.track_train_metrics, pretrain.create_sharded_train_state = track, create
        MeshManager.destroy()
    wall = time.perf_counter() - t0

    sink = os.path.join(args.save_args.save_path, "telemetry", "rank-00000.jsonl")
    with open(sink) as f:
        records = [json.loads(line) for line in f]
    by_kind: dict[str, list] = {}
    for record in records:
        by_kind.setdefault(record["kind"], []).append(record)
    steps = by_kind["step"]
    say(
        f"{label}: {len(losses)} steps in {wall:.1f} s wall (smoke); first step "
        f"{steps[0]['t']['compile']:.1f} s incl. compile (set-up, smoke); later steps "
        + ", ".join(f"{s['t']['step']:.3f}" for s in steps[1:])
        + " s (smoke, after the loss reached the host)"
    )
    say(f"{label}: losses " + ", ".join(f"{loss:.4f}" for loss in losses))
    if len(losses) != TRAIN_STEPS or not all(math.isfinite(loss) for loss in losses):
        raise AssertionError(f"{label}: expected {TRAIN_STEPS} finite losses, got {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    latest = os.path.join(args.save_args.save_path, "latest_checkpointed_iteration.json")
    with open(latest) as f:
        saved = json.load(f)["latest_checkpointed_iteration"]
    if saved != TRAIN_STEPS:
        raise AssertionError(f"{label}: checkpoint at step {saved}, expected {TRAIN_STEPS}")
    say(f"{label}: checkpoint saved at step {saved}")
    return dict(losses=losses, state_bytes=state_bytes, records=by_kind)


def train_phase(out_dir: str, seed: int, tiny: bool, chips: int) -> None:
    import jax

    from dolomite_engine_tpu.data.megatron import native
    from dolomite_engine_tpu.ops.pallas import active_kernel_backends

    n_layer = 2 if tiny else (TRAIN_LAYERS if chips == 1 else FOUR_CHIP_LAYERS)
    model_config = flagship_model_args(n_layer, tiny)["pretrained_config"]
    sequence_length = model_config["n_positions"]
    corpus = os.path.join(out_dir, "corpus")
    sequences_per_step = TRAIN_MICRO_BATCH * TRAIN_ACCUM * max(chips // 2, 1)  # dp = chips / tp
    write_corpus(
        corpus,
        model_config["vocab_size"],
        model_config["eos_token_id"],
        # a little over one epoch of what the run consumes
        num_tokens=(TRAIN_STEPS + 2) * sequences_per_step * (sequence_length + 1),
        seed=seed,
    )

    def args_for(run: str, accum: int, tp: int):
        return training_args(
            os.path.join(out_dir, run),
            corpus,
            n_layer=n_layer,
            accum=accum,
            tensor_parallel_size=tp,
            seed=seed,
            tiny=tiny,
        )

    say(
        f"train: n_layer {n_layer} (of 32), micro batch {TRAIN_MICRO_BATCH} x "
        f"{sequence_length} tokens, accumulation {TRAIN_ACCUM}, {TRAIN_STEPS} steps, "
        f"{chips} device(s)"
    )
    if chips == 1:
        run = run_pretrain(args_for("train", TRAIN_ACCUM, tp=1), "train")
    else:
        # fsdp 2 x tp 2 (dp world 2) against ONE device of the same process at the same
        # seed, data and global batch: the one-device run makes up the batch in accumulation
        run = run_pretrain(args_for("train_4dev", TRAIN_ACCUM, tp=2), "train[fsdp2 x tp2]")
        with visible_devices(jax.devices()[:1]):
            single = run_pretrain(args_for("train_1dev", TRAIN_ACCUM * 2, tp=1), "train[1 device]")
        compare_sharded(run, single, chips)

    helpers = "native (built from helpers.cpp)" if native.compile_helpers() else "numpy path"
    say(f"train: megatron index helpers: {helpers}")
    backends = active_kernel_backends()
    recorded = run["records"]["run_start"][0]["kernels"]
    if recorded != backends:
        raise AssertionError(f"run_start recorded kernels {recorded}, resolved {backends}")
    (program,) = run["records"]["program_signature"][0]["programs"]
    say(f"train: program {program['name']} tpu_kernels {program['hlo']['tpu_kernels']}")
    if jax.default_backend() == "tpu":
        check_kernels("train_step", TRAIN_STEP_FAMILIES, backends, program["hlo"]["tpu_kernels"])
    say(f"train: {memory_line(jax.devices()[0])}")


@contextlib.contextmanager
def visible_devices(devices: list):
    """Show the trainer only `devices`: `pretrain.main` builds its mesh and its batch
    accounting from `jax.devices()` / `jax.device_count()`, so the one-device reference
    of the four-chip comparison narrows those for the call (the program has no option
    for it, and gets none for this script's sake)."""
    import jax

    names = ("devices", "local_devices", "device_count", "local_device_count")
    saved = {name: getattr(jax, name) for name in names}
    jax.devices = jax.local_devices = lambda *a, **kw: list(devices)
    jax.device_count = jax.local_device_count = lambda *a, **kw: len(devices)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(jax, name, fn)


def compare_sharded(sharded: dict, single: dict, chips: int) -> None:
    deltas = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    say("4 chips: |loss(sharded) - loss(1 device)| per step " + ", ".join(f"{d:.4f}" for d in deltas))
    if max(deltas) > SHARDED_LOSS_TOLERANCE:
        raise AssertionError(f"sharded and one-device losses differ by {max(deltas)}")
    one = max(single["state_bytes"].values())
    per_device = sharded["state_bytes"]
    say(
        f"4 chips: params + optimizer bytes per device {sorted(per_device.values())} "
        f"vs {one} on one device (ratios "
        + ", ".join(f"{b / one:.3f}" for b in sorted(per_device.values()))
        + ")"
    )
    if len(single["state_bytes"]) != 1 or len(per_device) != chips:
        raise AssertionError(f"state lives on {len(per_device)} device(s), expected {chips}")
    # a quarter each, with room for the small leaves that replicate (norm weights, scalars)
    if not all(0.2 * one < b < 0.3 * one for b in per_device.values()):
        raise AssertionError("per-device state is not about a quarter of the one-device state")


# ------------------------------------------------------------------------------ serve


def serve_phase(seed: int, tiny: bool, chips: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    from dolomite_engine_tpu.enums import AttentionImplementation, Mode
    from dolomite_engine_tpu.model_wrapper import ModelWrapper
    from dolomite_engine_tpu.ops.pallas import active_kernel_backends, kernel_overrides
    from dolomite_engine_tpu.ops.pallas.config import KERNEL_FAMILIES
    from dolomite_engine_tpu.serving import ServingEngine, serve_batch
    from dolomite_engine_tpu.serving.cluster.sharded import inference_mesh, make_sharded_engine

    n_layer = 2 if tiny else (SERVE_LAYERS if chips == 1 else FOUR_CHIP_SERVE_LAYERS)
    model_args = flagship_model_args(n_layer, tiny)
    model_args["scan_layers"] = False  # generation runs the unrolled model

    def wrapper(attention: AttentionImplementation) -> ModelWrapper:
        return ModelWrapper(
            mode=Mode.inference,
            pretrained_config=model_args["pretrained_config"],
            model_class=model_args["model_class"],
            dtype="bf16",
            attention_implementation=attention,
        )

    served = wrapper(AttentionImplementation(model_args["attention_implementation"]))
    config = served.config
    max_len = config.n_positions
    lengths = [min(n, max_len // 4) for n in SERVE_PROMPT_LENGTHS] if tiny else SERVE_PROMPT_LENGTHS
    engine_args = dict(num_slots=SERVE_SLOTS, max_len=max_len, num_pages=64 if tiny else SERVE_PAGES)

    t0 = time.perf_counter()
    init = jax.jit(
        lambda: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16),
            nn.unbox(served.model.init(jax.random.PRNGKey(seed), **served.get_dummy_inputs())["params"]),
        )
    )
    params = jax.block_until_ready(init())
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say(
        f"serve: n_layer {n_layer} (of 32), {param_bytes / 2e9:.2f}B bf16 params from seed "
        f"{seed} in {time.perf_counter() - t0:.1f} s (set-up, smoke)"
    )
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, config.vocab_size, size=n).tolist() for n in lengths]
    specs = [dict(prompt_ids=p, max_new_tokens=SERVE_NEW_TOKENS, eos_token_id=None) for p in prompts]
    backends = active_kernel_backends()
    on_tpu = jax.default_backend() == "tpu"

    def serve(label: str, engine: ServingEngine) -> list:
        """The requests twice through `engine`; returns the request states of both passes."""
        pool = engine.pool
        say(
            f"{label}: {SERVE_SLOTS} slots, max_len {max_len}, paged pool of {pool.num_pages} "
            f"pages x {pool.page_size} tokens ({pool.kv_bytes_per_token / 1024:.0f} KiB of K/V a "
            f"token), prefill chunks of {engine.scheduler.prefill_chunk_tokens}"
        )
        t0 = time.perf_counter()
        states = serve_batch(engine, specs)
        say(
            f"{label}: {len(specs)} requests (prompts {list(lengths)}, {SERVE_NEW_TOKENS} new "
            f"tokens each, greedy) in {time.perf_counter() - t0:.1f} s incl. compiles (set-up, smoke)"
        )
        # the same requests again: every program is compiled now. Prefix caching answers
        # the prompts from resident pages, so this times decode and the cached-prefix path
        t0 = time.perf_counter()
        states += serve_batch(engine, specs)
        say(f"{label}: the same requests again in {time.perf_counter() - t0:.2f} s (smoke, all compiled)")
        for state in states:
            if str(state.status) != "completed" or len(state.tokens) != SERVE_NEW_TOKENS:
                raise AssertionError(f"request ended {state.status} with {len(state.tokens)} tokens")
        if engine.decode_compiles != 1:
            raise AssertionError(f"decode_compiles == {engine.decode_compiles}, expected 1")
        if pool.num_free != SERVE_SLOTS:
            raise AssertionError(f"{pool.num_free} of {SERVE_SLOTS} slots free after the drain")
        say(f"{label}: decode_compiles 1, chunk_compiles {engine.chunk_compiles}, all slots free")
        signatures = engine.program_signatures(compile=on_tpu)
        say(f"{label}: programs {sorted(signatures)}")
        if on_tpu:
            for name, signature in signatures.items():
                families = DECODE_FAMILIES if name == "decode" else CHUNK_FAMILIES
                check_kernels(name, families, backends, signature.hlo["tpu_kernels"])
        return states

    served_states = {"serve": serve("serve", ServingEngine(served.model, params, **engine_args))}
    if chips == 4:
        # one tensor-parallel replica over all four chips beside the one-chip engine
        replica = make_sharded_engine(
            served.model,
            params,
            mesh=inference_mesh(tensor_parallel_size=4, devices=jax.devices()),
            **engine_args,
        )
        per_device = bytes_per_device(replica._variables)
        say(
            f"serve[tp=4]: weight bytes per device {sorted(per_device.values())} of "
            f"{param_bytes} unsharded (the embedding and the norms replicate)"
        )
        if len(per_device) != 4 or max(per_device.values()) > 0.5 * param_bytes:
            raise AssertionError("the tp=4 replica's weights are not spread over the four chips")
        served_states["serve[tp=4]"] = serve("serve[tp=4]", replica)

    # the reference: ONE plain forward per request over prompt + decoded tokens, on the XLA
    # lowering of every kernel family and sdpa attention. Causal attention makes the right
    # padding to a common length invisible to the positions compared. Every pass is judged
    # on its own tokens: the second prefilled in other chunks (cached prefix + tail), and
    # between near-tied logits of random weights that may pick another token.
    reference = wrapper(AttentionImplementation.sdpa)
    width = -(-(max(lengths) + SERVE_NEW_TOKENS) // 128) * 128
    with kernel_overrides(**{family: "xla" for family in KERNEL_FAMILIES}):
        forward = jax.jit(lambda p, ids: reference.model.apply({"params": p}, ids).logits)
        for label, states in served_states.items():
            worst, exact, total = 0.0, 0, 0
            for prompt, state in zip(prompts + prompts, states):
                ids = np.zeros((1, width), np.int32)
                tokens = prompt + state.tokens
                ids[0, : len(tokens)] = tokens
                rows = np.asarray(
                    forward(params, jnp.asarray(ids))[0, len(prompt) - 1 : len(tokens) - 1], np.float32
                )
                if not np.all(np.isfinite(rows)):
                    raise AssertionError("reference logits are not finite")
                for row, token in zip(rows, state.tokens):
                    worst = max(worst, float((row.max() - row[token]) / row.std()))
                    exact += int(row.argmax() == token)
                    total += 1
            say(
                f"{label}: {exact}/{total} decoded tokens (both passes) are the reference "
                f"forward's argmax; the worst trails its row's maximum by {worst:.4f} of the "
                f"row's std (tolerance {SERVE_LOGIT_TOLERANCE_STD})"
            )
            if worst > SERVE_LOGIT_TOLERANCE_STD:
                raise AssertionError(f"{label}: tokens disagree with the plain forward beyond the bf16 tolerance")
    say(f"serve: {memory_line(jax.devices()[0])}")


# ------------------------------------------------------------------------------ main


def run(options) -> None:
    import jax

    from dolomite_engine_tpu.ops.pallas import active_kernel_backends
    from dolomite_engine_tpu.utils import enable_compilation_cache, pallas_interpret_mode

    devices = jax.devices()
    say(
        f"jax {jax.__version__}; {devices[0].platform} / {devices[0].device_kind} x "
        f"{len(devices)}; kernels {active_kernel_backends()}"
    )
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not options.tiny:
        raise RuntimeError(f"no TPU: jax found {devices[0].platform} devices")
    if len(devices) != options.chips:
        raise RuntimeError(f"--chips {options.chips} but jax found {len(devices)} device(s)")
    if on_tpu and pallas_interpret_mode():
        raise RuntimeError("Pallas kernels would run interpreted on the TPU")
    say(f"compile cache: {enable_compilation_cache()}")

    shutil.rmtree(options.out, ignore_errors=True)
    os.makedirs(options.out)
    train_phase(options.out, options.seed, options.tiny, options.chips)
    gc.collect()  # the train state is unreferenced now; the weights need its room
    serve_phase(options.seed, options.tiny, options.chips)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"))
    parser.add_argument(
        "--tiny", action="store_true", help="CPU rehearsal at toy widths; never reports ok"
    )
    options = parser.parse_args()

    device = {"platform": None, "kind": None, "count": 0}
    passed = False
    try:
        import jax

        devices = jax.devices()
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        run(options)
        passed = True
    except Exception:  # the boundary: report the failure, then fail
        traceback.print_exc()
    ok = passed and device["platform"] == "tpu" and not options.tiny
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
