"""MFU sweep harness: run the bench train step for one (model, batch, remat) point.

Usage: python tools/bench_sweep.py --n_embd 2048 --n_layer 16 --micro_bs 8 --ckpt 1 [--steps 10]

Prints one JSON line per run with mfu/step_time/HBM (`mfu` is null off-TPU: a CPU has no
peak in the table, and no number is made up for it). No on-chip sweep is on record: not
measured.

Kernel-tier A/B mode (docs/PERFORMANCE.md "Kernel tier"):

    python tools/bench_sweep.py --kernels [--kernel_families rmsnorm,moe_dispatch]

runs each Pallas kernel family against its XLA reference lowering on the family's hot
shape (decode-shaped paged attention, block-shaped rmsnorm rows, token-batch MoE
dispatch) and prints one ``{"bench": "kernel_ab", "family": ...}`` JSON line per family
for the BENCH trajectory. Off-TPU the Pallas side runs in interpret mode — numbers then
measure the emulator, not the kernel (the ``interpret`` field says which you got), so
only TPU lines are meaningful as speedups; CPU runs exist to keep the harness exercised.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _mfu(model_tflops: float, step_seconds: float) -> float | None:
    """MFU against the ONE peaks table (utils/telemetry): None on a CPU, an error on a TPU
    the table does not know."""
    from dolomite_engine_tpu.utils import detect_peak_tflops_per_device

    peak = detect_peak_tflops_per_device()
    if peak is None:
        return None
    return round(model_tflops / step_seconds / jax.device_count() / peak, 4)


KERNEL_AB_FAMILIES = (
    "paged_attention",
    "prefill_attention",
    "paged_kv_quant",
    "rmsnorm",
    "moe_dispatch",
    "fused_ce",
    "fused_rope_qkv",
)

REMAT_AB_POLICIES = ("full", "save_dots", "save_attention_out", "offload_dots")


def _time_jitted(fn, args, reps: int) -> float:
    """Median wall ms of an already-jitted callable (one warmup compile call)."""
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3


def _bench_kernel_family(family: str, args) -> dict:
    """One xla-vs-pallas A/B on the family's hot shape; returns the JSON payload."""
    from dolomite_engine_tpu.ops.pallas import kernel_overrides

    key = jax.random.PRNGKey(0)
    if family == "rmsnorm":
        rows, hidden = args.micro_bs * 512, args.n_embd
        x = jax.random.normal(key, (rows, hidden), jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(1), (rows, hidden), jnp.bfloat16)
        w = jnp.ones((hidden,), jnp.float32)
        from dolomite_engine_tpu.ops.normalization import rmsnorm
        from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm

        xla_fn = jax.jit(lambda x, r: rmsnorm(x + r, w, 1e-5))
        pallas_fn = jax.jit(lambda x, r: fused_rmsnorm(x, w, 1e-5, residual=r)[0])
        shape = {"rows": rows, "hidden": hidden}
        operands = (x, r)
    elif family == "moe_dispatch":
        tokens, d, f, E, k = args.micro_bs * 512, args.n_embd, 2 * args.n_embd, 8, 2
        x = jax.random.normal(key, (tokens, d), jnp.bfloat16)
        w_fc = jax.random.normal(jax.random.PRNGKey(1), (E, d, f), jnp.bfloat16) * 0.02
        w_proj = jax.random.normal(jax.random.PRNGKey(2), (E, f, d), jnp.bfloat16) * 0.02
        logits = jax.random.normal(jax.random.PRNGKey(3), (tokens, E), jnp.float32)
        from dolomite_engine_tpu.ops.moe import combine_weights, experts_eager, route
        from dolomite_engine_tpu.ops.pallas.moe import experts_grouped

        weights, selected = route(logits, k)
        weights = weights.astype(x.dtype)

        def run_xla(x):
            combine = combine_weights(weights, selected, E)
            return experts_eager(x, combine, w_fc, None, w_proj, None, jax.nn.gelu)

        xla_fn = jax.jit(run_xla)
        pallas_fn = jax.jit(
            lambda x: experts_grouped(
                x, weights, selected, w_fc, None, w_proj, None, jax.nn.gelu, E
            )
        )
        shape = {"tokens": tokens, "d": d, "f": f, "experts": E, "top_k": k}
        operands = (x,)
    elif family == "paged_attention":
        # decode-shaped: many slots, 1 query token each, ragged resident lengths
        slots, page, max_pages, hq, hkv, hd = args.micro_bs * 4, 16, 32, 8, 2, 64
        num_pages = slots * max_pages + 1
        q = jax.random.normal(key, (slots, 1, hq, hd), jnp.bfloat16)
        k_pages = jax.random.normal(
            jax.random.PRNGKey(1), (num_pages, page, hkv, hd), jnp.bfloat16
        )
        v_pages = jax.random.normal(
            jax.random.PRNGKey(2), (num_pages, page, hkv, hd), jnp.bfloat16
        )
        rs = np.random.RandomState(0)
        lengths = jnp.asarray(rs.randint(1, max_pages * page - 1, slots), jnp.int32)
        table = jnp.asarray(
            1 + np.arange(slots * max_pages, dtype=np.int32).reshape(slots, max_pages)
        )
        scale = hd**-0.5
        from dolomite_engine_tpu.ops.attention import (
            eager_attention,
            make_attention_mask,
            paged_gather_kv,
        )
        from dolomite_engine_tpu.ops.pallas.paged_attention import paged_decode_attention

        def run_xla(q, k_pages, v_pages):
            view_len = max_pages * page
            valid = jnp.arange(view_len)[None, :] < (lengths[:, None] + 1)
            mask = make_attention_mask(
                slots, 1, view_len, causal=True,
                attention_mask=valid.astype(jnp.int32), query_offset=lengths,
            )
            return eager_attention(
                q, paged_gather_kv(k_pages, table), paged_gather_kv(v_pages, table),
                mask, None, scale,
            )

        xla_fn = jax.jit(run_xla)
        pallas_fn = jax.jit(
            lambda q, k, v: paged_decode_attention(q, k, v, table, lengths, scale)
        )
        shape = {
            "slots": slots, "page_size": page, "max_pages": max_pages,
            "q_heads": hq, "kv_heads": hkv, "head_dim": hd,
        }
        operands = (q, k_pages, v_pages)
    elif family == "prefill_attention":
        # chunk-shaped: one row, a wide query window, a long resident prefix — the
        # XLA side pays the worst-case gathered view, the kernel walks resident pages
        rows, chunk, page, max_pages, hq, hkv, hd = 1, 256, 16, 64, 8, 2, 64
        num_pages = rows * max_pages + 1
        q = jax.random.normal(key, (rows, chunk, hq, hd), jnp.bfloat16)
        k_pages = jax.random.normal(
            jax.random.PRNGKey(1), (num_pages, page, hkv, hd), jnp.bfloat16
        )
        v_pages = jax.random.normal(
            jax.random.PRNGKey(2), (num_pages, page, hkv, hd), jnp.bfloat16
        )
        table = jnp.asarray(
            1 + np.arange(rows * max_pages, dtype=np.int32).reshape(rows, max_pages)
        )
        starts = jnp.full((rows,), 8 * page, jnp.int32)  # resident prefix: 8 pages
        scale = hd**-0.5
        from dolomite_engine_tpu.ops.attention import (
            eager_attention,
            make_attention_mask,
            paged_gather_kv,
        )
        from dolomite_engine_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention,
        )

        def run_xla(q, k_pages, v_pages):
            view_len = max_pages * page
            mask = make_attention_mask(
                rows, chunk, view_len, causal=True, query_offset=starts
            )
            return eager_attention(
                q, paged_gather_kv(k_pages, table), paged_gather_kv(v_pages, table),
                mask, None, scale,
            )

        xla_fn = jax.jit(run_xla)
        pallas_fn = jax.jit(
            lambda q, k, v: paged_prefill_attention(q, k, v, table, starts, scale)
        )
        shape = {
            "rows": rows, "chunk": chunk, "page_size": page, "max_pages": max_pages,
            "q_heads": hq, "kv_heads": hkv, "head_dim": hd,
        }
        operands = (q, k_pages, v_pages)
    elif family == "fused_ce":
        # chunk-shaped: one fused-loss chunk's rows against a real vocab — the XLA side
        # materializes the [rows, V] logits in HBM, the kernel tiles V through VMEM
        rows, hidden, vocab = args.micro_bs * 64, args.n_embd, args.vocab
        h = jax.random.normal(key, (rows, hidden), jnp.float32)
        table = jax.random.normal(jax.random.PRNGKey(1), (vocab, hidden), jnp.float32) * 0.02
        y = jnp.asarray(np.random.RandomState(0).randint(0, vocab, rows), jnp.int32)
        from dolomite_engine_tpu.ops.loss import cross_entropy_terms
        from dolomite_engine_tpu.ops.pallas.fused_ce import fused_ce_chunk

        def run_xla(h):
            logits = jnp.dot(h, table.T)
            return cross_entropy_terms(logits, y, want_z=True)

        xla_fn = jax.jit(run_xla)
        pallas_fn = jax.jit(
            lambda h: fused_ce_chunk(
                h[None], table, y[None], logit_scale=None, upcast=True,
                compute_dtype=jnp.float32,
            )
        )
        shape = {"rows": rows, "hidden": hidden, "vocab": vocab}
        operands = (h,)
    elif family == "fused_rope_qkv":
        # attention-entry-shaped: a full fused QKV projection output + per-row cos/sin
        rows, hq, hkv, hd = args.micro_bs * 512, 8, 2, 64
        total = (hq + 2 * hkv) * hd
        qkv = jax.random.normal(key, (1, rows, total), jnp.bfloat16)
        from dolomite_engine_tpu.ops.rope import RoPEParams, get_cos_sin, split_qkv_apply_rope
        from dolomite_engine_tpu.ops.pallas.rope_qkv import fused_rope_qkv

        rope = RoPEParams.from_config(hd)
        cos, sin = get_cos_sin(rope, jnp.arange(rows)[None, :], dtype=jnp.bfloat16)

        xla_fn = jax.jit(lambda x: split_qkv_apply_rope(x, hq, hkv, hd, (cos, sin)))
        pallas_fn = jax.jit(lambda x: fused_rope_qkv(x, cos, sin, hq, hkv, hd))
        shape = {"rows": rows, "q_heads": hq, "kv_heads": hkv, "head_dim": hd}
        operands = (qkv,)
    elif family == "paged_kv_quant":
        # scatter-shaped: the batch of touched pages one engine step re-encodes
        pages_n, page, hkv, hd = args.micro_bs * 8, 16, 2, 64
        values = jax.random.normal(key, (pages_n, page, hkv, hd), jnp.float32)
        valid = jnp.asarray(
            np.random.RandomState(0).rand(pages_n, page) > 0.25
        )
        from dolomite_engine_tpu.ops.kv_quant import quantize_pages_xla
        from dolomite_engine_tpu.ops.pallas.kv_quant import quantize_pages_pallas

        xla_fn = jax.jit(lambda v: quantize_pages_xla(v, valid, 127.0, jnp.int8))
        pallas_fn = jax.jit(lambda v: quantize_pages_pallas(v, valid, 127.0, jnp.int8))
        shape = {"pages": pages_n, "page_size": page, "kv_heads": hkv, "head_dim": hd}
        operands = (values,)
    else:
        raise ValueError(f"unknown kernel family for A/B: {family}")

    from dolomite_engine_tpu.utils import pallas_interpret_mode

    # pin the reference arm to XLA: with `auto` promotion defaults the dispatching call
    # sites (e.g. split_qkv_apply_rope) would otherwise lower Pallas on TPU in both arms
    with kernel_overrides(**{family: "xla"}):
        xla_ms = _time_jitted(xla_fn, operands, args.steps)
    with kernel_overrides(**{family: "pallas"}):
        pallas_ms = _time_jitted(pallas_fn, operands, args.steps)
    return {
        "bench": "kernel_ab",
        "family": family,
        "backend": jax.default_backend(),
        "interpret": pallas_interpret_mode(),
        **shape,
        "xla_ms": round(xla_ms, 3),
        "pallas_ms": round(pallas_ms, 3),
        "pallas_speedup": round(xla_ms / pallas_ms, 3) if pallas_ms else None,
    }


def run_remat_ab(args) -> None:
    """Per-remat-policy train-step A/B: one ``{"bench": "train_fast_path", ...}`` JSON
    line per policy with the step-time ratio and HBM high-water vs the ``full`` policy.

    HBM high water comes from the compiled step's static buffer assignment (the
    ``temp_size_in_bytes`` field of the step's perf signature,
    ``utils/program_signature.capture_jit_signature`` — the same extraction
    ``tools/perf_ledger.py`` gates on) so the line is meaningful on CPU too —
    live ``device.memory_stats()`` peaks ride along when the backend exposes them
    (TPU). Off-TPU the step-time column measures the CPU backend, not the claim; the
    ``backend`` field says which you got (the PR 11 bench resilience contract: a
    flagged line always lands, never a bench_error zero)."""
    from dolomite_engine_tpu.enums import AttentionImplementation, LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
    from dolomite_engine_tpu.train_utils import (
        get_model_tflops,
        make_train_step,
        run_timed_windows,
    )
    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.utils.program_signature import capture_jit_signature

    backend = jax.default_backend()
    n_head = args.n_head or args.n_embd // 64
    config = dict(
        model_type="gpt_dolomite",
        vocab_size=args.vocab,
        n_positions=args.seq,
        n_embd=args.n_embd,
        n_layer=args.n_layer,
        n_head=n_head,
        num_key_value_heads=args.kv_heads,
        attention_head_type="gqa",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        add_bias=False,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        tie_word_embeddings=True,
        fused_lm_head_loss=args.fused_loss,
        loss_chunk_size=args.loss_chunk,
    )
    MeshManager()
    mesh = MeshManager.get_mesh()
    tokens = np.random.RandomState(0).randint(
        0, config["vocab_size"], size=(1, args.micro_bs, args.seq + 1)
    ).astype(np.int32)

    baseline = {}
    for policy in REMAT_AB_POLICIES:
        wrapper = ModelWrapperForPretraining(
            mode=Mode.training,
            pretrained_config=config,
            dtype=args.dtype,
            sequence_length=args.seq,
            attention_implementation=(
                AttentionImplementation.flash_attention_2
                if backend == "tpu"
                else AttentionImplementation.sdpa
            ),
            zero_stage=3,
            gradient_checkpointing_args={"checkpoint_every": args.ckpt or 1, "policy": policy},
        )
        sched = get_scheduler(10, 0, None, 1000, LRDecaySchedule.cosine, 0.1, base_lr=3e-4)
        opt = get_optimizer(
            "TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, sched
        )
        state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(0))
        step_fn = make_train_step(
            lambda params, micro, rng, fp8_state=None: wrapper.loss(
                params, micro["text"], train=True, fp8_state=fp8_state
            ),
            opt,
        )
        with mesh:
            jit_step = jax.jit(step_fn, donate_argnums=0)
            batch = {
                "text": jax.device_put(
                    jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp"))
                )
            }
            sig = capture_jit_signature(
                jit_step,
                (state, batch, jax.random.PRNGKey(1)),
                name=f"train_step[policy={policy}]",
            )
            temp_bytes = sig.memory.get("temp_size_in_bytes")
            state, window_times = run_timed_windows(
                jit_step, state, batch, jax.random.PRNGKey(1), args.steps,
                windows=args.windows,
            )
        step_ms = float(np.median(window_times)) * 1e3
        peak_bytes = None
        try:
            stats = jax.local_devices()[0].memory_stats()
            if stats and stats.get("peak_bytes_in_use"):
                peak_bytes = int(stats["peak_bytes_in_use"])
        except Exception:
            pass
        tflops = get_model_tflops(
            wrapper.config, args.micro_bs, args.seq,
            gradient_checkpointing_method="block",
            gradient_checkpointing_args={"checkpoint_every": args.ckpt or 1, "policy": policy},
        )
        mfu = _mfu(tflops, step_ms / 1e3)
        if policy == "full":
            baseline = {"step_ms": step_ms, "temp_bytes": temp_bytes}
        line = {
            "bench": "train_fast_path",
            "policy": policy,
            "backend": backend,
            "ckpt": args.ckpt or 1,
            "fused_loss": args.fused_loss,
            "step_ms": round(step_ms, 2),
            "mfu": mfu,
            "train_step_hbm_high_water": temp_bytes,
            "peak_bytes_in_use": peak_bytes,
            "train_step_time_ratio": (
                round(baseline["step_ms"] / step_ms, 3) if baseline.get("step_ms") else None
            ),
            "hbm_vs_full": (
                round(temp_bytes / baseline["temp_bytes"], 3)
                if temp_bytes and baseline.get("temp_bytes")
                else None
            ),
        }
        print(json.dumps(line), flush=True)


def run_kernel_ab(args) -> None:
    families = [
        f.strip() for f in (args.kernel_families or ",".join(KERNEL_AB_FAMILIES)).split(",")
        if f.strip()
    ]
    for family in families:
        print(json.dumps(_bench_kernel_family(family, args)), flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--n_embd", type=int, default=1024)
    p.add_argument("--n_layer", type=int, default=24)
    p.add_argument("--n_head", type=int, default=0)  # 0 = n_embd // 64
    p.add_argument("--kv_heads", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--micro_bs", type=int, default=8)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--ckpt", type=int, default=0, help="checkpoint_every (0 = no remat)")
    p.add_argument("--ckpt_policy", type=str, default=None,
                   help="jax.checkpoint_policies name (e.g. dots_saveable), with --ckpt")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--mu_dtype", type=str, default=None, help="optax adamw mu dtype override")
    p.add_argument("--dtype", type=str, default="bf16")
    p.add_argument("--upcast", action="store_true", help="fp32-upcast logits for loss")
    p.add_argument("--fused_loss", action="store_true", help="chunked LM-head loss (no full logits)")
    p.add_argument("--loss_chunk", type=int, default=256)
    p.add_argument("--profile", type=str, default=None, help="jax.profiler trace dir")
    p.add_argument("--splash", action="store_true", help="use the splash attention kernel")
    p.add_argument("--packed", action="store_true", help="packed segment-ids path (reset_attention_mask)")
    p.add_argument("--moe", type=int, default=0, help="num_experts (0 = dense gpt_dolomite)")
    p.add_argument("--top_k", type=int, default=2, help="experts per token (with --moe)")
    p.add_argument("--model_type", type=str, default=None,
                   choices=["gpt_dolomite", "moe_dolomite", "dense_moe", "rnn_dolomite",
                            "gpt_crosslayer"],
                   help="model family (default gpt_dolomite; --moe implies moe_dolomite)")
    p.add_argument("--n_inner", type=int, default=0, help="MLP inner dim (0 = 4*n_embd)")
    p.add_argument("--kv_sharing", type=int, default=2,
                   help="gpt_crosslayer: consecutive layers sharing one KV (group size)")
    p.add_argument("--attention_pattern", type=str, default=None,
                   help="rnn_dolomite layer pattern over {a,d} (default: 'ad'*... mix)")
    p.add_argument("--offload", action="store_true",
                   help="cpu_offload: optimizer state in pinned_host memory (TPU only)")
    p.add_argument("--scan", action="store_true",
                   help="scan_layers: nn.scan over one block (or k-block groups with --ckpt k)")
    p.add_argument("--windows", type=int, default=1,
                   help="timing windows of --steps each; reports the median window")
    p.add_argument("--kernels", action="store_true",
                   help="kernel-tier A/B mode: per-family xla-vs-pallas JSON lines "
                        "instead of the train-step sweep")
    p.add_argument("--kernel_families", type=str, default=None,
                   help="comma list of families for --kernels "
                        f"(default: {','.join(KERNEL_AB_FAMILIES)})")
    p.add_argument("--remat", action="store_true",
                   help="remat-policy A/B mode: one train_fast_path JSON line per "
                        f"policy ({','.join(REMAT_AB_POLICIES)}) with step-time ratio "
                        "and compiled HBM high-water vs the full policy")
    args = p.parse_args()

    if args.kernels:
        run_kernel_ab(args)
        return
    if args.remat:
        run_remat_ab(args)
        return

    if args.splash:
        os.environ["DOLOMITE_SPLASH_ATTENTION"] = "1"

    from dolomite_engine_tpu.enums import AttentionImplementation, LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
    from dolomite_engine_tpu.train_utils import get_model_tflops, make_train_step
    from dolomite_engine_tpu.distributed import create_sharded_train_state

    backend = jax.default_backend()
    n_head = args.n_head or args.n_embd // 64
    config = dict(
        model_type="gpt_dolomite",
        vocab_size=args.vocab,
        n_positions=args.seq,
        n_embd=args.n_embd,
        n_layer=args.n_layer,
        n_head=n_head,
        num_key_value_heads=args.kv_heads,
        attention_head_type="gqa",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        add_bias=False,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        tie_word_embeddings=True,
        upcast_logits_for_loss=args.upcast,
        fused_lm_head_loss=args.fused_loss,
        loss_chunk_size=args.loss_chunk,
    )
    if args.n_inner:
        config["n_inner"] = args.n_inner
    model_type = args.model_type or ("moe_dolomite" if args.moe else "gpt_dolomite")
    if model_type == "moe_dolomite":
        config.update(
            model_type="moe_dolomite",
            num_experts=args.moe or 8,
            num_experts_per_tok=args.top_k,
            router_aux_loss_coef=0.01,
        )
    elif model_type == "dense_moe":
        # dense_moe forces num_key_value_heads = num_experts (models/config.py)
        config.pop("num_key_value_heads")
        config.update(model_type="dense_moe", num_experts=args.moe or 8)
    elif model_type == "rnn_dolomite":
        # default: the reference-style hybrid — 1 attention layer per 4 DeltaNet layers
        pattern = args.attention_pattern or (
            "ddda" * (args.n_layer // 4) + "d" * (args.n_layer % 4)
        )
        config.update(model_type="rnn_dolomite", attention_pattern=pattern)
    elif model_type == "gpt_crosslayer":
        g = args.kv_sharing
        config.update(
            model_type="gpt_crosslayer",
            sharing_pattern=[(i // g) * g for i in range(args.n_layer)],
        )

    MeshManager()
    mesh = MeshManager.get_mesh()

    gc_args = {"checkpoint_every": args.ckpt} if args.ckpt else None
    if gc_args and args.ckpt_policy:
        gc_args["checkpoint_policy"] = args.ckpt_policy
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training,
        pretrained_config=config,
        dtype=args.dtype,
        sequence_length=args.seq,
        attention_implementation=(
            AttentionImplementation.flash_attention_2
            if backend == "tpu"
            else AttentionImplementation.sdpa
        ),
        reset_attention_mask=args.packed,
        reset_position_ids=args.packed,
        zero_stage=3,
        gradient_checkpointing_args=gc_args,
        model_kwargs={"scan_layers": True} if args.scan else None,
    )

    sched = get_scheduler(10, 0, None, 1000, LRDecaySchedule.cosine, 0.1, base_lr=3e-4)
    opt_kwargs = {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}
    if args.mu_dtype:
        opt_kwargs["mu_dtype"] = args.mu_dtype
    opt = get_optimizer("TorchAdamW", opt_kwargs, sched)
    offload = args.offload and backend == "tpu"
    state, _ = create_sharded_train_state(
        wrapper, opt, mesh, jax.random.PRNGKey(0), offload_optimizer=offload
    )
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    def loss_fn(params, micro, rng, fp8_state=None):
        return wrapper.loss(params, micro["text"], train=True, fp8_state=fp8_state)

    step_fn = make_train_step(
        loss_fn, opt, gradient_accumulation_steps=args.accum, offload_optimizer=offload
    )
    tokens = np.random.RandomState(0).randint(
        0, config["vocab_size"], size=(args.accum, args.micro_bs, args.seq + 1)
    ).astype(np.int32)

    with mesh:
        jit_kwargs = {"donate_argnums": 0}
        if offload:
            from dolomite_engine_tpu.train_utils import offload_jit_kwargs

            jit_kwargs.update(offload_jit_kwargs(state))
        jit_step = jax.jit(step_fn, **jit_kwargs)
        batch = {"text": jax.device_put(jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp")))}
        rng = jax.random.PRNGKey(1)

        t_c = time.perf_counter()
        state, metrics = jit_step(state, batch, rng)
        jax.block_until_ready(metrics["loss"])
        compile_s = time.perf_counter() - t_c

        if args.profile:
            with jax.profiler.trace(args.profile):
                state, metrics = jit_step(state, batch, rng)
                jax.block_until_ready(metrics["loss"])

        from dolomite_engine_tpu.train_utils import run_timed_windows

        state, window_times = run_timed_windows(
            jit_step, state, batch, rng, args.steps, windows=args.windows
        )

    step_time = float(np.median(window_times))
    tokens_per_step = args.accum * args.micro_bs * args.seq
    n_devices = jax.device_count()
    model_tflops = get_model_tflops(
        wrapper.config,
        args.accum * args.micro_bs,
        args.seq,
        gradient_checkpointing_method="block" if args.ckpt else None,
        gradient_checkpointing_args=gc_args,
    )
    mfu = _mfu(model_tflops, step_time)

    mem = {}
    try:
        ms = jax.local_devices()[0].memory_stats()
        if ms:
            mem = {"hbm_gb": round(ms.get("bytes_in_use", 0) / 2**30, 2),
                   "peak_gb": round(ms.get("peak_bytes_in_use", 0) / 2**30, 2)}
    except Exception:
        pass

    print(json.dumps({
        "model": model_type, "n_embd": args.n_embd, "n_layer": args.n_layer,
        "scan": args.scan, "micro_bs": args.micro_bs,
        "accum": args.accum, "ckpt": args.ckpt, "params_m": round(n_params / 1e6, 1),
        "mfu": mfu, "step_ms": round(step_time * 1e3, 1),
        "win_ms": [round(w * 1e3, 1) for w in window_times],
        "tok_s": round(tokens_per_step / step_time / n_devices, 0),
        "compile_s": round(compile_s, 1), **mem,
    }))


if __name__ == "__main__":
    main()
