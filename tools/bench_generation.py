"""Generation micro-bench: prefill latency + per-token decode throughput.

Usage: python tools/bench_generation.py [--n_embd 1024 --n_layer 24 --prompt 1920 --new 128]

Records the prefill-path win from the flash segment-ids conversion (VERDICT r2 weak #4 /
item 8: prefill previously ran masked sdpa over the full cache; now it attends over the
local prompt with the Pallas kernel). Prints one JSON line.
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


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--n_embd", type=int, default=1024)
    p.add_argument("--n_layer", type=int, default=24)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=1920)
    p.add_argument("--new", type=int, default=128)
    p.add_argument("--impl", type=str, default="flash_attention_2")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument(
        "--paged",
        action="store_true",
        help="A/B the paged KV pool against the dense slot pool at a FIXED KV HBM "
        "budget: sustainable concurrent slots, prefix-hit vs cold TTFT, decode tok/s; "
        "emits a BENCH-trajectory JSON line with the slot-capacity ratio",
    )
    p.add_argument(
        "--speculate",
        action="store_true",
        help="A/B speculative decoding (n-gram self-drafting) against plain decode on a "
        "repetitive-text workload: decode tokens/s ratio + accepted-tokens/step; emits "
        "a BENCH-trajectory JSON line with spec_decode_tokens_per_s_ratio",
    )
    p.add_argument(
        "--draft-k",
        type=int,
        default=8,
        help="draft tokens per step for --speculate (K >= 1)",
    )
    p.add_argument(
        "--kv-dtype",
        type=str,
        default=None,
        choices=["bf16", "int8", "fp8"],
        help="A/B a quantized paged KV pool against bf16 paged at FIXED KV HBM bytes: "
        "sustainable concurrent slots + greedy-accuracy gate + model-dtype+pallas-"
        "prefill bit-exactness vs generate_tokens; emits a BENCH-trajectory JSON line "
        "with quantized_sustainable_slots_ratio and ASSERTS the >= 1.8x acceptance",
    )
    p.add_argument(
        "--overload-mix",
        action="store_true",
        help="A/B contention-aware scheduling (priority tiers + paged-KV preemption + "
        "oversubscription) against the reserve-everything baseline on a two-tier "
        "overload: low-tier page hogs submitted first, high-tier interactive "
        "requests arriving mid-flight. Emits a BENCH-trajectory JSON line with "
        "preemption_goodput_ratio and per-tier p99 TTFT, and ASSERTS that aggregate "
        "goodput beats baseline while high-tier p99 TTFT holds",
    )
    p.add_argument(
        "--replicas",
        type=int,
        default=0,
        help="A/B the telemetry-driven router over N engine replicas against 1 replica "
        "at FIXED per-replica slots: aggregate decode tok/s + completed-requests/s "
        "goodput; emits a BENCH-trajectory JSON line with router_goodput_ratio",
    )
    p.add_argument(
        "--seq2seq",
        action="store_true",
        help="bench enc_dec_dolomite decode instead: --prompt is the ENCODER length; the "
        "short-prompt rerun sizes the cross-KV-precompute win (decode tokens/s should "
        "barely depend on encoder length now that K/V are projected once)",
    )
    args = p.parse_args()

    from dolomite_engine_tpu.enums import AttentionImplementation
    from dolomite_engine_tpu.generation_utils import make_generate_fn
    from dolomite_engine_tpu.models import config_from_dict, get_model_class

    backend = jax.default_backend()
    if backend != "tpu":  # tiny CPU fallback so the harness is always runnable
        args.n_embd, args.n_layer, args.prompt, args.new, args.batch = 128, 2, 48, 16, 2

    model_type = "enc_dec_dolomite" if args.seq2seq else "gpt_dolomite"
    config_dict = dict(
        model_type=model_type,
        vocab_size=50304 if backend == "tpu" else 512,
        # CPU headroom past the tiny prompt+new: the --speculate workload needs a longer
        # decode budget to reach its steady state (rope: positions are compute-only, so
        # this costs no params/HBM and leaves the other benches' shapes untouched)
        n_positions=args.prompt + args.new if backend == "tpu" else max(args.prompt + args.new, 256),
        n_embd=args.n_embd,
        n_layer=args.n_layer,
        n_head=args.n_embd // 64,
        num_key_value_heads=8 if backend == "tpu" else 2,
        attention_head_type="gqa",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        add_bias=False,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        bos_token_id=0,
        eos_token_id=1,
        pad_token_id=2,
    )
    config = config_from_dict(config_dict)
    model = get_model_class(model_type)(
        config=config,
        dtype=jnp.bfloat16 if backend == "tpu" else jnp.float32,
        attention_implementation=(
            AttentionImplementation.sdpa if args.seq2seq else AttentionImplementation(args.impl)
        ),
    )

    rng = jax.random.PRNGKey(0)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(3, config.vocab_size, (args.batch, args.prompt)),
        jnp.int32,
    )
    if args.seq2seq:
        params = model.init(rng, ids[:, :8], labels=ids[:, :4])
    else:
        params = model.init(rng, ids[:, :8])
    # left padding on half the rows exercises the mask -> segment-ids prefill path
    pad = args.prompt // 4
    mask = np.ones((args.batch, args.prompt), np.int32)
    mask[::2, :pad] = 0
    ids = jnp.where(jnp.asarray(mask, bool), ids, config.pad_token_id)
    mask = jnp.asarray(mask)

    gen_kwargs = dict(max_new_tokens=args.new, do_sample=False)
    if args.seq2seq:
        # eos=None keeps every row decoding the full budget (pure throughput timing)
        gen_kwargs.update(
            is_encoder_decoder=True, decoder_start_token_id=0, pad_token_id=2, eos_token_id=None
        )
    gen = make_generate_fn(model, **gen_kwargs)
    out, _ = gen(params, ids, mask, rng)
    np.asarray(out)  # compile; the host fetch of the [B, new] int32 result ends the
    # timed region on real completion at ~µs cost

    t0 = time.perf_counter()
    for _ in range(args.reps):
        out, _ = gen(params, ids, mask, rng)
        np.asarray(out)
    total = (time.perf_counter() - t0) / args.reps

    # short-prompt baseline (128 tokens, or 1/4 of the tiny CPU prompt): same decode length,
    # much smaller prefill. The difference between the two runs is the prefill cost DELTA
    # between the long and short prompts — it still contains the short prefill, so it
    # under-reports absolute prefill slightly; decode_tok_s likewise folds the short prefill
    # into the decode steps (a few percent at these shapes).
    short_len = min(128, max(args.prompt // 4, 8))
    gen1 = make_generate_fn(model, **gen_kwargs)
    ids1, mask1 = ids[:, :short_len], mask[:, :short_len]
    out, _ = gen1(params, ids1, mask1, rng)
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        out, _ = gen1(params, ids1, mask1, rng)
        np.asarray(out)
    short = (time.perf_counter() - t0) / args.reps

    decode_tok_s = args.batch * args.new / short  # decode-dominated (incl. short prefill)

    record = {
        "backend": backend,
        "model": model_type,
        "impl": "sdpa" if args.seq2seq else args.impl,
        "batch": args.batch,
        "prompt": args.prompt,
        "short_prompt": short_len,
        "new_tokens": args.new,
        "e2e_s": round(total, 4),
        "short_prompt_s": round(short, 4),
        "prefill_delta_s": round(total - short, 4),
        "decode_tok_s": round(decode_tok_s, 1),
        # one-shot decode surfaces nothing until the whole batch returns, so its TTFT IS
        # the end-to-end time — the number continuous batching exists to beat
        "legacy": {
            "ttft_s": round(total, 4),
            "prefill_tok_s": round(
                args.batch * (args.prompt - short_len) / max(total - short, 1e-9), 1
            ),
            "decode_tok_s": round(decode_tok_s, 1),
        },
    }

    if not args.seq2seq:
        record["engine"] = _bench_engine(model, params, config, args, short_len)
        if args.paged:
            record["paged_ab"] = _bench_paged_ab(
                model, params, config, args, short_len, record["engine"]
            )
        if args.speculate:
            record["speculate_ab"] = _bench_speculate_ab(model, params, config, args)
        if args.kv_dtype:
            record["kv_dtype_ab"] = _bench_kv_dtype_ab(model, params, config, args)
        if args.overload_mix:
            record["overload_mix_ab"] = _bench_overload_mix(model, params, config, args)
        if args.replicas > 0:
            record["router_ab"] = _bench_router_ab(model, params, config, args)

    print(json.dumps(record))

    if not args.seq2seq and args.speculate:
        spec = record["speculate_ab"]
        print(
            json.dumps(
                {
                    "metric": "spec_decode_tokens_per_s_ratio",
                    "value": spec["decode_tok_s_ratio"],
                    "unit": "x plain decode tok/s on the repetitive-text workload",
                    "vs_baseline": spec["decode_tok_s_ratio"],
                    "accepted_tokens_per_step": spec["accepted_tokens_per_step"],
                }
            )
        )

    if not args.seq2seq and args.kv_dtype:
        ab = record["kv_dtype_ab"]
        print(
            json.dumps(
                {
                    "metric": "quantized_sustainable_slots_ratio",
                    "value": ab["sustainable_slots_ratio"],
                    "unit": f"x bf16-paged slots at fixed KV HBM bytes ({args.kv_dtype})",
                    "vs_baseline": ab["sustainable_slots_ratio"],
                    "greedy_token_match": ab["accuracy"]["greedy_token_match"],
                    "kv_bytes_per_token": ab["quantized"]["kv_bytes_per_token"],
                }
            )
        )

    if not args.seq2seq and args.paged:
        ratio = record["paged_ab"]["capacity"]["sustainable_slots_ratio"]
        print(
            json.dumps(
                {
                    "metric": "paged_sustainable_slots_ratio",
                    "value": round(ratio, 2),
                    "unit": "x dense slots at fixed KV HBM bytes",
                    "vs_baseline": round(ratio, 2),
                }
            )
        )

    if not args.seq2seq and args.overload_mix:
        ab = record["overload_mix_ab"]
        print(
            json.dumps(
                {
                    "metric": "preemption_goodput_ratio",
                    "value": ab["goodput_ratio"],
                    "unit": "x reserve-everything goodput (completed req/s) on the "
                    "two-tier overload mix",
                    "vs_baseline": ab["goodput_ratio"],
                    "high_tier_p99_ttft_ms": {
                        "baseline": ab["baseline"]["high_tier_p99_ttft_ms"],
                        "preemption": ab["preemption"]["high_tier_p99_ttft_ms"],
                    },
                    "preemptions": ab["preemption"]["preemptions"],
                }
            )
        )
        # trace-derived attribution: where the mean high-tier TTFT went in each arm
        # (per-request span trees, utils/tracing.critical_path — not aggregate counters)
        print(
            json.dumps(
                {
                    "metric": "high_tier_ttft_split_ms",
                    "unit": "mean high-tier critical-path TTFT decomposition (ms): "
                    "queue wait / prefill / parked, from per-request traces",
                    "baseline": ab["baseline"]["high_tier_ttft_split_ms"],
                    "preemption": ab["preemption"]["high_tier_ttft_split_ms"],
                }
            )
        )

    if not args.seq2seq and args.replicas > 0:
        ab = record["router_ab"]
        print(
            json.dumps(
                {
                    "metric": "router_goodput_ratio",
                    "value": ab["goodput_ratio"],
                    "unit": f"x 1-replica completed req/s at {args.batch} slots/replica",
                    "vs_baseline": ab["goodput_ratio"],
                    "replicas": args.replicas,
                    "aggregate_decode_tok_s": ab["fleet"]["aggregate_decode_tok_s"],
                }
            )
        )


def _bench_engine(model, params, config, args, short_len: int, paged: bool = True) -> dict:
    """Continuous-batching engine on the same model: 2x num_slots requests with mixed
    prompt lengths, per-request TTFT, separate prefill/decode tokens-per-second from the
    engine's own accounting (EngineStats). `paged` selects the KV pool; the page budget
    is pinned to the dense pool's HBM footprint so the two modes are byte-comparable."""
    import numpy as np

    from dolomite_engine_tpu.serving import EngineStats, ServingEngine, serve_batch

    multiple = 64 if jax.default_backend() == "tpu" else 16
    max_len = -(-args.prompt // multiple) * multiple + args.new
    page_size = 64 if jax.default_backend() == "tpu" else 16
    budget_pages = args.batch * (-(-max_len // page_size))
    engine = ServingEngine(
        model,
        params,
        num_slots=args.batch,
        max_len=max_len,
        prefill_bucket_multiple=multiple,
        max_waiting=4 * args.batch,
        eos_token_id=None,  # every request decodes the full budget (pure throughput)
        pad_token_id=config.pad_token_id,
        paged=paged,
        page_size=page_size,
        num_pages=budget_pages + 1,  # + trash page: same KV HBM bytes as the dense pool
    )

    rs = np.random.RandomState(1)

    def specs(n):
        return [
            dict(
                prompt_ids=list(
                    map(int, rs.randint(3, config.vocab_size, args.prompt if i % 2 else short_len))
                ),
                max_new_tokens=args.new,
            )
            for i in range(n)
        ]

    serve_batch(engine, specs(2))  # compile prefill buckets + the decode step
    engine.stats = EngineStats()  # drop warmup/compile time from the measured window

    t0 = time.perf_counter()
    for _ in range(args.reps):  # stats accumulate across reps: averaged rates
        serve_batch(engine, specs(2 * args.batch))
    e2e = (time.perf_counter() - t0) / args.reps

    stats = engine.stats
    return {
        "paged": paged,
        "num_slots": args.batch,
        "requests": 2 * args.batch,
        "e2e_s": round(e2e, 4),
        "ttft_mean_s": round(stats.mean_ttft_s() or 0.0, 4),
        "prefill_tok_s": round(stats.prefill_tok_s() or 0.0, 1),
        "decode_tok_s": round(stats.decode_tok_s() or 0.0, 1),
        "decode_compiles": engine.decode_compiles,
    }


def _bench_speculate_ab(model, params, config, args) -> dict:
    """Speculative vs plain decode on a REPETITIVE-TEXT workload — the regime n-gram
    self-drafting targets (quoting/copying from the prompt, templated continuations;
    greedy decode of small models also converges to repetition loops, which prompt
    lookup rides for free). Same requests, same engine geometry, greedy decode; the only
    difference is `speculate_ngram`. Decode tok/s comes from each engine's own
    accounting (EngineStats), so prefill cost is excluded from the ratio."""
    import numpy as np

    from dolomite_engine_tpu.serving import EngineStats, ServingEngine, serve_batch

    backend_tpu = jax.default_backend() == "tpu"
    multiple = 64 if backend_tpu else 16
    page_size = 64 if backend_tpu else 16
    # a repeated phrase as the prompt, a decode budget long enough for lookup to engage;
    # both sized inside the model's n_positions (the tiny CPU config is only 64 wide)
    rs = np.random.RandomState(23)
    phrase = list(map(int, rs.randint(3, config.vocab_size, 12)))
    prompt_len = max(min(args.prompt // 2, config.n_positions // 4), 14)
    prompt = (phrase * (-(-prompt_len // len(phrase))))[:prompt_len]
    bucket = -(-len(prompt) // multiple) * multiple
    new_tokens = min(max(4 * args.new, 128), config.n_positions - bucket)
    max_len = bucket + new_tokens

    def run(speculate: bool) -> tuple[dict, "ServingEngine"]:
        engine = ServingEngine(
            model,
            params,
            num_slots=args.batch,
            max_len=max_len,
            prefill_bucket_multiple=multiple,
            max_waiting=4 * args.batch,
            eos_token_id=None,  # full decode budget: pure throughput timing
            pad_token_id=config.pad_token_id,
            page_size=page_size,
            speculate_ngram=speculate,
            draft_k=args.draft_k,
        )
        specs = [
            dict(prompt_ids=list(prompt), max_new_tokens=new_tokens)
            for _ in range(args.batch)
        ]
        serve_batch(engine, [dict(s) for s in specs])  # compile warmup
        engine.stats = EngineStats()  # measure steady-state only
        t0 = time.perf_counter()
        for _ in range(args.reps):
            serve_batch(engine, [dict(s) for s in specs])
        e2e = (time.perf_counter() - t0) / args.reps
        stats = engine.stats
        return {
            "e2e_s": round(e2e, 4),
            "decode_tok_s": round(stats.decode_tok_s() or 0.0, 1),
            "decode_steps": stats.decode_steps,
            "decode_tokens": stats.decode_tokens,
        }, engine

    baseline, _ = run(speculate=False)
    speculated, engine = run(speculate=True)
    stats = engine.stats
    return {
        "workload": {
            "prompt": len(prompt),
            "phrase": len(phrase),
            "max_new_tokens": new_tokens,
            "requests": args.batch,
            "draft_k": args.draft_k,
        },
        "baseline": baseline,
        "speculated": speculated,
        "decode_tok_s_ratio": round(
            speculated["decode_tok_s"] / max(baseline["decode_tok_s"], 1e-9), 3
        ),
        "accept_rate": round(stats.accept_rate() or 0.0, 4),
        "accepted_tokens_per_step": round(stats.accepted_tokens_per_step() or 0.0, 3),
        "verify_compiles": engine.verify_compiles,
    }


def _bench_kv_dtype_ab(model, params, config, args) -> dict:
    """Quantized-vs-bf16 paged KV at FIXED KV HBM BYTES (the acceptance A/B).

    Both pools get the same byte budget (the bf16 dense-parity footprint); the
    quantized pool's smaller pages buy proportionally more of them, and since admission
    reserves worst-case PAGES, sustainable concurrency scales with the page count —
    int8 page bytes are value bytes + the amortized per-page scale rows, so the
    expected ratio is just under 2x. Three assertions ride along:

    - capacity: peak concurrently-active slots on a shared-prefix mixed workload must
      reach >= 1.8x the bf16 pool's (the PR acceptance criterion; asserted for
      int8/fp8);
    - accuracy gate: greedy outputs over the quantized pool must match the model-dtype
      reference on >= 70% of tokens (CPU tiny model typically matches 100%);
    - bit-exactness: model-native pages with the ``prefill_attention`` Pallas kernel
      reproduce `generate_tokens` token-for-token (on TPU the model dtype IS bf16, so
      this is the "bf16+pallas prefill bit-exact" acceptance clause).
    """
    import numpy as np

    from dolomite_engine_tpu.generation_utils import generate_tokens
    from dolomite_engine_tpu.ops.pallas import kernel_overrides
    from dolomite_engine_tpu.serving import ServingEngine, serve_batch
    from dolomite_engine_tpu.serving.kv_cache import PagedKVCachePool, QUANTIZED_KV_DTYPES

    backend_tpu = jax.default_backend() == "tpu"
    multiple = 64 if backend_tpu else 16
    page_size = 64 if backend_tpu else 16
    max_len = -(-args.prompt // multiple) * multiple + args.new
    max_pages = -(-max_len // page_size)
    budget_pages_bf16 = args.batch * max_pages

    # per-dtype page bytes from throwaway pools (layers/heads/head_dim included)
    def page_bytes(kv_dtype):
        pool = PagedKVCachePool(model, 1, max_len, page_size, kv_dtype=kv_dtype)
        return pool.kv_bytes_per_token * page_size, pool

    bf16_page_bytes, _ = page_bytes("bf16")
    q_page_bytes, probe_pool = page_bytes(args.kv_dtype)
    budget_bytes = budget_pages_bf16 * bf16_page_bytes
    budget_pages_q = int(budget_bytes // q_page_bytes)

    # slot rows are cheap host state — give BOTH engines enough that the page budget
    # (the thing the A/B fixes) is the binding constraint, not the decode batch width
    num_slots = min(2 + budget_pages_q, 32 * args.batch)

    def capacity_engine(kv_dtype, num_pages):
        return ServingEngine(
            model,
            params,
            num_slots=num_slots,
            max_len=max_len,
            prefill_bucket_multiple=multiple,
            max_waiting=64 * args.batch,
            eos_token_id=None,
            pad_token_id=config.pad_token_id,
            page_size=page_size,
            num_pages=num_pages + 1,  # + trash page
            kv_dtype=kv_dtype,
        )

    # shared system prompt + short unique tails + modest decode budgets: the same
    # capacity workload as --paged, so the two trajectory lines compose
    rs = np.random.RandomState(17)
    shared = list(map(int, rs.randint(3, config.vocab_size, 2 * page_size)))
    new_tokens = max(8, min(args.new, page_size // 2))
    num_requests = 2 * num_slots

    def capacity(kv_dtype, num_pages):
        engine = capacity_engine(kv_dtype, num_pages)
        specs = [
            dict(
                prompt_ids=shared + list(map(int, rs.randint(3, config.vocab_size, 8))),
                max_new_tokens=new_tokens,
            )
            for _ in range(num_requests)
        ]
        serve_batch(engine, specs)
        return engine.stats.peak_active, engine

    bf16_peak, _ = capacity("bf16", budget_pages_bf16)
    q_peak, q_engine = capacity(args.kv_dtype, budget_pages_q)
    ratio = q_peak / max(bf16_peak, 1)

    # accuracy gate: greedy tokens over the quantized pool vs the model-dtype reference
    rs2 = np.random.RandomState(29)
    gate_prompts = [
        list(map(int, rs2.randint(3, config.vocab_size, args.prompt // 2 or 8)))
        for _ in range(max(args.batch, 2))
    ]
    gate_rngs = [jax.random.PRNGKey(900 + i) for i in range(len(gate_prompts))]
    gate_new = min(args.new, 16)

    def reference(prompt, rng):
        ids = jnp.asarray([prompt], jnp.int32)
        out, _ = generate_tokens(
            model, params, ids, jnp.ones_like(ids), rng, max_new_tokens=gate_new,
            do_sample=False, eos_token_id=None, pad_token_id=config.pad_token_id,
        )
        return [int(t) for t in np.asarray(out[0])]

    def engine_tokens(kv_dtype, overrides=None):
        engine = ServingEngine(
            model, params, num_slots=args.batch, max_len=max_len,
            prefill_bucket_multiple=multiple, max_waiting=4 * len(gate_prompts),
            eos_token_id=None, pad_token_id=config.pad_token_id, page_size=page_size,
            kv_dtype=kv_dtype,
        )
        specs = [
            dict(prompt_ids=list(p), max_new_tokens=gate_new, rng=r)
            for p, r in zip(gate_prompts, gate_rngs)
        ]
        if overrides:
            with kernel_overrides(**overrides):
                states = serve_batch(engine, specs)
        else:
            states = serve_batch(engine, specs)
        return [s.tokens for s in states]

    refs = [reference(p, r) for p, r in zip(gate_prompts, gate_rngs)]
    quant_tokens = engine_tokens(args.kv_dtype)
    matched = sum(
        sum(a == b for a, b in zip(t, ref)) for t, ref in zip(quant_tokens, refs)
    ) / (len(refs) * gate_new)

    # bit-exactness clause: model-native pages + the Pallas prefill kernel
    native_tokens = engine_tokens(None, overrides={"prefill_attention": "pallas"})
    prefill_bit_exact = native_tokens == refs

    quantized = args.kv_dtype in QUANTIZED_KV_DTYPES
    assert prefill_bit_exact, (
        "model-dtype pages + pallas prefill_attention diverged from generate_tokens"
    )
    assert matched >= 0.7, f"greedy accuracy gate failed: {matched:.3f} < 0.7"
    if quantized:
        assert ratio >= 1.8, (
            f"quantized sustainable-slots ratio {ratio:.3f} < 1.8x acceptance "
            f"({q_peak} vs {bf16_peak} slots at {budget_bytes / 2**20:.1f} MiB KV)"
        )

    return {
        "kv_dtype": args.kv_dtype,
        "page_size": page_size,
        "kv_budget_mib": round(budget_bytes / 2**20, 2),
        "bf16": {
            "num_pages": budget_pages_bf16,
            "peak_active_slots": int(bf16_peak),
            "page_bytes": round(bf16_page_bytes, 1),
        },
        "quantized": {
            "num_pages": budget_pages_q,
            "peak_active_slots": int(q_peak),
            "page_bytes": round(q_page_bytes, 1),
            "kv_bytes_per_token": round(probe_pool.kv_bytes_per_token, 2),
            "decode_tok_s": round(q_engine.stats.decode_tok_s() or 0.0, 1),
            "decode_compiles": q_engine.decode_compiles,
        },
        "sustainable_slots_ratio": round(ratio, 3),
        "accuracy": {
            "greedy_token_match": round(matched, 4),
            "requests": len(refs),
            "new_tokens": gate_new,
            "prefill_pallas_bit_exact": prefill_bit_exact,
        },
    }


def _mean_ttft_split_ms(states, tier: int) -> dict | None:
    """Mean critical-path TTFT decomposition (ms) over one tier's traced requests —
    where the winning arm's TTFT actually went (queue wait vs prefill vs parked), from
    the per-request span trees rather than aggregate counters."""
    from dolomite_engine_tpu.utils.tracing import critical_path

    splits = []
    for state in states:
        if state.request.priority != tier or state.trace is None:
            continue
        path = critical_path(state.trace.spans)
        if path is None or path["ttft_s"] is None:
            continue
        splits.append(path["buckets"])
    if not splits:
        return None
    return {
        name: round(1e3 * sum(split[name] for split in splits) / len(splits), 3)
        for name in splits[0]
    }


def _bench_overload_mix(model, params, config, args) -> dict:
    """Contention-aware scheduling vs reserve-everything on a two-tier overload.

    The workload is the stranding scenario preemption exists for: low-tier requests
    with long decode budgets grab worst-case page reservations first, then high-tier
    interactive requests arrive mid-flight. Both arms run identical traffic on an
    identical page budget; the only difference is the scheduler contract:

    - baseline: ``preemption="off"``, ratio 1.0 — admission is page-gated by worst-case
      reservations, so most slots idle while reserved-but-unused pages strand capacity
      and high-tier arrivals queue behind running page hogs;
    - treatment: ``preemption="swap"``, ratio 2.0 — admission oversubscribes into the
      stranded reservations and high-tier arrivals evict a low-tier slot instantly,
      parking its pages in the host swap pool (one jitted gather/scatter pair each
      way, byte-identical restore — the cheap preemption mode; drop-and-recompute
      trades the host copy for recompute and is covered by the test suite).

    Goodput is completed requests per second over the full drain (both arms complete
    every request, so it is inverse wall time). Asserted: aggregate goodput beats the
    baseline AND high-tier p99 TTFT holds (no worse than baseline within noise slack —
    in practice it collapses by an order of magnitude), with decode still compiling
    exactly once through the preemption churn."""
    import numpy as np

    from dolomite_engine_tpu.serving import EngineStats, ServingEngine, TierSLO

    backend_tpu = jax.default_backend() == "tpu"
    multiple = 64 if backend_tpu else 16
    page_size = 64 if backend_tpu else 16
    low_prompt_len = page_size
    low_new = 3 * page_size  # the page hog: worst case 4 pages
    high_prompt_len = page_size
    high_new = 8  # interactive: worst case 2 pages
    max_len = low_prompt_len + low_new
    low_worst = -(-(low_prompt_len + low_new) // page_size)
    budget_pages = 2 * low_worst + 1  # two hogs fit outright; everything else contends
    num_low, num_high = 12, 12
    tier_slos = {0: TierSLO(ttft_target_s=0.5), 2: TierSLO(ttft_target_s=30.0)}
    rs = np.random.RandomState(31)

    def make_specs(count, length, new_tokens, tier):
        return [
            dict(
                prompt_ids=list(map(int, rs.randint(3, config.vocab_size, length))),
                max_new_tokens=new_tokens,
                priority=tier,
            )
            for _ in range(count)
        ]

    def run(preemption, ratio):
        engine = ServingEngine(
            model,
            params,
            num_slots=num_low,
            max_len=max_len,
            prefill_bucket_multiple=multiple,
            max_waiting=4 * (num_low + num_high),
            eos_token_id=None,  # full decode budgets: deterministic page pressure
            pad_token_id=config.pad_token_id,
            page_size=page_size,
            num_pages=budget_pages + 1,  # + trash page; same bytes in both arms
            preemption=preemption,
            oversubscribe_ratio=ratio,
            tier_slos=tier_slos,
            # per-request tracing ON in both arms (same host-side cost each side): the
            # spans are what the trace-derived TTFT attribution line is computed from
            trace_requests=True,
        )

        def one_round(measure):
            states = [
                engine.submit(**spec)
                for spec in make_specs(num_low, low_prompt_len, low_new, tier=2)
            ]
            highs = make_specs(num_high, high_prompt_len, high_new, tier=0)
            injected = steps = 0
            t0 = time.perf_counter()
            while engine.has_work() or injected < len(highs):
                if engine.has_work():
                    engine.step()
                steps += 1
                # a high-tier arrival every other step, starting once the hogs run
                if injected < len(highs) and steps >= 2 and steps % 2 == 0:
                    states.append(engine.submit(**highs[injected]))
                    injected += 1
            wall = time.perf_counter() - t0
            return wall, states

        one_round(measure=False)  # warm every program, incl. the preempt/resume paths
        engine.stats = EngineStats()
        wall = 0.0
        states: list = []
        for _ in range(args.reps):  # fresh prompts each round; averaged wall
            round_wall, round_states = one_round(measure=True)
            wall += round_wall / args.reps
            states.extend(round_states)
        assert all(str(s.status) == "completed" for s in states)
        assert engine.decode_compiles == 1, (
            f"decode recompiled under preemption churn: {engine.decode_compiles}"
        )
        high_ttfts = sorted(s.ttft_s for s in states if s.request.priority == 0)
        p99 = high_ttfts[min(len(high_ttfts) - 1, max(0, int(0.99 * len(high_ttfts))))]
        return {
            "preemption": preemption,
            "oversubscribe_ratio": ratio,
            "wall_s": round(wall, 4),
            "goodput_req_s": round(len(states) / args.reps / wall, 3),
            "high_tier_p99_ttft_ms": round(p99 * 1e3, 1),
            "high_tier_ttft_split_ms": _mean_ttft_split_ms(states, tier=0),
            "low_tier_completed": sum(
                1 for s in states if s.request.priority == 2 and str(s.status) == "completed"
            ),
            "preemptions": engine.stats.preemptions,
            "peak_active_slots": engine.stats.peak_active,
            "session_hits": engine.stats.session_hits,
        }

    baseline = run("off", 1.0)
    treatment = run("swap", 2.0)
    ratio = treatment["goodput_req_s"] / max(baseline["goodput_req_s"], 1e-9)
    # the acceptance pair: goodput beats reserve-everything AND the top tier's p99
    # TTFT holds (small slack absorbs scheduler-clock noise; the expected gap is >10x)
    assert ratio > 1.0, (
        f"overload-mix goodput ratio {ratio:.3f} <= 1.0 "
        f"({treatment['goodput_req_s']} vs {baseline['goodput_req_s']} req/s)"
    )
    assert treatment["high_tier_p99_ttft_ms"] <= baseline["high_tier_p99_ttft_ms"] * 1.1 + 50.0, (
        f"high-tier p99 TTFT degraded under preemption: "
        f"{treatment['high_tier_p99_ttft_ms']}ms vs {baseline['high_tier_p99_ttft_ms']}ms"
    )
    return {
        "workload": {
            "page_size": page_size,
            "kv_budget_pages": budget_pages,
            "low_tier": {"requests": num_low, "prompt": low_prompt_len, "max_new": low_new},
            "high_tier": {"requests": num_high, "prompt": high_prompt_len, "max_new": high_new},
            "tier_slos_ttft_ms": {
                str(t): round(s.ttft_target_s * 1e3, 1) for t, s in tier_slos.items()
            },
        },
        "baseline": baseline,
        "preemption": treatment,
        "goodput_ratio": round(ratio, 3),
        "high_tier_p99_ttft_ratio": round(
            treatment["high_tier_p99_ttft_ms"] / max(baseline["high_tier_p99_ttft_ms"], 1e-9),
            3,
        ),
    }


def _bench_router_ab(model, params, config, args) -> dict:
    """Router fleet vs single replica at FIXED per-replica slots (`--batch` each).

    The same mixed workload — a shared page-aligned prefix on half the requests (so
    prefix-affinity routing has something to exploit) plus unique prompts — is driven
    through (a) one engine and (b) N replicas behind the router, each round sized at
    ``requests_per_slot * total slots``. Goodput is completed requests per second;
    aggregate decode tok/s sums every replica's own accounting. On a single CPU host
    the replicas time-share one device, so the ratio mostly measures router overhead —
    the TPU fleet run is where N-replica scaling shows up; the JSON line exists to
    track the trajectory either way."""
    import numpy as np

    from dolomite_engine_tpu.serving import EngineStats, ServingEngine
    from dolomite_engine_tpu.serving.cluster import EngineReplica, Router, route_batch

    backend_tpu = jax.default_backend() == "tpu"
    multiple = 64 if backend_tpu else 16
    page_size = 64 if backend_tpu else 16
    max_len = -(-args.prompt // multiple) * multiple + args.new
    rs = np.random.RandomState(11)
    shared = list(map(int, rs.randint(3, config.vocab_size, 2 * page_size)))

    def make_specs(count):
        specs = []
        for i in range(count):
            if i % 2:
                ids = shared + list(map(int, rs.randint(3, config.vocab_size, 8)))
            else:
                ids = list(map(int, rs.randint(3, config.vocab_size, args.prompt)))
            specs.append(dict(prompt_ids=ids, max_new_tokens=args.new))
        return specs

    def build_fleet(n):
        replicas = []
        for replica_id in range(n):
            engine = ServingEngine(
                model,
                params,
                num_slots=args.batch,
                max_len=max_len,
                prefill_bucket_multiple=multiple,
                max_waiting=8 * args.batch * max(n, 1),
                eos_token_id=None,
                pad_token_id=config.pad_token_id,
                page_size=page_size,
            )
            replicas.append(EngineReplica(replica_id, engine))
        return Router(replicas)

    def run(n):
        router = build_fleet(n)
        requests = 2 * args.batch * n
        route_batch(router, make_specs(requests))  # compile warmup
        for replica in router.replicas:
            replica.engine.stats = EngineStats()
        t0 = time.perf_counter()
        for _ in range(args.reps):
            route_batch(router, make_specs(requests))
        wall = (time.perf_counter() - t0) / args.reps
        completed = sum(r.engine.stats.completed for r in router.replicas) / args.reps
        decode_tokens = sum(r.engine.stats.decode_tokens for r in router.replicas)
        decode_seconds = sum(r.engine.stats.decode_seconds for r in router.replicas)
        hit_rate = router.stats.affinity_hit_rate()
        return {
            "replicas": n,
            "requests_per_round": requests,
            "wall_s": round(wall, 4),
            "goodput_req_s": round(completed / wall, 2),
            "aggregate_decode_tok_s": round(
                decode_tokens / max(decode_seconds, 1e-9), 1
            ),
            "prefix_affinity_hit_rate": None if hit_rate is None else round(hit_rate, 3),
            "per_replica_routed": {
                str(k): v for k, v in sorted(router.stats.per_replica_routed.items())
            },
        }

    baseline = run(1)
    fleet = run(args.replicas)
    return {
        "slots_per_replica": args.batch,
        "baseline": baseline,
        "fleet": fleet,
        "goodput_ratio": round(
            fleet["goodput_req_s"] / max(baseline["goodput_req_s"], 1e-9), 3
        ),
    }


def _bench_paged_ab(model, params, config, args, short_len: int, paged_engine_record: dict) -> dict:
    """Paged-vs-dense A/B at a FIXED KV HBM budget (the dense pool's bytes).

    Three measurements:
    - decode tok/s apples-to-apples: the default `engine` record is the paged pool on the
      dense-compatible workload; re-run the same workload on the dense pool.
    - sustainable concurrent slots: realistic mixed traffic (shared system prompt + short
      unique tails, modest decode budgets) against the SAME page budget. The dense pool is
      pinned at `batch` slots because HBM = num_slots * max_len by construction; the paged
      pool admits until pages run out (worst-case reservation, so no preemption needed) —
      `peak_active` is the sustainable concurrency.
    - TTFT: the same prompt cold (empty prefix cache) vs warm (prefix resident).
    """
    import numpy as np

    from dolomite_engine_tpu.serving import ServingEngine, serve_batch

    backend_tpu = jax.default_backend() == "tpu"
    multiple = 64 if backend_tpu else 16
    page_size = 64 if backend_tpu else 16
    max_len = -(-args.prompt // multiple) * multiple + args.new
    budget_pages = args.batch * (-(-max_len // page_size))

    dense_record = _bench_engine(model, params, config, args, short_len, paged=False)

    # realistic mixed traffic: a shared system prompt (page-aligned), short unique tails,
    # decode budget well under the worst case the dense pool must provision for
    rs = np.random.RandomState(7)
    shared = list(map(int, rs.randint(3, config.vocab_size, 2 * page_size)))
    tail_len = 8
    new_tokens = max(8, min(args.new, page_size // 2))
    num_requests = 4 * args.batch

    def capacity_engine():
        return ServingEngine(
            model,
            params,
            num_slots=4 * args.batch,  # slot rows are host state; KV HBM stays fixed
            max_len=max_len,
            prefill_bucket_multiple=multiple,
            max_waiting=4 * num_requests,
            eos_token_id=None,
            pad_token_id=config.pad_token_id,
            paged=True,
            page_size=page_size,
            num_pages=budget_pages + 1,
        )

    def spec():
        return dict(
            prompt_ids=shared + list(map(int, rs.randint(3, config.vocab_size, tail_len))),
            max_new_tokens=new_tokens,
        )

    engine = capacity_engine()
    # compile warmup with an UNRELATED prompt of the same shape (twice: the repeat warms
    # the prefix-hit path's short final chunk + page copy too), so the cold/warm TTFT
    # numbers below measure prefill work, not jit compiles
    warmup = dict(
        prompt_ids=list(map(int, rs.randint(3, config.vocab_size, len(shared) + tail_len))),
        max_new_tokens=new_tokens,
    )
    serve_batch(engine, [dict(warmup)])
    serve_batch(engine, [dict(warmup)])
    cold = serve_batch(engine, [spec()])[0]  # its prefix is not resident: full prefill
    warm = serve_batch(engine, [spec()])[0]  # shared pages resident: prefill skips them
    serve_batch(engine, [spec() for _ in range(num_requests)])
    peak = engine.stats.peak_active
    ratio = peak / args.batch

    return {
        "page_size": page_size,
        "kv_budget_pages": budget_pages,
        "dense": dense_record,
        "paged": paged_engine_record,
        "decode_tok_s_ratio": round(
            paged_engine_record["decode_tok_s"] / max(dense_record["decode_tok_s"], 1e-9), 3
        ),
        "capacity": {
            "workload": {
                "shared_prefix": len(shared),
                "unique_tail": tail_len,
                "max_new_tokens": new_tokens,
                "requests": num_requests,
            },
            "dense_sustainable_slots": args.batch,
            "paged_peak_active_slots": peak,
            "sustainable_slots_ratio": round(ratio, 3),
            "cold_ttft_s": round(cold.ttft_s or 0.0, 4),
            "prefix_hit_ttft_s": round(warm.ttft_s or 0.0, 4),
            "prefix_hit_rate": round(engine.stats.prefix_hit_rate() or 0.0, 4),
            "decode_tok_s": round(engine.stats.decode_tok_s() or 0.0, 1),
            "decode_compiles": engine.decode_compiles,
        },
    }


if __name__ == "__main__":
    main()
