"""Driver ``serve_open_loop``: ``ServingEngine`` under an open-loop arrival schedule.

One host loop in the process that holds the chip: submit every request whose due time has
passed, call ``engine.step()``, sleep only when the engine has no work. Token times come
from ``on_token`` (the engine calls it after the token reached the host); latencies count
from the time a request was DUE, not from when it was submitted, so a stall is charged to
every request it delays, and the generator's lateness is printed. A ramp at the cell's rate
fills the slots before the window opens; requests due in the window are followed to their
end under the traffic file's drain limit, and what is unfinished then counts as failed and
as the worst latency.

Set-up makes the weights on the device from ``--seed`` in one jitted call (bf16, the type
served), builds the engine with the configuration's keywords, and runs warm-up requests
that reach every program the traffic can reach: decode and a chunk-prefill program for
each (width, final) pair the chunk budget and bucket allow.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark import traffic as traffic_lib, weights as W
from benchmark.program_layout import TINY, unrolled_program_tree
from benchmark.harness import Check, RunResult, fullest_memory_stats, say
from benchmark.reduce_trace import reduce_trace


def model_config(ctx) -> dict:
    cfg = dict(ctx.cell.config["pretrained_config"])
    cfg["n_layer"] = ctx.cell.config["serve"]["n_layer"]
    if ctx.tiny:
        cfg.update(TINY, n_layer=2)
    return cfg


def build_engine(ctx, cfg: dict):
    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu.enums import AttentionImplementation, Mode
    from dolomite_engine_tpu.model_wrapper import ModelWrapper
    from dolomite_engine_tpu.serving import ServingEngine

    config = ctx.cell.config
    wrapper = ModelWrapper(
        mode=Mode.inference,
        pretrained_config=cfg,
        model_class=config["model_class"],
        dtype=config["dtype"],
        attention_implementation=AttentionImplementation(config["attention_implementation"]),
    )
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda key: unrolled_program_tree(W.make_all(cfg, key, jnp.bfloat16)))(W.base_key(ctx.seed))
    )
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say(f"serve: {weight_bytes / 2**30:.2f} GiB of bf16 weights from the seed in {time.perf_counter() - t0:.1f} s")
    keywords = dict(config["serve"]["engine"])
    if ctx.tiny:
        keywords.update(num_slots=4, max_len=cfg["n_positions"], num_pages=96, page_size=8,
                        prefill_chunk_tokens=64, prefill_bucket_multiple=32)
    engine = ServingEngine(wrapper.model, params, trace_requests=ctx.trace, **keywords)
    return engine, keywords, weight_bytes


def warm_up(engine, keywords: dict, vocab: int, seed: int) -> set:
    """Reach every program: with a chunk budget B and bucket M the chunk widths are the
    multiples of M up to B, each final or not. A lone prompt of w - 1 tokens runs (w,
    final); two prompts admitted together, one of B - w + 1 tokens and one long, leave the
    second a non-final chunk of width w. Returns the (width, final) pairs compiled."""
    budget, multiple = keywords["prefill_chunk_tokens"], keywords["prefill_bucket_multiple"]
    slots = keywords["num_slots"]
    rng = np.random.default_rng(seed + 1)

    def prompt(n):
        return rng.integers(1, vocab, size=n).tolist()

    def drain(*lengths):
        for n in lengths:
            engine.submit(prompt(n), 2, eos_token_id=None)
        while engine.step():
            pass

    widths = list(range(multiple, budget + 1, multiple))
    for width in widths:
        drain(width - 1)  # (width, final)
    for width in widths:
        if width == budget:
            drain(budget + 1)  # (budget, not final), then a short final chunk
        else:
            # the first takes budget - width + 1 tokens of the step's budget (its one, final
            # chunk); the second gets the width - 1 left: a non-final chunk of that bucket
            drain(budget - width + 1, 2 * budget)
    # the decode program at the full slot batch
    drain(*([multiple // 2] * slots))
    # the prefix cache's copy-on-write of a partly shared page: two random prompts that
    # begin with the same token are enough to reach it (one run in forty did, PR 23), so
    # a second prompt repeats the first tokens of one just served
    first = prompt(multiple // 2)
    for ids in (first, first[:5] + prompt(multiple // 2)):
        engine.submit(ids, 2, eos_token_id=None)
        while engine.step():
            pass
    if engine.prefix is not None and engine.pool._copy_fn is None:
        raise RuntimeError("warm-up did not reach the prefix cache's page copy")
    return set(engine._chunk_fns)


def run(ctx) -> RunResult:
    import jax

    from dolomite_engine_tpu.serving.scheduler import QueueFullError

    traffic = ctx.cell.traffic
    cfg = model_config(ctx)
    engine, keywords, weight_bytes = build_engine(ctx, cfg)
    pool = engine.pool
    say(
        f"serve: n_layer {cfg['n_layer']}, {keywords['num_slots']} slots, max_len {keywords['max_len']}, "
        f"pool {pool.num_pages} pages x {pool.page_size} tokens ({pool.kv_bytes_per_token / 1024:.0f} KiB of K/V a token), "
        f"chunk budget {keywords['prefill_chunk_tokens']}, bucket {keywords['prefill_bucket_multiple']}"
    )
    t0 = time.perf_counter()
    compiled = warm_up(engine, keywords, cfg["vocab_size"], ctx.seed)
    widths = range(keywords["prefill_bucket_multiple"], keywords["prefill_chunk_tokens"] + 1, keywords["prefill_bucket_multiple"])
    wanted = {(w, f) for w in widths for f in (True, False)}
    say(f"serve: warm-up reached {sorted(compiled)} and decode in {time.perf_counter() - t0:.1f} s")
    if compiled != wanted or engine.decode_compiles != 1:
        raise RuntimeError(f"warm-up reached {sorted(compiled)}, the traffic can reach {sorted(wanted)}")

    length_scale = cfg["n_positions"] / ctx.cell.config["pretrained_config"]["n_positions"] if ctx.tiny else 1.0
    # a traced run goes on for some seconds after the window, at the same rate, and traces
    # those: starting and stopping the profiler stalls the host for seconds each, which
    # inside the window would land on the requests' latencies
    # (and the trace starts skip_seconds after the close, when the window's last arrivals
    # have their first tokens: the start's stall would else be their time to first token)
    trace_skip_s = traffic["trace"]["skip_seconds"] if ctx.trace else 0.0
    tail_s = trace_skip_s + traffic["trace"]["seconds"] if ctx.trace else 0.0
    arrivals, ramp_s = traffic_lib.open_loop_schedule(
        traffic, ctx.seed, ctx.seconds, cfg["vocab_size"], length_scale, tail_seconds=tail_s
    )
    window_end = ramp_s + ctx.seconds
    records = [
        dict(index=a.index, due_s=a.due_s, measured=a.measured, prompt_tokens=len(a.prompt_ids),
             max_new_tokens=a.max_new_tokens, token_times=[], state=None, lateness_s=None, refused=False)
        for a in arrivals
    ]
    trace_dir = os.path.join(ctx.out_dir, "trace")
    trace_window: list = []
    live_samples: list = []
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter

    start = clock()
    setup_s = start + ramp_s - ctx.process_start  # the ramp is warm-up: the window opens after it
    next_arrival, opened, closed, backlog = 0, False, False, (0, 0)
    deadline = window_end + tail_s + traffic["drain_limit_seconds"]
    while True:
        now = clock() - start
        if not opened and now >= ramp_s:
            opened = True
            ctx.compiles.open()
        if opened and not closed and now >= window_end:
            closed = True
            ctx.compiles.close()
            backlog = (engine.scheduler.queue_depth, pool.num_active)
        if ctx.trace and now >= window_end + trace_skip_s:
            if not trace_window:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # python frames slow the host they measure
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                trace_window.append(clock())
            elif len(trace_window) == 1 and now >= window_end + tail_s:
                trace_window.append(clock())
                jax.profiler.stop_trace()
        while next_arrival < len(arrivals) and arrivals[next_arrival].due_s <= now:
            arrival, record = arrivals[next_arrival], records[next_arrival]
            with annotate("bench.submit"):
                times = record["token_times"]
                try:
                    record["state"] = engine.submit(
                        arrival.prompt_ids, arrival.max_new_tokens, eos_token_id=None,
                        on_token=lambda token, times=times: times.append(clock() - start),
                    )
                except QueueFullError:
                    record["refused"] = True  # counts as failed and as the worst latency
            record["lateness_s"] = clock() - start - arrival.due_s
            next_arrival += 1
        if engine.has_work():
            with annotate("bench.engine_step"):
                engine.step()
            if len(trace_window) == 1:
                live_samples.append(int(pool.lengths.sum()))
        elif next_arrival < len(arrivals):
            with annotate("bench.wait_arrival"):
                time.sleep(min(max(arrivals[next_arrival].due_s - (clock() - start), 0.0), 0.002))
        elif now < window_end + tail_s:
            with annotate("bench.wait_window_end"):
                time.sleep(min(window_end + tail_s - now, 0.002))
        else:
            break
        if closed and (not ctx.trace or len(trace_window) == 2) and all(r["refused"] or (r["state"] is not None and r["state"].done) for r in records if r["measured"]):
            break
        if clock() - start > deadline:
            say(f"serve: drain limit of {traffic['drain_limit_seconds']} s reached")
            break
    end = clock() - start
    ctx.compiles.close()
    if len(trace_window) == 1:
        trace_window.append(clock())
        jax.profiler.stop_trace()
    memory_stats = fullest_memory_stats(jax.devices())

    # ---- the window's metrics
    measured = [r for r in records if r["measured"]]
    done = [r for r in measured if r["state"] is not None and str(r["state"].status) == "completed"
            and len(r["token_times"]) == r["max_new_tokens"]]
    failed = len(measured) - len(done)
    ttft = [(r["token_times"][0] - r["due_s"]) if r["token_times"] else (end - r["due_s"]) for r in measured]
    gaps = [b - a for r in measured for a, b in zip(r["token_times"], r["token_times"][1:])]
    delivered = sum(1 for r in records for t in r["token_times"] if ramp_s <= t < window_end)
    lateness = [r["lateness_s"] for r in records if r["lateness_s"] is not None]
    # the tails and the means alike: a cell's end-to-end metrics are those among them that
    # BENCHMARK.json names for it
    end_to_end = {
        "ttft_p95_ms": 1e3 * traffic_lib.percentile(ttft, 0.95),
        "itl_p95_ms": 1e3 * traffic_lib.percentile(gaps, 0.95),
        "ttft_mean_ms": 1e3 * float(np.mean(ttft)),
        "itl_mean_ms": 1e3 * float(np.mean(gaps)),
        "serve_out_tokens_per_s": delivered / ctx.seconds,
        "setup_s": setup_s,
    }
    offered = sum(r["max_new_tokens"] for r in measured) / ctx.seconds
    say(
        f"serve: set-up {setup_s:.2f} s (ramp {ramp_s} s included); {len(measured)} requests due in the window, "
        f"{len(done)} completed, {failed} failed or unfinished; at the window's close {backlog[0]} waiting and "
        f"{backlog[1]} in slots, drained {end - window_end:.2f} s later"
    )
    say(
        f"serve: ttft mean {end_to_end['ttft_mean_ms']:.1f} ms, median {1e3 * traffic_lib.percentile(ttft, 0.5):.1f}, "
        f"p75 {1e3 * traffic_lib.percentile(ttft, 0.75):.1f}, p95 {end_to_end['ttft_p95_ms']:.1f} ({len(ttft)} samples); "
        f"itl mean {end_to_end['itl_mean_ms']:.2f} ms, median {1e3 * traffic_lib.percentile(gaps, 0.5):.2f}, p90 "
        f"{1e3 * traffic_lib.percentile(gaps, 0.9):.2f}, p95 {end_to_end['itl_p95_ms']:.2f}, p99 "
        f"{1e3 * traffic_lib.percentile(gaps, 0.99):.2f} ({len(gaps)} samples); {end_to_end['serve_out_tokens_per_s']:.1f} "
        f"tokens/s delivered (offered {offered:.1f}); generator lateness median "
        f"{1e3 * traffic_lib.percentile(lateness, 0.5):.2f} ms, max {1e3 * max(lateness):.1f} ms; "
        f"peak active {engine.stats.peak_active}, decode steps {engine.stats.decode_steps}"
    )
    say("serve: ttft of the window's requests in arrival order, ms: " + " ".join(f"{1e3 * t:.0f}" for t in ttft))
    checks = [
        Check("chunk_programs_after_window", engine.chunk_compiles, len(wanted), engine.chunk_compiles == len(wanted)),
        Check("decode_programs_after_window", engine.decode_compiles, 1, engine.decode_compiles == 1),
    ]

    for r in records:
        state = r["state"]
        r["tokens"] = list(state.tokens) if state is not None else []
        r["queue_wait_s"] = None
        if state is not None and state.trace is not None:
            spans = [s for s in state.trace.find("queue_wait") if s.duration_s is not None]
            r["queue_wait_s"] = sum(s.duration_s for s in spans) if spans else None
        r["prompt_ids"] = arrivals[r["index"]].prompt_ids
        r["state"] = None
    facts = dict(
        cfg=cfg, weight_bytes=weight_bytes, kv_bytes_per_token=pool.kv_bytes_per_token,
        decode_program="_decode_impl_paged", chunk_program="chunk",
    )
    if ctx.trace:
        facts.update(traced_serve_s=trace_window[1] - trace_window[0],
                     mean_live_kv_tokens=float(np.mean(live_samples)) if live_samples else 0.0)

    # ---- correct: free the engine, then the reference reads a seeded sample of the window's
    # finished requests (the longest among them)
    del engine, pool
    jax.clear_caches()
    gc.collect()
    if not ctx.skip_check:
        checks += compare_with_reference(ctx, cfg, done, control=ctx.control)
    result = RunResult(
        attempted=len(measured), failed=failed, end_to_end=end_to_end, checks=checks,
        memory_stats=memory_stats, requests=records, facts=facts,
    )
    if ctx.trace:
        result.trace = reduce_trace(trace_dir, facts["traced_serve_s"])
        say(f"serve: traced {facts['traced_serve_s']:.2f} s; programs {result.trace.program_names()}")
    return result


def sample_for_check(done: list, count: int, seed: int) -> list:
    """The longest finished request and ``count - 1`` others drawn from the seed."""
    if not done:
        return []
    ordered = sorted(done, key=lambda r: -(r["prompt_tokens"] + len(r["tokens"])))
    rest = ordered[1:]
    rng = np.random.default_rng(seed + 2)
    picks = rng.choice(len(rest), size=min(count - 1, len(rest)), replace=False) if rest else []
    return [ordered[0]] + [rest[i] for i in sorted(picks)]


def compare_with_reference(ctx, cfg: dict, done: list, control: bool = False) -> list:
    import jax.numpy as jnp

    from benchmark.reference import gpt_dense

    limits = ctx.cell.limits
    sample = sample_for_check(done, ctx.cell.traffic["check_requests"], ctx.seed)
    if not sample:
        return [Check("served_token_gap_widest", float("inf"), limits.get("served_token_gap_widest", float("nan")), False, "(no request finished)")]
    t0 = time.perf_counter()
    gaps = gpt_dense.served_token_gaps(
        cfg, ctx.seed, [(r["prompt_ids"], r["tokens"]) for r in sample], dtype=jnp.bfloat16,
        bucket=64 if ctx.tiny else 512, control=control,
    )
    tokens = sum(len(g["gap"]) for g in gaps)
    widest = max(float(np.max(g["gap"])) for g in gaps)
    exact = sum(int(np.sum(g["gap"] == 0)) for g in gaps)
    mean = float(np.mean(np.concatenate([g["gap"] for g in gaps])))
    say(
        f"serve: reference read {len(sample)} requests ({tokens} served tokens, longest "
        f"{sample[0]['prompt_tokens']} + {len(sample[0]['tokens'])}) in {time.perf_counter() - t0:.1f} s; "
        f"{exact}/{tokens} served tokens are the reference's first choice; mean gap {mean:.5f}"
    )
    limit = limits.get("served_token_gap_widest", float("nan"))
    limit_mean = limits.get("served_token_gap_mean", float("nan"))
    checks = [
        Check("served_token_gap_widest", widest, limit, widest <= limit, "(of a logit row's standard deviation, below the reference's best)"),
        Check("served_token_gap_mean", mean, limit_mean, mean <= limit_mean),
    ]
    if control:
        c_widest = max(float(np.max(g["control_gap"])) for g in gaps)
        c_mean = float(np.mean(np.concatenate([g["control_gap"] for g in gaps])))
        say(f"serve: control (fp8 reference) widest gap {c_widest:.5f}, mean gap {c_mean:.5f}")
        checks.append(Check("control_gap_widest", c_widest, limit, c_widest > limit, "(the control must exceed the limit)"))
        checks.append(Check("control_gap_mean", c_mean, limit_mean, c_mean > limit_mean, "(the control must exceed the limit)"))
    return checks
