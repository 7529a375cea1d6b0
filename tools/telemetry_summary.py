"""Render a run's JSONL telemetry sink into markdown tables.

    python tools/telemetry_summary.py <run_dir | telemetry_dir | *.jsonl> [...]

Accepts one or more sink files, or directories (a run's save_path or its `telemetry/`
subdir — every `*.jsonl` underneath is read and merged, so multi-host runs summarize in one
call). Output is paste-ready for PERF.md / bench reports: step-time percentiles
(steady-state, first-step compile excluded), the goodput breakdown as a % of wall-clock,
MFU, cumulative counter totals, plus the training-health records — run exit status, the
`model_report` introspection (param groups/bytes/sharding/HBM), the latest per-group
`health` stats, anomaly events, and pointers to any crash flight records in the run dir;
for a train loop the median split of an iteration and its slowest iterations, each with the
span that holds the excess and what else the host did in it (gc, other threads, the process).

Schema: docs/OBSERVABILITY.md (`dolomite_engine_tpu/utils/telemetry.py` writes it).
Malformed lines — the one line a SIGKILL may tear — are counted and skipped, never fatal.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def find_sink_files(paths: list[str]) -> list[str]:
    files: list[str] = []
    for path in paths:
        if os.path.isdir(path):
            found = sorted(
                glob.glob(os.path.join(path, "**", "*.jsonl"), recursive=True)
            )
            files.extend(found)
        else:
            files.append(path)
    # de-dup while keeping order (a dir arg plus an explicit file inside it)
    seen: set[str] = set()
    unique = []
    for f in files:
        real = os.path.realpath(f)
        if real not in seen:
            seen.add(real)
            unique.append(f)
    return unique


def read_records(files: list[str]) -> tuple[list[dict], int]:
    """All parseable records across the sinks, plus the count of torn/invalid lines."""
    records: list[dict] = []
    bad_lines = 0
    for path in files:
        # errors="replace": a crash can tear the last line mid-multibyte-character; the
        # mangled line must count as bad, not raise UnicodeDecodeError for the whole sink
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except json.JSONDecodeError:
                    bad_lines += 1
                    continue
                if isinstance(record, dict):
                    records.append(record)
                else:
                    bad_lines += 1
    return records, bad_lines


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list (no numpy dependency needed)."""
    if not sorted_values:
        return float("nan")
    rank = max(int(round(q / 100.0 * len(sorted_values) + 0.5)) - 1, 0)
    return sorted_values[min(rank, len(sorted_values) - 1)]


def _format_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.4g} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.4g} TiB"


def format_model_report(report: dict) -> list[str]:
    """Markdown rendering of one `model_report` record (shared with tools/doctor.py)."""
    lines: list[str] = []
    totals = report.get("totals") or {}
    hbm = report.get("hbm") or {}
    lines.append(
        f"model: {totals.get('parameters', 0):,} parameters, "
        f"{_format_bytes(totals.get('param_bytes', 0))} params + "
        f"{_format_bytes(totals.get('optimizer_bytes', 0))} optimizer state"
        + (
            f" + {_format_bytes(totals['fp8_bytes'])} fp8 state"
            if totals.get("fp8_bytes")
            else ""
        )
    )
    mesh = report.get("mesh")
    device_line = f"devices: {report.get('devices', '?')} [{report.get('device_kind', '?')}]"
    if mesh:
        device_line += f", mesh {dict(zip(mesh['axis_names'], mesh['shape']))}"
    lines.append(device_line)
    state_per_device = hbm.get("state_bytes_per_device")
    if state_per_device is not None:
        memory_line = f"state per device: {_format_bytes(state_per_device)}"
        if hbm.get("bytes_limit"):
            memory_line += (
                f" of {_format_bytes(hbm['bytes_limit'])} detected HBM "
                f"({100.0 * hbm.get('state_fraction_of_limit', 0):.1f}%)"
            )
            if hbm.get("state_fraction_of_limit", 0) > 0.9:
                memory_line += " — **WARNING: little or no headroom for activations**"
        else:
            memory_line += " (device capacity not detected)"
        lines.append(memory_line)
    remat = report.get("remat")
    if remat:
        remat_line = (
            f"remat: policy {remat.get('policy', 'full')} "
            f"(checkpoint_every {remat.get('checkpoint_every', 0)})"
        )
        if remat.get("activation_bytes_per_replica") is not None:
            remat_line += (
                f", ~{_format_bytes(remat['activation_bytes_per_replica'])} saved "
                f"activations/replica ({'+' if remat.get('delta_vs_full_bytes', 0) >= 0 else ''}"
                f"{_format_bytes(remat.get('delta_vs_full_bytes', 0))} vs full)"
            )
        if remat.get("host_offload_bytes_per_replica"):
            remat_line += (
                f", {_format_bytes(remat['host_offload_bytes_per_replica'])} offloaded to host"
            )
        lines.append(remat_line)
    if report.get("model_tflops_per_step"):
        lines.append(f"analytic model TFLOPs/step/group: {report['model_tflops_per_step']:.4g}")
    cost = report.get("cost_analysis")
    if cost:
        lines.append(
            "compiled-step cost analysis: "
            + ", ".join(f"{k} = {v:.4g}" for k, v in sorted(cost.items()))
        )
    groups = report.get("param_groups") or {}
    if groups:
        lines.append("")
        lines.append("| parameter group | params | bytes | bytes/device | sharding |")
        lines.append("|---|---|---|---|---|")
        for name in sorted(groups):
            g = groups[name]
            shardings = ", ".join(g.get("shardings") or []) or "-"
            lines.append(
                f"| {name} | {g.get('parameters', 0):,} | {_format_bytes(g.get('bytes', 0))} "
                f"| {_format_bytes(g.get('bytes_per_device', 0))} | {shardings} |"
            )
    return lines


def span_holding_the_excess(split: dict, inner: dict, history: list[dict]) -> str | None:
    """Which span made an iteration slow: the one whose seconds exceed by most the median of
    that span over `history` (``{span: seconds}`` of the other iterations; a span is compared
    with the iterations that ran it), a nested span instead of its parent where it holds at
    least half of that excess. The rule of `utils/telemetry.span_holding_the_excess` (the
    ``anomaly`` event's ``blame``), written again because this tool imports nothing of the
    package; `tests/test_telemetry.py` holds the two to one answer."""

    def excess(parts: dict) -> dict:
        return {
            name: seconds - statistics.median([h[name] for h in history if name in h] or [0.0])
            for name, seconds in parts.items()
        }

    if not split:
        return None
    outer, nested = excess(split), excess(inner)
    name = max(outer, key=outer.get)
    if nested:
        deepest = max(nested, key=nested.get)
        if nested[deepest] >= 0.5 * outer[name]:
            return deepest
    return name


def format_slowest_iterations(steps: list[dict], count: int = 5) -> list[str]:
    """The slowest steady iterations of a train loop, each with the span that holds its
    excess over that span's median and what else the host did in it (``t.gc``, ``t.off_loop``,
    ``t.host``): what an untraced run that held a stall says about it. Iterations under 1.2 x
    the median are left out; nothing is printed when none is slower."""
    median = statistics.median(r["t"]["wall"] for r in steps)
    slow = sorted((r for r in steps if r["t"]["wall"] >= 1.2 * median), key=lambda r: -r["t"]["wall"])[:count]
    if not slow or median <= 0:
        return []
    flat = [{**r["t"]["split"], **r["t"].get("inner", {})} for r in steps]
    lines = [
        f"| slowest iterations (median {1e3 * median:.4g} ms) | wall ms | x median | span holding the excess "
        "| gc | off the loop's thread | host |",
        "|---|---|---|---|---|---|---|",
    ]
    for record in slow:
        t = record["t"]
        inner = t.get("inner", {})
        blame = span_holding_the_excess(t["split"], inner, flat)
        seconds = {**t["split"], **inner}.get(blame, 0.0)
        gc, host = t.get("gc"), t.get("host")
        collected = f"{gc['count']} in {1e3 * gc['seconds']:.4g} ms" if gc else "-"
        beside = ", ".join(f"{k} {1e3 * v:.4g} ms" for k, v in t.get("off_loop", {}).items()) or "-"
        process = (
            f"{host['nivcsw']} involuntary switches, {host['majflt']} major faults, cpu {1e3 * host['cpu']:.4g} ms"
            if host
            else "-"
        )
        lines.append(
            f"| step {record['step']} | {1e3 * t['wall']:.4g} | {t['wall'] / median:.2f} "
            f"| {blame} {1e3 * seconds:.4g} ms | {collected} | {beside} | {process} |"
        )
    lines.append("")
    return lines


def summarize(records: list[dict]) -> str:
    steps = [r for r in records if r.get("kind") == "step"]
    windows = [r for r in records if r.get("kind") == "window"]
    events = [r for r in records if r.get("kind") == "event"]
    run_starts = [r for r in records if r.get("kind") == "run_start"]
    run_ends = [r for r in records if r.get("kind") == "run_end"]
    healths = [r for r in records if r.get("kind") == "health"]
    model_reports = [r for r in records if r.get("kind") == "model_report"]
    servings = [r for r in records if r.get("kind") == "serving"]
    routers = [r for r in records if r.get("kind") == "router"]
    fleets = [r for r in records if r.get("kind") == "fleet"]
    traces = [r for r in records if r.get("kind") == "trace"]
    signatures = [r for r in records if r.get("kind") == "program_signature"]

    # tolerate sinks written by a newer schema: count-and-skip kinds this renderer
    # does not know, never crash on them (forward compatibility for mixed fleets)
    known_kinds = {
        "step", "window", "event", "run_start", "run_end", "health", "model_report",
        "serving", "router", "fleet", "trace", "program_signature",
    }
    unknown_kinds: dict[str, int] = {}
    for record in records:
        kind = str(record.get("kind", "?"))
        if kind not in known_kinds:
            unknown_kinds[kind] = unknown_kinds.get(kind, 0) + 1

    lines: list[str] = []

    if run_starts:
        first = run_starts[0]
        lines.append(
            f"run: {first.get('devices', '?')} device(s) [{first.get('device_kind', '?')}], "
            f"peak {first.get('peak_tflops_per_device') or 'n/a'} TFLOPs/device, "
            f"model {first.get('model_tflops_per_step') or 'n/a'} TFLOPs/step"
        )
        if first.get("host") or first.get("config_hash"):
            lines.append(
                f"host {first.get('host', '?')} pid {first.get('pid', '?')}, "
                f"jax {first.get('jax_version', '?')}/{first.get('jaxlib_version', '?')}, "
                f"config {first.get('config_hash') or 'n/a'}"
            )
        kernels = first.get("kernels")
        if kernels:
            # only call out non-default (non-xla) families; all-XLA is the baseline
            pallas = sorted(k for k, v in kernels.items() if v != "xla")
            lines.append(
                "kernels: "
                + (
                    f"pallas [{', '.join(pallas)}], xla elsewhere"
                    if pallas
                    else "xla (all families)"
                )
            )
        lines.append("")

    if run_ends:
        statuses = sorted({str(r.get("status", "unknown")) for r in run_ends})
        last_step = max((r.get("step") or 0) for r in run_ends)
        lines.append(f"run end: status = {', '.join(statuses)} @ step {last_step}")
        lines.append("")

    # ---------------------------------------------------------------- model report
    if model_reports:
        lines.extend(format_model_report(model_reports[0]))
        lines.append("")

    # ------------------------------------------------------- compiled-program signatures
    if signatures:
        # one entry per (source, program) — the run's self-report of what compiled
        # (utils/program_signature.py; gated offline by tools/perf_ledger.py)
        programs: dict[str, dict] = {}
        for record in signatures:
            for prog in record.get("programs") or []:
                programs[f"{record.get('source', '?')}:{prog.get('name', '?')}"] = prog
        temps = [
            temp
            for prog in programs.values()
            if (temp := (prog.get("memory") or {}).get("temp_size_in_bytes")) is not None
        ]
        compiles = {
            name.rsplit(":", 1)[-1]: prog["compiles"]
            for name, prog in sorted(programs.items())
            if prog.get("compiles") is not None
        }
        undonated = sorted(
            name
            for name, prog in programs.items()
            if not (prog.get("donation") or {}).get("donated_inputs")
        )
        parts = [f"programs: {len(programs)} captured"]
        if temps:
            parts.append(f"temp HBM high water {_format_bytes(max(temps))}")
        if compiles:
            parts.append(
                "compiles " + ", ".join(f"{k}={v}" for k, v in compiles.items())
            )
        if undonated:
            parts.append(f"no donation [{', '.join(undonated)}]")
        lines.append(", ".join(parts))
        lines.append("")

    # ---------------------------------------------------------------- step times
    steady = sorted(t["step"] for r in steps if "step" in (t := r.get("t", {})))
    compiles = [t["compile"] for r in steps if "compile" in (t := r.get("t", {}))]
    data_waits = sorted(t["data"] for r in steps if "data" in (t := r.get("t", {})))
    if steady or compiles:
        lines.append("| step time (s) | p50 | p95 | max | n |")
        lines.append("|---|---|---|---|---|")
        if steady:
            lines.append(
                f"| train step (steady) | {percentile(steady, 50):.4g} "
                f"| {percentile(steady, 95):.4g} | {steady[-1]:.4g} | {len(steady)} |"
            )
        if data_waits:
            lines.append(
                f"| dataloader wait | {percentile(data_waits, 50):.4g} "
                f"| {percentile(data_waits, 95):.4g} | {data_waits[-1]:.4g} "
                f"| {len(data_waits)} |"
            )
        if compiles:
            lines.append(
                f"| first-step compile | {max(compiles):.4g} | - | {max(compiles):.4g} "
                f"| {len(compiles)} |"
            )
        lines.append("")

    # ---------------------------------------------------------------- an iteration's split
    # step records written from a train loop carry the loop's spans (t.split, in the order
    # they ran) and the iteration's wall time: the median of each part over the steady
    # steps, and the slowest iteration with its own split — which part stalled
    split_records = [r for r in steps if "split" in r.get("t", {}) and "step" in r["t"]]
    split_steps = [r["t"] for r in split_records]
    if split_steps:
        names = list(dict.fromkeys(name for t in split_steps for name in t["split"]))
        walls = sorted(t["wall"] for t in split_steps)
        slowest = max(split_steps, key=lambda t: t["wall"])
        lines.append(
            f"| iteration split (ms) | median of {len(split_steps)} | slowest iteration "
            f"({1e3 * slowest['wall']:.4g} ms) |"
        )
        lines.append("|---|---|---|")
        for name in names:
            values = sorted(t["split"].get(name, 0.0) for t in split_steps)
            lines.append(
                f"| {name} | {1e3 * percentile(values, 50):.4g} "
                f"| {1e3 * slowest['split'].get(name, 0.0):.4g} |"
            )
        lines.append(
            f"| (whole iteration) | {1e3 * percentile(walls, 50):.4g} | {1e3 * slowest['wall']:.4g} |"
        )
        lines.append("")
        lines.extend(format_slowest_iterations(split_records))

    # ---------------------------------------------------------------- goodput
    if windows:
        totals = {
            k: sum(w["goodput"].get(k, 0.0) for w in windows if w.get("goodput"))
            for k in ("compile", "data", "step", "checkpoint", "eval", "other")
        }
        wall = sum(w.get("window_seconds", 0.0) for w in windows) or 1e-9
        lines.append(f"| goodput bucket | seconds | % of wall ({wall:.4g}s) |")
        lines.append("|---|---|---|")
        for name, seconds in totals.items():
            lines.append(f"| {name} | {seconds:.4g} | {100.0 * seconds / wall:.1f}% |")
        lines.append("")

        mfus = [w["mfu_pct"] for w in windows if w.get("mfu_pct") is not None]
        summary = [f"goodput = {100.0 * totals['step'] / wall:.1f}%"]
        if mfus:
            summary.append(
                f"MFU = {sum(mfus) / len(mfus):.2f}% mean "
                f"({min(mfus):.2f}-{max(mfus):.2f}% over {len(mfus)} windows)"
            )
        lines.append("**" + ", ".join(summary) + "**")
        lines.append("")

    # ---------------------------------------------------------------- serving
    if servings:
        last = servings[-1]  # counters/rates are cumulative, so the last record is total
        counters = last.get("counters") or {}
        parts = [
            f"serving: {counters.get('completed', 0)} completed / "
            f"{counters.get('admitted', 0)} admitted"
        ]
        if last.get("ttft_ms") is not None:
            parts.append(f"ttft {last['ttft_ms']:.0f}ms")
        if last.get("prefill_tok_s") is not None:
            parts.append(f"prefill {last['prefill_tok_s']:.0f} tok/s")
        if last.get("decode_tok_s") is not None:
            parts.append(f"decode {last['decode_tok_s']:.0f} tok/s")
        hit = counters.get("prefix_hit_tokens", 0)
        miss = counters.get("prefix_miss_tokens", 0)
        if hit + miss > 0:
            parts.append(
                f"prefix hit rate {100.0 * hit / (hit + miss):.1f}% "
                f"({hit}/{hit + miss} prompt tokens reused)"
            )
        proposed = counters.get("draft_tokens_proposed", 0)
        if proposed > 0:
            accepted = counters.get("draft_tokens_accepted", 0)
            spec = (
                f"speculation accept rate {100.0 * accepted / proposed:.1f}% "
                f"({accepted}/{proposed} drafts)"
            )
            if last.get("accepted_tokens_per_step") is not None:
                spec += f", {last['accepted_tokens_per_step']:.2f} accepted/step"
            parts.append(spec)
        serving_kernels = last.get("kernels") or {}
        serving_pallas = sorted(k for k, v in serving_kernels.items() if v != "xla")
        if serving_pallas:
            parts.append(f"pallas kernels [{', '.join(serving_pallas)}]")
        if last.get("pages_in_use") is not None:
            page_line = f"pages {last['pages_in_use']}/{last.get('pages_total', '?')}"
            if last.get("page_fragmentation") is not None:
                page_line += f" (frag {100.0 * last['page_fragmentation']:.1f}%)"
            parts.append(page_line)
        if last.get("kv_bytes_per_token") is not None:
            kv_line = f"kv {last['kv_bytes_per_token']:.0f} B/token"
            if last.get("kv_dtype"):
                kv_line += f" ({last['kv_dtype']})"
            parts.append(kv_line)
        replica_ids = sorted(
            {r["replica_id"] for r in servings if r.get("replica_id") is not None}
        )
        if replica_ids:
            parts.append(f"replicas seen {replica_ids}")
        lines.append(", ".join(parts))
        lines.append("")

        # contention line: only when the run actually scheduled under contention
        # (preemptions, sessions, or more than the single default tier)
        tiers = last.get("tiers") or {}
        contended = (
            last.get("preemptions")
            or last.get("session_hits")
            or last.get("sessions_live")
            or len(tiers) > 1
        )
        if contended:
            cparts = [
                f"contention: {last.get('preemptions', 0)} preemption(s) "
                f"({last.get('pages_swapped_out', 0)} pages swapped out / "
                f"{last.get('pages_swapped_in', 0)} in)"
            ]
            if last.get("session_hits") or last.get("sessions_live"):
                cparts.append(
                    f"session hits {last.get('session_hits', 0)} "
                    f"({last.get('sessions_live', 0)} live)"
                )
            for tier, info in sorted(tiers.items(), key=lambda kv: int(kv[0])):
                bits = [f"{info.get('completed', 0)}/{info.get('admitted', 0)} done"]
                if info.get("preempted"):
                    bits.append(f"{info['preempted']} preempted")
                if info.get("ttft_p99_ms") is not None:
                    ttft_bit = f"p99 ttft {info['ttft_p99_ms']:.0f}ms"
                    if info.get("ttft_target_ms") is not None:
                        ttft_bit += f" (target {info['ttft_target_ms']:.0f}ms)"
                    bits.append(ttft_bit)
                cparts.append(f"tier {tier}: " + " ".join(bits))
            lines.append(", ".join(cparts))
            lines.append("")

    # ---------------------------------------------------------------- router
    if routers:
        last = routers[-1]  # routed/rejected/affinity are cumulative
        counters = last.get("counters") or {}
        parts = [
            f"router: {last.get('routed', 0)} routed / {last.get('rejected', 0)} rejected "
            f"over {last.get('replicas', '?')} replica(s)"
        ]
        hits = last.get("prefix_affinity_hits", 0)
        routed = last.get("routed", 0)
        if routed:
            parts.append(
                f"prefix-affinity hits {hits} ({100.0 * hits / routed:.1f}% of routed)"
            )
        per_replica = counters.get("per_replica_routed") or {}
        if per_replica:
            parts.append(
                "per-replica " + ", ".join(f"#{k}:{v}" for k, v in sorted(per_replica.items()))
            )
        if last.get("queue_depths"):
            parts.append(f"queue depths {last['queue_depths']}")
        if last.get("handoff_latency_ms") is not None:
            parts.append(
                f"kv handoff {counters.get('kv_handoffs', '?')} transfers "
                f"(mean {last['handoff_latency_ms']:.1f}ms)"
            )
        lines.append(", ".join(parts))
        # fleet fault tolerance: the record carries health/reroute fields only when
        # health monitoring was on or a recovery action fired (serving/cluster/)
        health = last.get("health")
        if health is not None:
            healthy = sum(1 for s in health.values() if s == "healthy")
            fleet = [
                f"fleet: {healthy}/{len(health)} replicas healthy "
                + "("
                + ", ".join(f"#{k}:{v}" for k, v in sorted(health.items()))
                + ")"
            ]
            crashes = counters.get("replica_crashes", 0)
            if crashes:
                fleet.append(f"{crashes} crashed")
            reroutes = last.get("reroutes", 0)
            if reroutes:
                fleet.append(
                    f"{reroutes} requests rerouted "
                    f"({last.get('reroute_retries', 0)} extra attempts)"
                )
            if counters.get("requests_shed"):
                fleet.append(f"{counters['requests_shed']} shed")
            if counters.get("drains"):
                fleet.append(f"{counters['drains']} drains")
            lines.append(", ".join(fleet))
        lines.append("")

    # ---------------------------------------------------------------- fleet aggregate
    if fleets:
        last = fleets[-1]  # totals are cumulative sums across replicas
        parts = [
            f"fleet aggregate: {last.get('replicas', '?')} replica(s), "
            f"{last.get('completed', 0)}/{last.get('admitted', 0)} done "
            f"({last.get('preempted', 0)} preempted, {last.get('rejected', 0)} rejected)"
        ]
        parts.append(
            f"queue {last.get('queue_depth', 0)}, "
            f"slots {last.get('slots_active', 0)}/{last.get('num_slots', 0)}"
        )
        if last.get("accept_rate") is not None:
            parts.append(f"accept rate {100.0 * last['accept_rate']:.1f}%")
        if last.get("sessions_live"):
            parts.append(f"{last['sessions_live']} live session(s)")
        health = last.get("health") or {}
        if health:
            healthy = sum(1 for s in health.values() if s == "healthy")
            parts.append(f"{healthy}/{len(health)} healthy")
        for tier, info in sorted(
            (last.get("tiers") or {}).items(), key=lambda kv: int(kv[0])
        ):
            bits = [f"{(info or {}).get('completed', 0)}/{(info or {}).get('admitted', 0)} done"]
            if (info or {}).get("ttft_p99_ms") is not None:
                bits.append(f"p99 ttft {info['ttft_p99_ms']:.0f}ms")
            if (info or {}).get("itl_mean_ms") is not None:
                bits.append(f"itl {info['itl_mean_ms']:.1f}ms")
            parts.append(f"tier {tier}: " + " ".join(bits))
        lines.append(", ".join(parts) + f" ({len(fleets)} fleet record(s))")
        lines.append("")

    # ---------------------------------------------------------------- traces
    if traces:
        # per-request distributed tracing (--trace): critical-path TTFT by tier.
        # Import lazily so summarizing an untraced sink stays dependency-free; a sink
        # with trace records but no importable package still summarizes (count only).
        try:
            from dolomite_engine_tpu.utils.tracing import (
                aggregate_critical_paths,
                trace_record_critical_path,
            )
        except ImportError:
            lines.append(f"traces: {len(traces)} request(s) (tracing module unavailable)")
            lines.append("")
        else:
            targets: dict[int, float] = {}
            for record in servings:
                for tier, info in (record.get("tiers") or {}).items():
                    target_ms = (info or {}).get("ttft_target_ms")
                    if target_ms is not None:
                        try:
                            targets[int(tier)] = target_ms / 1e3
                        except (TypeError, ValueError):
                            continue
            paths = [
                p
                for p in (trace_record_critical_path(r) for r in traces)
                if p is not None
            ]
            aggregate = aggregate_critical_paths(paths, targets)
            parts = [f"traces: {len(traces)} request(s)"]
            for tier, entry in aggregate.items():
                p50, p99 = entry["ttft_p50_s"], entry["ttft_p99_s"]
                bits = []
                if p50 is not None:
                    bits.append(f"p50 ttft {p50 * 1e3:.1f}ms / p99 {p99 * 1e3:.1f}ms")
                if entry["top_bucket"] is not None:
                    share = entry["bucket_shares"][entry["top_bucket"]]
                    bits.append(f"top bucket {entry['top_bucket']} {100.0 * share:.0f}%")
                if entry.get("misses"):
                    bits.append(
                        f"{entry['misses']} SLO miss(es), {entry.get('miss_top_bucket')} "
                        "dominated"
                    )
                tier_name = "untiered" if tier is None else f"tier {tier}"
                parts.append(f"{tier_name}: " + ", ".join(bits) if bits else tier_name)
            lines.append(", ".join(parts) + " (tools/trace_analyze.py for the breakdown)")
            lines.append("")

    # ---------------------------------------------------------------- health / anomalies
    if healths:
        last = healths[-1]  # the latest per-group snapshot is what a triage wants first
        stats = last.get("stats") or {}
        metric_names = [m for m in ("grad_norm", "param_norm", "update_ratio") if m in stats]
        group_names = sorted({g for metric in stats.values() for g in metric})
        if metric_names and group_names:
            lines.append(
                f"| health @ step {last.get('step', '?')} | " + " | ".join(metric_names) + " |"
            )
            lines.append("|---|" + "---|" * len(metric_names))
            for group in group_names:
                cells = []
                for metric in metric_names:
                    value = stats[metric].get(group)
                    cells.append(f"{value:.4g}" if isinstance(value, (int, float)) else "-")
                lines.append(f"| {group} | " + " | ".join(cells) + " |")
            lines.append(f"({len(healths)} health record(s))")
            lines.append("")

    # serving SLO alerts (utils/diagnostics.ServingSLOMonitor) get their own line with
    # replica/tier attribution; everything else stays on the training "anomalies:" line
    serving_signals = {
        "ttft_burn_rate", "queue_growth", "accept_rate_collapse", "handoff_latency",
    }
    anomalies = [e for e in events if e.get("event") == "anomaly"]
    alerts = [a for a in anomalies if str(a.get("signal", "?")) in serving_signals]
    anomalies = [a for a in anomalies if a not in alerts]
    if alerts:
        by_signal: dict[str, list] = {}
        for alert in alerts:
            by_signal.setdefault(str(alert.get("signal", "?")), []).append(alert)
        parts = []
        for signal_name in sorted(by_signal):
            group = by_signal[signal_name]
            where = sorted(
                {
                    f"#{a['replica_id']}" + (f"/tier{a['tier']}" if "tier" in a else "")
                    for a in group
                    if a.get("replica_id") is not None
                }
            )
            suffix = f" [{', '.join(where)}]" if where else ""
            parts.append(f"{signal_name} x{len(group)}{suffix}")
        lines.append("alerts: " + ", ".join(parts))
        lines.append("")
    if anomalies:
        by_signal = {}
        for anomaly in anomalies:
            by_signal.setdefault(str(anomaly.get("signal", "?")), []).append(
                anomaly.get("step")
            )
        parts = []
        for signal_name in sorted(by_signal):
            flagged_steps = [s for s in by_signal[signal_name] if s is not None]
            span = (
                f" (steps {min(flagged_steps)}-{max(flagged_steps)})" if flagged_steps else ""
            )
            parts.append(f"{signal_name} x{len(by_signal[signal_name])}{span}")
        lines.append("anomalies: " + ", ".join(parts))
        lines.append("")

    # ---------------------------------------------------------------- counters
    # last-window/run_end counters are cumulative; merge max-per-name across ranks
    counters: dict[str, int] = {}
    for record in windows + run_ends:
        for name, value in (record.get("counters") or {}).items():
            counters[name] = max(counters.get(name, 0), int(value))
    if counters:
        lines.append("| counter | total |")
        lines.append("|---|---|")
        for name in sorted(counters):
            lines.append(f"| {name} | {counters[name]} |")
        lines.append("")

    if events:
        names: dict[str, int] = {}
        for e in events:
            names[e.get("event", "?")] = names.get(e.get("event", "?"), 0) + 1
        lines.append(
            "events: " + ", ".join(f"{k} x{v}" for k, v in sorted(names.items()))
        )
        lines.append("")

    if unknown_kinds:
        skipped = ", ".join(f"{k} x{v}" for k, v in sorted(unknown_kinds.items()))
        lines.append(f"(skipped records of unknown kind: {skipped})")
        lines.append("")

    if not (
        steps
        or windows
        or events
        or run_starts
        or healths
        or model_reports
        or servings
        or routers
        or fleets
        or traces
    ):
        lines.append("(no telemetry records found)")
    return "\n".join(lines).rstrip() + "\n"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "paths", nargs="+", help="sink .jsonl file(s) or run/telemetry directories"
    )
    parsed = parser.parse_args(argv)

    files = find_sink_files(parsed.paths)
    if not files:
        print(f"no .jsonl sinks found under {parsed.paths}", file=sys.stderr)
        return 1
    records, bad_lines = read_records(files)
    print(f"telemetry summary over {len(files)} sink(s), {len(records)} records\n")
    print(summarize(records))
    flight_records = sorted(
        path
        for arg in parsed.paths
        if os.path.isdir(arg)
        for path in glob.glob(
            os.path.join(arg, "**", "flight-record-*.json"), recursive=True
        )
    )
    if flight_records:
        print("flight record(s) found — a run died here:")
        for path in flight_records:
            print(f"  {path}")
    if bad_lines:
        print(f"({bad_lines} malformed line(s) skipped)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
