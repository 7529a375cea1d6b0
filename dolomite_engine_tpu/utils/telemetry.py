"""Always-on telemetry: goodput accounting, rank-tagged JSONL sink, on-demand profiling.

The reference engine's observability stops at rank-0 aim/wandb scalars
(`dolomite_engine/utils/tracking.py`); without a tracker installed a pod run is a black box.
This module is the always-on layer underneath: every host appends line-JSON records to a
local sink (zero optional deps), rank-0 scalars additionally fan out to the existing
:class:`~dolomite_engine_tpu.utils.tracking.ExperimentsTracker`, and the train loops feed a
**goodput breakdown** — first-step compile, dataloader wait, jitted step, checkpoint-blocking,
eval — from which steady-state MFU (vs detected per-device peak FLOPs) and goodput %% are
derived per logging window. With the async input pipeline on (``prefetch_depth > 0``,
data/prefetch.py) the ``data`` bucket measures only *residual* prefetch-queue wait — batch
assembly and H2D transfer run on the prefetch worker, overlapped with the previous step.

Sink schema (one JSON object per line; see docs/OBSERVABILITY.md):

    {"kind": "run_start", "ts", "rank", "devices", "device_kind",
     "peak_tflops_per_device", "model_tflops_per_step", "schema": 1}
    {"kind": "step",   "ts", "rank", "step", "t": {"data", "step" | "compile",
     "wall", "split": {span name: seconds}, "inner": {nested span: seconds},
     "off_loop": {other threads' span: seconds}, "gc": {"seconds", "count"},
     "host": {"nivcsw", "majflt", "cpu"}}}      # wall .. host: inside a train loop only
    {"kind": "window", "ts", "rank", "step", "window_seconds",
     "goodput": {"compile","data","step","checkpoint","eval","other","goodput_pct"},
     "step_time": {"count","mean","min","max"}, "mfu_pct", "tflops_per_group",
     "counters": {...cumulative...}, "gauges": {...device memory, host rss...}}
    {"kind": "event",  "ts", "rank", "event", "step", ...}   # nan_skip, loader_stall, anomaly,
                                                             # compile (seconds, program)
    {"kind": "health", "ts", "rank", "step", "stats"}        # per-group norms (diagnostics.py)
    {"kind": "model_report", ...}                            # one-shot introspection (diagnostics.py)
    {"kind": "serving", "ts", "rank", "step", "queue_depth", "slots_active", "num_slots",
     "ttft_ms", "prefill_tok_s", "decode_tok_s", "counters"}  # serving engine (serving/engine.py)
    {"kind": "trace",  "ts", "rank", "step", "trace_id", "request_id", "spans"}  # per-request
                                             # span tree (utils/tracing.py, --trace only)
    {"kind": "fleet",  "ts", "rank", "step", "replicas", "queue_depth", ..., "tiers",
     "per_replica"}                          # cross-replica aggregate (serving/cluster/metrics.py)
    {"kind": "run_end","ts", "rank", "step", "status", "counters"}

The full kind -> required-field table is :data:`RECORD_SCHEMA`;
`scripts/check_telemetry_schema.py` statically checks every call site against it.

Cross-module counters (`utils/retry.py`, `utils/fault_tolerance.py`, `checkpointing.py`,
`data/dataloader.py`) reach the active instance through :func:`get_telemetry`, a process-wide
registry that degrades to a no-op when no train loop installed telemetry — inference tools
and unit tests pay nothing.

Spans: :meth:`Telemetry.span` is the one primitive that cuts a boundary of the program —
once, on both clocks. It enters a ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation``
for the step's dispatch), reads ``time.perf_counter()`` at both ends, adds the duration to
the current iteration's split (the ``step`` record's ``t.split``; the loop thread's
outermost spans tile the iteration, so the parts sum to ``t.wall``; a span nested in one of
them goes to ``t.inner`` under its own name, one that closed on another thread to
``t.off_loop``) and, where a ``bucket`` is named, to that goodput bucket. A captured trace
and the sink therefore cut at the same places. Span names are lower case with no ``(``,
``:``, ``$`` or space.

What else the host did in an iteration rides the same record, always on: ``t.gc`` (Python's
garbage collections in the process: a ``gc.callbacks`` hook from
:meth:`Telemetry.begin_iterations` to :meth:`Telemetry.close`, each collection also a span
``gc.collect`` in a profile) and ``t.host`` (involuntary context switches, major page
faults and CPU seconds of the process). A slow iteration therefore says what held it, in an
untraced run too (the ``anomaly`` event of signal ``step_time`` carries the same fields and
the span that holds the excess; `tools/telemetry_summary.py` prints the slowest iterations).

Compilations: one ``jax.monitoring`` listener a process counts every XLA backend compilation
(cache loads too) from the start of a train loop (:meth:`Telemetry.begin_iterations`) into
the installed telemetry's ``compiles`` counter and writes a ``compile`` event with its seconds, the program's name and the step it fell in — a run that
recompiles at step 40,000 says so.

On-demand profiling: :class:`OnDemandProfiler` is polled once per step (same pattern as the
fault-tolerance preemption flag); touching the trigger file — or SIGUSR1 — captures an N-step
`jax.profiler` trace mid-run, no restart. A capture starts and stops only after the last
dispatched step has finished on the device (:func:`profiler_call_at_step_boundary`, shared
with the fixed schedule of `train_utils.get_profiler_context`), so it holds whole steps.
"""

from __future__ import annotations

import gc
import hashlib
import json
import logging
import os
import resource
import signal
import socket
import statistics
import threading
import time
from collections import deque
from typing import Any

import jax

from .logger import log_rank_0

SCHEMA_VERSION = 1

# Declared record kinds -> required fields. scripts/check_telemetry_schema.py statically
# validates every sink write in the package against this table (tier-1 test), so a new
# record type cannot ship undeclared/undocumented. `ts` and `rank` are stamped by _emit on
# every record and are not repeated here.
RECORD_SCHEMA: dict[str, tuple[str, ...]] = {
    "run_start": (
        "schema",
        "devices",
        "device_kind",
        "peak_tflops_per_device",
        "model_tflops_per_step",
        "host",
        "pid",
        "jax_version",
        "jaxlib_version",
        "config_hash",
        # family -> backend map of what each op family lowers through
        # (ops/pallas/config.active_kernel_backends; "pallas" only when the build
        # probe passes, so the record reflects what actually ran)
        "kernels",
    ),
    # `t` = {"data": residual queue wait, "step" | "compile": dispatch-to-sync wall time}
    # and, when written from a train loop (Telemetry.begin_iterations), "wall" (the whole
    # iteration, boundary to boundary) and "split" ({span name: seconds}: the loop thread's
    # outermost spans, which tile the iteration — the record is written once the iteration
    # has ended, and its own write is charged to the next one as `loop.record`), "inner"
    # ({span: seconds} of the spans nested in those, absent when none closed: `sync.step`,
    # `sync.read`, `log.read`, `log.track`, `log.progress`, ...), "off_loop" ({span: seconds}
    # of spans that closed on other threads during the iteration, absent when none did),
    # "gc" ({"seconds", "count"} of Python's garbage collections in the process, absent when
    # none ran) and "host" ({"nivcsw", "majflt", "cpu"}: the iteration's involuntary context
    # switches, major page faults and CPU seconds of the process)
    "step": ("step", "t"),
    "window": (
        "step",
        "window_seconds",
        "goodput",
        "step_time",
        "mfu_pct",
        "tflops_per_group",
        "counters",
        "gauges",
    ),
    "event": ("event",),
    "run_end": ("step", "status", "counters"),
    # training health subsystem (utils/diagnostics.py)
    "health": ("step", "stats"),
    "model_report": ("param_groups", "totals", "hbm"),
    # continuous-batching serving engine (serving/engine.py): queue/slot/page state is
    # instantaneous, rates and counters are cumulative over the engine's lifetime
    # (pages_* / page_fragmentation are null when the dense slot pool is in use;
    # replica_id identifies the engine within a router fleet, null standalone)
    "serving": (
        "replica_id",
        "queue_depth",
        "slots_active",
        "num_slots",
        "pages_in_use",
        "pages_total",
        "page_fragmentation",
        # paged KV storage format ("bf16"/"int8"/"fp8", null = model/cache dtype) and
        # resident K/V bytes per cached token incl. quantized scale-pool overhead
        # (serving/kv_cache.kv_bytes_per_token) — how the HBM sizing formula and the
        # --kv-dtype bench A/B attribute capacity
        "kv_dtype",
        "kv_bytes_per_token",
        "ttft_ms",
        "prefill_tok_s",
        "decode_tok_s",
        # speculative decoding (null when speculation is off): cumulative fraction of
        # proposed draft tokens the target accepted, and accepted drafts per verify step
        # (emitted tokens per step is this + 1)
        "accept_rate",
        "accepted_tokens_per_step",
        # contention-aware scheduling (serving/engine.py, docs/SERVING.md "Scheduling
        # under contention"): cumulative slot evictions, pages moved through the host
        # swap pool, admissions that reused a live session's pinned prefix, live pinned
        # sessions, and a per-tier breakdown {tier: {queue_depth, admitted, completed,
        # preempted, ttft_p99_ms, ttft_target_ms, itl_mean_ms, itl_target_ms}}
        "preemptions",
        "pages_swapped_out",
        "pages_swapped_in",
        "session_hits",
        "sessions_live",
        "tiers",
        # active kernel backend per op family (ops/pallas/config.py) — which lowering
        # produced these serving numbers, for kernel A/B attribution
        "kernels",
        "counters",
    ),
    # distributed serving router (serving/cluster/router.py): per-replica queue/slot
    # state is instantaneous (list index == fleet position), routed/rejected/affinity
    # counters are cumulative; handoff_latency_ms is the mean KV-handoff wall time over
    # disaggregated replicas (null when no replica disaggregates)
    "router": (
        "replicas",
        "queue_depths",
        "slots_active",
        "routed",
        "rejected",
        "prefix_affinity_hits",
        "handoff_latency_ms",
        "counters",
        # fleet fault tolerance (serving/cluster/health.py): present only when health
        # monitoring is on or a recovery action fired — the off path's record is
        # byte-identical to the health-unaware router. `health` maps replica_id ->
        # healthy|suspect|dead|parked; `reroutes`/`reroute_retries` count migrated
        # in-flight requests and extra placement attempts beyond each one's first.
        "health",
        "reroutes",
        "reroute_retries",
    ),
    # per-request distributed tracing (utils/tracing.py): one record per finished
    # request when tracing is enabled (`--trace` / trace_requests), carrying the whole
    # span tree — [{id, parent, name, t0, t1, attrs}] on the owning scheduler's clock.
    # Span names come from the KNOWN_SPANS vocabulary (dolo-lint `tracing` checker);
    # tools/trace_export.py renders Perfetto timelines and tools/trace_analyze.py the
    # critical-path TTFT attribution from these records.
    "trace": ("trace_id", "request_id", "spans"),
    # cross-replica fleet aggregate (serving/cluster/metrics.py ClusterMetricsAggregator):
    # per-replica EngineStats merged into one fleet-level view. Totals are sums over live
    # replicas; `tiers` merges every replica's per-tier series (ttft_p99_ms is computed over
    # the pooled samples, not a mean of means); `per_replica` maps replica_id -> its slice
    # (queue_depth, slots_active, num_slots, pages_in_use, occupancy, admitted, completed,
    # preemptions, sessions_live, accept_rate, health). Emitted only when an aggregator is
    # attached (--metrics-port / Router(metrics=...)); the off path never writes this kind.
    "fleet": (
        "replicas",
        "queue_depth",
        "slots_active",
        "num_slots",
        "admitted",
        "completed",
        "preempted",
        "rejected",
        "accept_rate",
        "sessions_live",
        "health",
        "tiers",
        "per_replica",
    ),
    # compiled-program perf signatures (utils/program_signature.py): the run self-reports
    # what XLA built for its hot jitted programs — cost_analysis flops/bytes, donation
    # count, HLO features, and (when captured with compile=True) the memory_analysis
    # buffer breakdown. `source` says which subsystem captured ("pretrain",
    # "serving_engine"); `programs` is a list of ProgramSignature.to_json() dicts.
    # tools/perf_ledger.py gates the same facts against PERF_LEDGER.json offline;
    # tools/telemetry_summary.py renders the "programs:" line.
    "program_signature": ("source", "platform", "programs"),
}

# every literal counter name used through the registry; `count(..., event=True)` names must
# additionally appear in KNOWN_EVENTS (they write an event record under the same name)
KNOWN_COUNTERS: tuple[str, ...] = (
    "nan_skips",
    "io_retries",
    "io_failures",
    "loader_stalls",
    "preemptions",
    "checkpoints_saved",
    "checkpoints_pruned",
    "loader_batches",
    "profiles_captured",
    # XLA backend compilations (persistent-cache loads included) since the train loop began
    # (begin_iterations); each also writes a `compile` event naming the program and the step
    "compiles",
    # async input pipeline (data/prefetch.py): consumer found the prefetch queue empty at
    # a steady-state step — the background worker is not keeping up with the loop
    "prefetch_stalls",
    # serving engine (serving/engine.py)
    "serving_requests_admitted",
    "serving_requests_completed",
    "serving_requests_rejected",
    "serving_requests_cancelled",
    "serving_prefill_tokens",
    "serving_decode_tokens",
    # paged-pool prefix caching (serving/prefix_cache.py): prompt tokens whose K/V were
    # already resident (skipped prefill) vs tokens actually computed — hit rate is
    # hit / (hit + miss), rendered by tools/telemetry_summary.py
    "serving_prefix_hit_tokens",
    "serving_prefix_miss_tokens",
    # speculative decoding (serving/engine.py): draft tokens proposed by the configured
    # drafter (n-gram lookup or draft model) vs accepted by the jitted verify step —
    # accept rate is accepted / proposed, rendered by tools/telemetry_summary.py
    "serving_draft_tokens_proposed",
    "serving_draft_tokens_accepted",
    # contention-aware scheduling (serving/engine.py): slots evicted for a higher tier
    # or for physical pages (swap or drop-and-recompute), KV pages moved out to / back
    # from the host swap pool, and admissions that reused a live session's pinned prefix
    "serving_preemptions",
    "serving_pages_swapped_out",
    "serving_pages_swapped_in",
    "serving_session_hits",
    # distributed serving router (serving/cluster/router.py): requests placed on a
    # replica / shed at the fleet-wide admission bound / routed by prefix affinity
    "router_requests_routed",
    "router_requests_rejected",
    "router_prefix_affinity_hits",
    # fleet fault tolerance (serving/cluster/router.py + health.py): replicas declared
    # dead (crash/wedge/thread death), in-flight requests migrated to a survivor,
    # requests cancelled under capacity loss (lowest tier first), and completed
    # drain_replica operations
    "router_replica_crashes",
    "router_requests_rerouted",
    "router_requests_shed",
    "router_drains",
    # prefill/decode disaggregation (serving/cluster/disagg.py): KV page transfers from
    # a prefill worker's pool into a decode worker's pool
    "cluster_kv_handoffs",
)

KNOWN_EVENTS: tuple[str, ...] = (
    "nan_skips",
    "io_failures",
    "loader_stalls",
    "preemptions",
    "profile_start",
    "profiles_captured",
    "anomaly",
    # one XLA backend compilation: seconds, program (the jitted function's name), step
    "compile",
    # how a call of the chunked loss engaged where the step was traced. The summed rule
    # (ops/loss.plan_loss_blocks): logits_products 1 — the gradients are formed in the
    # differentiated forward —, token_blocks, kept_logits_bytes a device (one block's logits),
    # table_carry_bytes (the float32 gradient an outer loop carries; 0 at one block). The
    # per-token rule (ops/loss.plan_loss_backward): logits_products 2 — its backward forms
    # the logits again —, token_blocks x vocab_tiles, tile_rows, hidden_carry_bytes,
    # table_carry_bytes. Both: vocab_shards, tokens_per_device, accumulator_bytes_moved
    "loss_tiling",
    # how the remat policy engaged where the model was traced (models/gpt_dolomite.remat_plan,
    # said by the dense stack, by the looped one and — for nemotron_h, joyai_llm_flash, lfm2_moe
    # and afmoe alike — by the one stack of models/unrolled_stack.py):
    # policy, checkpoint_every, the checkpoint_name tags it keeps, blocks and how many sit
    # under jax.checkpoint, how many of those ran attention through the Pallas kernel, how
    # many of them keep the kernel's output and log-sum-exp (the others run the forward
    # kernel again in the backward pass), and those bytes a block and batch row
    "remat_plan",
    # a looped model's loop where the model was traced (models/ouro.loop_plan): passes, blocks,
    # the block applications of a step and how many replay, the head's readings, and the bytes
    # of the block inputs and pass outputs kept between forward and backward (rows,
    # tokens_per_row: of what batch)
    "loop_plan",
    # what the splash kernel's launches run where attention was traced
    # (ops/attention._splash_attention_local): block_q, block_kv, the rows of a call, a
    # launch's grid (heads, query blocks, key slots), launches a call, and whether the block
    # tables come from the rows' segment ids ("segment_ids": blocks no document spans are
    # skipped) or are jax's static causal ones ("static", with why_static); a layer under a
    # window says window and window_key_blocks (the key blocks a query block can reach) and,
    # where that is fewer than a row's and the tables are the ids', key_slots: the tables are
    # that narrow, and it is the grid's last (a record without it: a row's key blocks)
    "splash_block_plan",
    # what a model cut to one chip's share holds of what was published (models/config.py:
    # every record ends in ExpertShareConfig.share_record; before it
    # NemotronHConfig.layout_record: pattern, experts held of published, vocabulary rows
    # held, the deployment's numbers; JoyAIFlashConfig.layout_record: blocks by kind in the
    # pattern's place; Lfm2MoeConfig.layout_record: layer_types and the blocks by operator
    # and by feed-forward; AfmoeConfig.layout_record: layer_types, the blocks by attention's
    # kind and by feed-forward, sliding_window), once a run
    "model_layout",
    # the one rope+QKV seam where a call norms q and k per head (ops/rope.split_qkv_apply_rope,
    # a config with qk_norm) and the fused rope+QKV kernel, promoted there, steps aside: form
    # ("xla"), why_xla, heads (query heads, key/value heads, head width), once a traced model
    "rope_qkv_plan",
    # which lowering of the Mamba-2 chunked scan each M layer of a traced model took
    # (models/nemotron_h.scan_plan, from ops/mamba2.scan_lowering, written where the tower
    # extends the shared stack's watch_blocks): the layers on the Pallas
    # kernel and on the jnp form (and why: backend, mesh or shape), the chunk, the kernel's
    # launches a layer and pass, and the bytes a layer its backward rule keeps
    "mamba2_scan_plan",
    # what the row movements of a traced model's layers of experts planned from their shapes
    # (ops/moe.experts_held_ragged, written by models/shared_expert_moe.say_dispatch_plan
    # round the blocks of models/unrolled_stack.UnrolledStack):
    # layers, the buffers' capacity, block_rows (the sorted slots a loop step of the gather,
    # the weighted scatter-add and their transposes takes), blocks_per_capacity, form; a layer
    # and step runs ceil(routed_slots / block_rows) of those blocks (routed_slots: the
    # step_counters event); activation_block_rows and activation_form (pallas or xla_loop: the
    # walk of the activation between the grouped products, ceil(routed_slots /
    # activation_block_rows) blocks a pass), group_sizes (sorted_keys: read off the sort)
    "moe_dispatch_plan",
    # what the step's forward pass counted, returned by the train step beside the loss
    # (train_utils.make_train_step has_aux) and read where the loss is read; the four unrolled
    # families' come from one place (models/unrolled_stack.UnrolledStackForCausalLM.step_counters
    # over the blocks' counters the stack collected): for nemotron_h
    # one entry a layer of experts — routed_slots (token-slots of held experts: the rows
    # the grouped products multiply), absent_slots, fullest_expert_rows, held_expert_rows;
    # for joyai_llm_flash the same (its multi-token-prediction module's layer last) and the
    # loss's two parts main_loss and mtp_loss with mtp_targets, the positions the second had;
    # for lfm2_moe the four counters of its layers of experts; for afmoe the same, and its splash
    # counters by the layer's kind (splash_blocks_visited_window, splash_blocks_visited_full,
    # splash_blocks_causal: ops/attention.splash_block_counters_by_kind);
    # for every family whose attention runs the splash kernel splash_blocks_visited and
    # splash_blocks_causal (ops/attention.splash_block_counters: the block pairs one
    # attention layer's tables ran over the step's rows, and those under the diagonal)
    "step_counters",
    # serving-fleet fault tolerance (serving/cluster/health.py + router.py): one event
    # per downward health edge, per completed drain/rejoin, and when a threaded
    # Router.wait timed out with work still pending (fields name who/why)
    "replica_suspect",
    "replica_dead",
    "replica_drained",
    "replica_rejoined",
    "router_wait_incomplete",
)

# every literal gauge name set through the registry (dynamic names — the per-device
# memory/host-RSS fan-out in collect_memory_gauges — are exempt, same rule as counters);
# scripts/check_telemetry_schema.py validates .gauge() call sites against this table
KNOWN_GAUGES: tuple[str, ...] = (
    # async input pipeline (data/prefetch.py): queue occupancy after each consumed batch
    "prefetch/queue_depth",
    # serving engine (serving/engine.py)
    "serving/queue_depth",
    "serving/slot_occupancy",
    # paged KV pool (serving/kv_cache.py): physical pages referenced by slots/prefix
    # index, and the fraction of allocated page capacity not holding valid tokens
    "serving/pages_in_use",
    "serving/page_fragmentation",
    # resident K/V bytes per cached token (all layers; quantized pools include their
    # per-page scale rows amortized over the page) — halves under kv_dtype=bf16 vs
    # fp32 and halves again under int8/fp8
    "serving/kv_bytes_per_token",
    # speculative decoding (serving/engine.py): cumulative draft acceptance rate and
    # accepted draft tokens per verify step (only written when speculation is enabled)
    "serving/accept_rate",
    "serving/accepted_tokens_per_step",
    # distributed serving (serving/cluster/): fleet-wide waiting requests across all
    # replicas, and the latest prefill->decode KV handoff wall time
    "router/queue_depth",
    "cluster/handoff_latency_ms",
    # fleet fault tolerance (serving/cluster/router.py): replicas whose health state
    # is `healthy` (suspect/dead/parked excluded); only written when health
    # monitoring is on
    "router/replicas_healthy",
)

# goodput buckets, in reporting order; "other" is the window remainder (python overhead,
# logging, host syncs) and is derived, never accumulated directly
GOODPUT_BUCKETS = ("compile", "data", "step", "checkpoint", "eval")

# pre-seeded at 0 in every Telemetry so window records always carry the full
# fault-tolerance set — a reader can tell "no NaN skips" from "counter not wired"
CANONICAL_COUNTERS = (
    "nan_skips",
    "io_retries",
    "io_failures",
    "loader_stalls",
    "preemptions",
    "checkpoints_saved",
    "checkpoints_pruned",
)

# bf16 peak TFLOPs per JAX device (v2/v3 devices are single TensorCores, half a chip;
# v4 onward one device == one chip). First substring match on device_kind wins, so more
# specific entries ("v5 lite") come before their prefixes ("v5").
_PEAK_TFLOPS_BY_KIND: tuple[tuple[str, float], ...] = (
    ("v6e", 918.0),
    ("v6 lite", 918.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5p", 459.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 61.5),
    ("v2", 22.5),
)


def detect_peak_tflops_per_device(device=None) -> float | None:
    """Per-device peak bf16 TFLOPs from `device_kind`, for MFU. None off-TPU (CPU) — MFU
    is then omitted rather than fabricated. A TPU this table does not know is an error,
    not a default: add its row (with the source of the number) instead."""
    if device is None:
        device = jax.local_devices()[0]
    kind = device.device_kind.lower()
    for pattern, peak in _PEAK_TFLOPS_BY_KIND:
        if pattern in kind:
            return peak
    if "tpu" in kind or getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"no peak TFLOPs known for TPU device_kind '{device.device_kind}' "
            "(utils/telemetry._PEAK_TFLOPS_BY_KIND)"
        )
    return None


def collect_memory_gauges() -> dict[str, int]:
    """Device HBM gauges from `memory_stats()` (None on CPU backends) + host peak RSS, so
    the window records show memory even where the device runtime reports none."""
    gauges: dict[str, int] = {}
    for i, device in enumerate(jax.local_devices()):
        # supported on TPU (an error there is a real one and propagates); the CPU
        # backend returns None
        stats = device.memory_stats()
        if not stats:
            continue
        # live arrays are bytes_in_use; a loaded program's temporaries are bytes_reserved
        for key in ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"):
            if key in stats:
                gauges[f"device{i}/{key}"] = int(stats[key])
    # ru_maxrss is KiB on Linux
    gauges["host/peak_rss_bytes"] = (
        int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    )
    return gauges


def nearest_rank(ordered, q: float):
    """Nearest-rank quantile over an already-sorted sequence (the serving engine's p99
    convention: rank = ceil(q * n), clamped into range). None on empty input."""
    n = len(ordered)
    if n == 0:
        return None
    rank = min(n - 1, max(0, int(-(-q * n // 1)) - 1))
    return ordered[rank]


class QuantileSketch:
    """Bounded nearest-rank quantile sketch: fixed-size uniform reservoir + exact running
    count/sum.

    Replaces the unbounded per-metric sample lists (``EngineStats.ttft_s`` et al.) so a
    long-running serve holds at most ``capacity`` floats per series while quantile queries
    stay nearest-rank over a uniform subsample. Below capacity the reservoir *is* the full
    stream in insertion order, so ``mean()``/``quantile()`` are bit-identical to the exact
    list-based computation — the off path (short runs, every existing test) cannot observe
    the bound. Replacement uses a deterministic 64-bit LCG seeded per sketch, so results are
    reproducible for a given insertion order without touching any global RNG.

    Not thread-safe on its own; :meth:`Telemetry.observe` wraps it in the registry lock.
    """

    __slots__ = ("capacity", "values", "count", "total", "_rng")

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError(f"QuantileSketch capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.values: list[float] = []
        self.count = 0  # samples offered over the stream's lifetime
        self.total = 0.0  # exact running sum (mean never degrades to the subsample's)
        self._rng = 0x9E3779B97F4A7C15

    def append(self, value: float) -> None:
        """Offer one sample (named ``append`` so it drops into list call sites)."""
        value = float(value)
        self.count += 1
        self.total += value
        if len(self.values) < self.capacity:
            self.values.append(value)
            return
        # algorithm R: replace a random retained sample with probability capacity/count
        self._rng = (self._rng * 6364136223846793005 + 1442695040888963407) % (1 << 64)
        slot = self._rng % self.count
        if slot < self.capacity:
            self.values[slot] = value

    def mean(self) -> float | None:
        return self.total / self.count if self.count else None

    def quantile(self, q: float) -> float | None:
        return nearest_rank(sorted(self.values), q)

    def snapshot(self) -> dict[str, float | int | None]:
        ordered = sorted(self.values)
        return {
            "count": self.count,
            "mean": self.mean(),
            "p50": nearest_rank(ordered, 0.50),
            "p90": nearest_rank(ordered, 0.90),
            "p99": nearest_rank(ordered, 0.99),
        }

    def __len__(self) -> int:
        return len(self.values)

    def __iter__(self):
        return iter(self.values)

    def __bool__(self) -> bool:
        return bool(self.values)


def profiler_call_at_step_boundary(call, last_outputs, what: str) -> bool:
    """Start or stop a profiler capture (`call`) once the last dispatched step has finished
    on the device, so that a capture holds whole steps: dispatch is asynchronous, and a
    trace stopped right after it ends while the step's device work is still queued.
    `last_outputs` is that step's outputs (None: nothing dispatched yet). Shared by both
    capture paths (:class:`OnDemandProfiler`, `train_utils.get_profiler_context`); never
    raises into training."""
    try:
        if last_outputs is not None:
            jax.block_until_ready(last_outputs)
        call()
    except Exception as error:  # a failed capture must never kill training
        log_rank_0(logging.WARNING, f"profiler capture failed to {what}: {error!r}")
        return False
    return True


class OnDemandProfiler:
    """Capture an N-step `jax.profiler` trace mid-run, without restarting.

    Armed by either of two triggers, polled once per step by the train loops (the same
    pattern as the fault-tolerance preemption flag):

    - **touch file**: `touch <trigger_path>` — the poll consumes (deletes) it and starts a
      trace; works over any shared filesystem and needs no PID.
    - **SIGUSR1** (when `use_signal`): `kill -USR1 <pid>`.

    The trace covers the next `num_steps` train steps and lands under `output_path`
    (one subdir per capture, named for its first traced step, so repeated triggers never
    clobber each other).
    """

    def __init__(
        self,
        trigger_path: str,
        output_path: str,
        num_steps: int = 3,
        use_signal: bool = True,
    ) -> None:
        self.trigger_path = trigger_path
        self.output_path = output_path
        self.num_steps = max(int(num_steps), 1)
        self._signal_flag = threading.Event()
        self._active_since: int | None = None
        self._last_outputs = None  # of the newest polled step, while a capture is active
        self._captures = 0
        if use_signal:
            self._install_signal_handler()

    def _install_signal_handler(self) -> None:
        if threading.current_thread() is not threading.main_thread():
            log_rank_0(
                logging.WARNING,
                "SIGUSR1 profile trigger not installed: signal handlers require the main "
                "thread; the touch-file trigger still works",
            )
            return
        signal.signal(signal.SIGUSR1, lambda signum, frame: self._signal_flag.set())

    def _consume_trigger(self) -> bool:
        if self._signal_flag.is_set():
            self._signal_flag.clear()
            return True
        if os.path.exists(self.trigger_path):
            try:
                os.remove(self.trigger_path)
            except OSError:
                pass  # another host on the same mount consumed it first — still triggered
            return True
        return False

    @property
    def active(self) -> bool:
        return self._active_since is not None

    def poll(self, step: int, telemetry: "Telemetry | None" = None, last_outputs=None) -> None:
        """Once per train step, after the step was dispatched: start a capture if
        triggered, stop one that has covered `num_steps` steps — either only after
        `last_outputs` (the step's outputs) are ready, so the capture holds whole steps."""
        if self._active_since is not None:
            self._last_outputs = last_outputs
            if step - self._active_since >= self.num_steps:
                self._stop(step, telemetry)
            return
        if self._consume_trigger():
            self._start(step, telemetry, last_outputs)

    def _start(self, step: int, telemetry: "Telemetry | None", last_outputs=None) -> None:
        trace_dir = os.path.join(self.output_path, f"step{step + 1}")

        def start():
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)

        if not profiler_call_at_step_boundary(start, last_outputs, "start"):
            return
        self._active_since = step
        log_rank_0(
            logging.INFO,
            f"on-demand profile: tracing {self.num_steps} step(s) from step {step + 1} "
            f"into {trace_dir}",
        )
        if telemetry is not None:
            telemetry.event("profile_start", step=step, trace_dir=trace_dir)

    def _stop(self, step: int, telemetry: "Telemetry | None") -> None:
        profiler_call_at_step_boundary(jax.profiler.stop_trace, self._last_outputs, "stop")
        self._active_since = None
        self._last_outputs = None
        self._captures += 1
        log_rank_0(logging.INFO, f"on-demand profile captured through step {step}")
        if telemetry is not None:
            telemetry.count("profiles_captured", event=True, step=step)

    def close(self) -> None:
        """End-of-run cleanup: commit a capture the run ended inside of."""
        if self._active_since is not None:
            self._stop(self._active_since + self.num_steps, None)


def _host_readings() -> tuple[int, int, float]:
    """Involuntary context switches and major page faults of the process so far, and its
    CPU seconds: was it descheduled, paging, or busy."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return usage.ru_nivcsw, usage.ru_majflt, time.process_time()


def _annotation(name: str, step: int | None):
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


class _Span:
    """The context manager behind :meth:`Telemetry.span`. The host clock is read outside
    the profiler annotation, so the annotation's own cost lies inside the span and the
    loop's spans leave no hole between them but their own call overhead."""

    __slots__ = ("telemetry", "name", "bucket", "step", "on_loop", "annotation", "start")

    def __init__(self, telemetry: "Telemetry", name: str, bucket: str | None, step: int | None):
        self.telemetry, self.name, self.bucket, self.step = telemetry, name, bucket, step

    def __enter__(self) -> "_Span":
        self.start = time.perf_counter()
        telemetry = self.telemetry
        self.on_loop = threading.get_ident() == telemetry._loop_thread
        if self.on_loop:
            telemetry._span_depth += 1
        if self.step is not None:
            telemetry._step_in_flight = self.step
        self.annotation = _annotation(self.name, self.step)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc_info) -> None:
        self.annotation.__exit__(*exc_info)
        elapsed = time.perf_counter() - self.start
        telemetry = self.telemetry
        if self.on_loop:
            # an outermost span is a part of the iteration's tiling; a nested one is a part
            # of its parent, kept under its own name
            telemetry._span_depth -= 1
            kept = telemetry._split if telemetry._span_depth == 0 else telemetry._inner
            kept[self.name] = kept.get(self.name, 0.0) + elapsed
        off_loop = not self.on_loop and telemetry._loop_thread is not None
        if off_loop or self.bucket is not None:
            with telemetry._lock:
                if off_loop:  # another thread's span, while a train loop runs
                    telemetry._off_loop[self.name] = telemetry._off_loop.get(self.name, 0.0) + elapsed
                if self.bucket is not None:
                    telemetry._buckets[self.bucket] = telemetry._buckets.get(self.bucket, 0.0) + elapsed


def span_holding_the_excess(split: dict, inner: dict, history) -> str | None:
    """Which span made an iteration slow: the one whose seconds exceed by most the median
    of that span over `history` (the flattened ``{span: seconds}`` of earlier iterations; a
    span is compared with the iterations that ran it). A nested span is named instead of
    its parent where it holds at least half of that excess. `tools/telemetry_summary.py`
    applies the same rule to a sink's ``step`` records (it imports nothing of the package;
    `tests/test_telemetry.py` holds the two to one answer)."""

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


class Telemetry:
    """Process-local metrics registry -> JSONL sink + rank-0 tracker fanout.

    Counters are cumulative over the run (thread-safe — the stall watchdog increments from
    its worker thread); gauges are last-value-wins; the goodput buckets accumulate seconds
    within the current logging window and reset at :meth:`emit_window`.

    The first :meth:`record_step` of a run attributes the whole step duration to the
    ``compile`` bucket (XLA traces+compiles inside the first call; the one real step
    execution inside it is noise at compile timescales) and is excluded from steady-state
    step-time stats and MFU.
    """

    def __init__(
        self,
        sink_path: str | None = None,
        experiments_tracker=None,
        model_tflops_per_step: float | None = None,
        peak_tflops_per_device: float | None = None,
        devices_per_group: int = 1,
        profiler: OnDemandProfiler | None = None,
        rank: int | None = None,
        config_hash: str | None = None,
    ) -> None:
        self.rank = jax.process_index() if rank is None else rank
        self.experiments_tracker = experiments_tracker
        self.model_tflops_per_step = model_tflops_per_step
        self.peak_tflops_per_device = peak_tflops_per_device
        self.devices_per_group = max(int(devices_per_group), 1)
        self.profiler = profiler
        self.sink_path = sink_path

        self._lock = threading.Lock()
        self.counters: dict[str, int] = {name: 0 for name in CANONICAL_COUNTERS}
        self.gauges: dict[str, Any] = {}
        self._sketches: dict[str, QuantileSketch] = {}
        self._buckets: dict[str, float] = {k: 0.0 for k in GOODPUT_BUCKETS}
        self._step_times: list[float] = []
        self._window_start = time.perf_counter()
        self._seen_first_step = False
        self._last_step = 0
        # the current iteration's split (begin_iterations .. record_step): only the loop's
        # thread writes it, and only its outermost spans — those tile the iteration
        self._loop_thread: int | None = None
        self._iteration_start = 0.0
        self._span_depth = 0
        self._split: dict[str, float] = {}
        self._inner: dict[str, float] = {}  # the loop thread's nested spans, by name
        self._off_loop: dict[str, float] = {}  # spans other threads closed (under the lock)
        # garbage collections of the process since begin_iterations (seconds, count): only
        # the gc hook writes the totals, only the loop's thread what it has read of them
        self._gc_total = (0.0, 0)
        self._gc_read = (0.0, 0)
        self._gc_open: tuple | None = None  # (annotation, perf_counter) of a running one
        self._host_read = (0, 0, 0.0)  # ru_nivcsw, ru_majflt, process_time at the boundary
        # the flattened spans of the last iterations: what a slow one is compared with
        self._span_history: deque[dict[str, float]] = deque(maxlen=50)
        self._step_in_flight = 0  # the newest dispatched step: where a compile event fell
        self._events_once: set = set()  # what `event_once` has written

        self._file = None
        if sink_path is not None:
            sink_dir = os.path.dirname(sink_path)
            if sink_dir:
                os.makedirs(sink_dir, exist_ok=True)
            self._file = open(sink_path, "a")

        try:
            import jaxlib

            jaxlib_version = jaxlib.__version__
        except Exception:
            jaxlib_version = None
        device_kinds = sorted({d.device_kind for d in jax.local_devices()})
        from ..ops.pallas import active_kernel_backends

        # host/pid/versions/config hash make runs attributable post-hoc: which machine,
        # which software, which exact resolved config produced this sink
        self._emit(
            {
                "kind": "run_start",
                "schema": SCHEMA_VERSION,
                "devices": jax.device_count(),
                "device_kind": ", ".join(device_kinds),
                "peak_tflops_per_device": peak_tflops_per_device,
                "model_tflops_per_step": model_tflops_per_step,
                "host": socket.gethostname(),
                "pid": os.getpid(),
                "jax_version": jax.__version__,
                "jaxlib_version": jaxlib_version,
                "config_hash": config_hash,
                "kernels": active_kernel_backends(),
            }
        )

    # ------------------------------------------------------------------ sink

    def _emit(self, record: dict) -> None:
        """One record = one line, flushed immediately: a SIGKILL mid-run loses at most the
        line being written, and readers never see interleaved halves (writes under a lock)."""
        if self._file is None:
            return
        record = {"ts": round(time.time(), 3), "rank": self.rank, **record}
        line = json.dumps(record, default=str)
        with self._lock:
            if self._file is not None and not self._file.closed:
                self._file.write(line + "\n")
                self._file.flush()

    # ------------------------------------------------------------------ registry

    def count(
        self, name: str, value: int = 1, event: bool = False, step: int | None = None
    ) -> None:
        """Increment a cumulative counter. `event=True` additionally writes an immediate
        event record — for increments whose process may die before the next window (loader
        stall, preemption)."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + value
        if event:
            self.event(name, step=step, total=self.counters[name])

    def gauge(self, name: str, value) -> None:
        with self._lock:
            self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        """Offer one latency/duration sample to the named in-memory quantile sketch
        (TTFT/ITL/step-time). Pure registry state: nothing is written to the sink, so the
        serving engine feeds these unconditionally without touching record byte-identity.
        Non-finite samples are dropped (a NaN would poison the running sum)."""
        value = float(value)
        if value != value or value in (float("inf"), float("-inf")):
            return
        with self._lock:
            sketch = self._sketches.get(name)
            if sketch is None:
                sketch = self._sketches[name] = QuantileSketch()
            sketch.append(value)

    # ---------------------------------------------------------------- snapshots
    # The live observability plane (serving/obs_server.py) scrapes these instead of
    # tailing the JSONL sink: point-in-time copies taken under the registry lock, safe
    # to read from the HTTP thread while engine/router threads keep writing.

    def counters_snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self.counters)

    def gauges_snapshot(self) -> dict[str, Any]:
        with self._lock:
            return dict(self.gauges)

    def quantiles_snapshot(self) -> dict[str, dict]:
        with self._lock:
            return {name: sketch.snapshot() for name, sketch in self._sketches.items()}

    def snapshot(self) -> dict[str, dict]:
        """Counters + gauges + quantile summaries in one locked pass."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "quantiles": {n: s.snapshot() for n, s in self._sketches.items()},
            }

    def event(self, name: str, step: int | None = None, **fields) -> None:
        record = {"kind": "event", "event": name}
        if step is not None:
            record["step"] = step
        record.update(fields)
        self._emit(record)

    def event_once(self, name: str, **fields) -> None:
        """:meth:`event`, unless this instance already wrote the same one: for facts of a
        trace (a program may be traced more than once: shape evaluation, remat, re-jit)."""
        key = (name, tuple(sorted(fields.items())))
        with self._lock:
            if key in self._events_once:
                return
            self._events_once.add(key)
        self.event(name, **fields)

    def emit_record(self, kind: str, step: int | None = None, **fields) -> None:
        """Write a record of an additional declared kind (``health``, ``model_report`` —
        utils/diagnostics.py). The kind must be declared in :data:`RECORD_SCHEMA`;
        scripts/check_telemetry_schema.py enforces that statically."""
        record: dict[str, Any] = {"kind": kind}
        if step is not None:
            record["step"] = step
        record.update(fields)
        self._emit(record)

    def span(self, name: str, bucket: str | None = None, step: int | None = None) -> "_Span":
        """Cut one boundary of the program, once, on both clocks: a ``TraceAnnotation`` in a
        captured profile (``StepTraceAnnotation`` when `step` is given: the dispatch of that
        train step) and ``perf_counter`` for the sink. The duration goes to the current
        iteration's split when this is an outermost span on the loop's thread (``t.split``),
        under its own name to ``t.inner`` when it is nested in one, to ``t.off_loop`` when
        another thread closes it, and to the goodput `bucket` when one is named. Outside a
        train loop (no :meth:`begin_iterations`) it only annotates — nothing is kept or
        written."""
        return _Span(self, name, bucket, step)

    # ------------------------------------------------------------------ goodput

    def begin_iterations(self) -> None:
        """The calling thread's train loop starts here: from now on every
        :meth:`record_step` closes one iteration, and the spans in between are its split.
        Python's garbage collections are timed from here to :meth:`close`."""
        self._loop_thread = threading.get_ident()
        self._span_depth = 0
        self._split, self._inner = {}, {}
        with self._lock:
            self._off_loop = {}
        self._gc_read = self._gc_total
        self._host_read = _host_readings()
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        self._iteration_start = time.perf_counter()

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook: one collection, cut as a span is (an annotation named
        ``gc.collect`` and ``perf_counter`` at the same two ends), on whichever thread it
        ran. Kept apart from :class:`_Span`: a collection can start inside the registry's
        lock, so this takes none, and one between two of the loop's spans is no part of
        their tiling."""
        if phase == "start":
            annotation = _annotation("gc.collect", None)
            annotation.__enter__()
            self._gc_open = (annotation, time.perf_counter())
        elif self._gc_open is not None:
            annotation, start = self._gc_open
            self._gc_open = None
            elapsed = time.perf_counter() - start
            annotation.__exit__(None, None, None)
            seconds, count = self._gc_total
            self._gc_total = (seconds + elapsed, count + 1)

    def _iteration_parts(self, drain: bool) -> dict:
        """The current iteration beside its wall time: ``split``, ``inner``, ``off_loop``,
        ``gc`` (the three absent when empty) and ``host``. `drain` starts the next
        iteration's count (:meth:`record_step`); without it nothing is taken away
        (:meth:`iteration_so_far`)."""
        parts: dict[str, Any] = {"split": {k: round(v, 6) for k, v in self._split.items()}}
        if self._inner:
            parts["inner"] = {k: round(v, 6) for k, v in self._inner.items()}
        with self._lock:
            off_loop = self._off_loop
            if drain:
                self._off_loop = {}
        if off_loop:
            parts["off_loop"] = {k: round(v, 6) for k, v in off_loop.items()}
        gc_total, host = self._gc_total, _host_readings()
        if gc_total[1] > self._gc_read[1]:
            parts["gc"] = {
                "seconds": round(gc_total[0] - self._gc_read[0], 6),
                "count": gc_total[1] - self._gc_read[1],
            }
        parts["host"] = {
            "nivcsw": host[0] - self._host_read[0],
            "majflt": host[1] - self._host_read[1],
            "cpu": round(host[2] - self._host_read[2], 6),
        }
        if drain:
            self._split, self._inner = {}, {}
            self._gc_read, self._host_read = gc_total, host
        return parts

    def iteration_so_far(self) -> dict:
        """What the current iteration has spent up to now — ``split``, ``inner``,
        ``off_loop``, ``gc``, ``host`` as the ``step`` record will carry them — and
        ``blame``, the span that holds the excess over the last iterations
        (:func:`span_holding_the_excess`). For the ``anomaly`` event of a slow step; empty
        outside a train loop."""
        if self._loop_thread is None:
            return {}
        parts = self._iteration_parts(drain=False)
        blame = span_holding_the_excess(parts["split"], parts.get("inner", {}), self._span_history)
        if blame is not None:
            parts["blame"] = blame
        return parts

    def record_step(self, step: int, data_seconds: float, step_seconds: float) -> None:
        """Per-step accounting from the train loops: dataloader wait + jitted-step wall
        time. Writes a step record and feeds the window buckets. In a train loop
        (:meth:`begin_iterations`) this is the iteration's last call: the record also
        carries the iteration's wall time, its split and what else the host did in it
        (``inner``, ``off_loop``, ``gc``, ``host``), and the write itself is the first span
        (``loop.record``) of the next iteration."""
        self._last_step = step
        timings: dict[str, Any] = {"data": round(data_seconds, 6)}
        with self.span("loop.record") as record:
            if self._loop_thread is not None:
                # the span's own start is the boundary: reading the iteration's parts is
                # the head of the next one's `loop.record`, and the tiling has no hole
                timings["wall"] = round(record.start - self._iteration_start, 6)
                timings.update(self._iteration_parts(drain=True))
                self._span_history.append({**timings["split"], **timings.get("inner", {})})
                self._iteration_start = record.start
            with self._lock:
                self._buckets["data"] += data_seconds
                if not self._seen_first_step:
                    self._seen_first_step = True
                    self._buckets["compile"] += step_seconds
                    timings["compile"] = round(step_seconds, 6)
                else:
                    self._buckets["step"] += step_seconds
                    self._step_times.append(step_seconds)
                    timings["step"] = round(step_seconds, 6)
            self._emit({"kind": "step", "step": step, "t": timings})

    def current_mfu(self) -> float | None:
        """Steady-state MFU %% over the current window: analytic model TFLOPs per step
        (`get_model_tflops`, per model-parallel device group) / measured step time, vs the
        group's aggregate peak. None until a steady step lands or when peak/model FLOPs are
        unknown."""
        if not self.model_tflops_per_step or not self.peak_tflops_per_device:
            return None
        with self._lock:
            if not self._step_times:
                return None
            mean_step = sum(self._step_times) / len(self._step_times)
        achieved = self.model_tflops_per_step / mean_step
        peak = self.peak_tflops_per_device * self.devices_per_group
        return 100.0 * achieved / peak

    def emit_window(self, step: int) -> dict | None:
        """Close the current logging window: write the window record (goodput breakdown,
        step-time stats, MFU, cumulative counters, memory gauges), fan rank-0 scalars out to
        the experiments tracker, reset the window accumulators."""
        now = time.perf_counter()
        mfu = self.current_mfu()
        for name, value in collect_memory_gauges().items():
            self.gauge(name, value)
        with self._lock:
            wall = max(now - self._window_start, 1e-9)
            buckets = {k: round(self._buckets.get(k, 0.0), 6) for k in GOODPUT_BUCKETS}
            accounted = sum(buckets.values())
            buckets["other"] = round(max(wall - accounted, 0.0), 6)
            buckets["goodput_pct"] = round(100.0 * self._buckets["step"] / wall, 3)
            step_times = self._step_times
            step_stats = None
            if step_times:
                step_stats = {
                    "count": len(step_times),
                    "mean": round(sum(step_times) / len(step_times), 6),
                    "min": round(min(step_times), 6),
                    "max": round(max(step_times), 6),
                }
            counters = dict(self.counters)
            gauges = dict(self.gauges)
            self._buckets = {k: 0.0 for k in GOODPUT_BUCKETS}
            self._step_times = []
            self._window_start = now

        tflops_per_group = None
        if self.model_tflops_per_step and step_stats:
            tflops_per_group = round(self.model_tflops_per_step / step_stats["mean"], 3)

        record = {
            "kind": "window",
            "step": step,
            "window_seconds": round(wall, 6),
            "goodput": buckets,
            "step_time": step_stats,
            "mfu_pct": round(mfu, 3) if mfu is not None else None,
            "tflops_per_group": tflops_per_group,
            "counters": counters,
            "gauges": gauges,
        }
        self._emit(record)

        if self.experiments_tracker is not None:
            scalars = {f"goodput/{k}_seconds": v for k, v in buckets.items() if k != "goodput_pct"}
            scalars["goodput/goodput_pct"] = buckets["goodput_pct"]
            if mfu is not None:
                scalars["goodput/mfu_pct"] = round(mfu, 3)
            for name, value in counters.items():
                scalars[f"counter/{name}"] = value
            for name, value in gauges.items():
                if isinstance(value, (int, float)):
                    scalars[f"gauge/{name}"] = value
            self.experiments_tracker.track(scalars, step=step, context="telemetry")
        return record

    # ------------------------------------------------------------------ profiler

    def poll_profiler(self, step: int, last_outputs=None) -> None:
        """`last_outputs`: the outputs of the step just dispatched (a capture waits for
        them before it starts or stops: it holds whole steps)."""
        if self.profiler is not None:
            self.profiler.poll(step, telemetry=self, last_outputs=last_outputs)

    # ------------------------------------------------------------------ lifecycle

    def close(self, status: str = "ok") -> None:
        """End of run. `status` is how it ended — ``ok``, ``preempted``, or
        ``error:<ExceptionType>`` — written into the run_end record so a reader can tell a
        clean exit from a crash without parsing logs (the loops call this from a `finally`,
        so the sink is flushed and statused on every exit path)."""
        if self.profiler is not None:
            self.profiler.close()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._emit(
            {
                "kind": "run_end",
                "step": self._last_step,
                "status": status,
                "counters": dict(self.counters),
            }
        )
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


class _NullTelemetry:
    """No-op stand-in returned by :func:`get_telemetry` when no train loop installed a real
    instance (inference tools, unit tests): cross-module counter calls cost one attribute
    lookup and nothing else."""

    rank = 0
    counters: dict[str, int] = {}
    profiler = None

    def count(self, name, value=1, event=False, step=None) -> None:
        pass

    def gauge(self, name, value) -> None:
        pass

    def event(self, name, step=None, **fields) -> None:
        pass

    def event_once(self, name, **fields) -> None:
        pass

    def emit_record(self, kind, step=None, **fields) -> None:
        pass

    def observe(self, name, value) -> None:
        pass

    def counters_snapshot(self) -> dict:
        return {}

    def gauges_snapshot(self) -> dict:
        return {}

    def quantiles_snapshot(self) -> dict:
        return {}

    def snapshot(self) -> dict:
        return {"counters": {}, "gauges": {}, "quantiles": {}}

    def span(self, name, bucket=None, step=None):
        # the profiler's clock is still cut (an inactive TraceMe costs nanoseconds)
        return _annotation(name, step)

    def begin_iterations(self) -> None:
        pass

    def iteration_so_far(self) -> dict:
        return {}

    def record_step(self, step, data_seconds, step_seconds) -> None:
        pass

    def current_mfu(self):
        return None

    def emit_window(self, step):
        return None

    def poll_profiler(self, step, last_outputs=None) -> None:
        pass

    def close(self, status: str = "ok") -> None:
        pass


_NULL = _NullTelemetry()
_ACTIVE: Telemetry | None = None


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_LISTENER_REGISTERED = False


def _on_jax_duration_event(event: str, duration_secs: float, **kwargs) -> None:
    """The process's one `jax.monitoring` listener: a backend compilation (a persistent
    cache load counts: the program was not in memory) while a train loop's telemetry is
    installed (`begin_iterations`; the serving engines' record streams stay as they are)."""
    telemetry = _ACTIVE
    if telemetry is None or telemetry._loop_thread is None or event != _COMPILE_EVENT:
        return
    telemetry.count("compiles")
    telemetry.event(
        "compile",
        step=telemetry._step_in_flight,
        seconds=round(duration_secs, 6),
        program=kwargs.get("fun_name"),
    )


def install_telemetry(telemetry: Telemetry) -> None:
    """Make `telemetry` the process-wide instance cross-module counters report to."""
    global _ACTIVE, _COMPILE_LISTENER_REGISTERED
    _ACTIVE = telemetry
    if not _COMPILE_LISTENER_REGISTERED:  # jax.monitoring has no way to take one back
        _COMPILE_LISTENER_REGISTERED = True
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration_event)


def uninstall_telemetry() -> None:
    global _ACTIVE
    _ACTIVE = None


def get_telemetry() -> Telemetry | _NullTelemetry:
    """The active instance, or a shared no-op when none is installed."""
    return _ACTIVE if _ACTIVE is not None else _NULL


def stable_config_hash(args) -> str | None:
    """Short stable hash of the fully-resolved config tree — two sinks with the same hash
    ran the same config, whatever YAML/defaults produced it. None when the args tree can't
    serialize (never fatal: the hash is attribution metadata)."""
    try:
        blob = json.dumps(args.to_dict(), sort_keys=True, default=str)
    except Exception:
        return None
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def build_telemetry(
    args,
    experiments_tracker=None,
    model_tflops_per_step: float | None = None,
    devices_per_group: int = 1,
) -> Telemetry:
    """Construct Telemetry from `args.logging_args.telemetry` (both train loops).

    Default paths hang off `save_args.save_path` so every run directory is self-contained:
    sink `<save_path>/telemetry/rank-<process>.jsonl`, profile trigger
    `<save_path>/telemetry/PROFILE_TRIGGER`, traces `<save_path>/telemetry/traces/`.
    """
    targs = getattr(args.logging_args, "telemetry", None)
    save_args = getattr(args, "save_args", None)
    save_path = getattr(save_args, "save_path", None)

    sink_path = None
    profiler = None
    peak_override = None
    if targs is not None:
        peak_override = targs.peak_tflops_per_device
        if targs.jsonl_sink:
            sink_path = targs.jsonl_path
            if sink_path is None and save_path is not None:
                sink_path = os.path.join(
                    save_path, "telemetry", f"rank-{jax.process_index():05d}.jsonl"
                )
        if targs.on_demand_profiling:
            trigger = targs.profile_trigger_path
            output = targs.profile_output_path
            if trigger is None and save_path is not None:
                trigger = os.path.join(save_path, "telemetry", "PROFILE_TRIGGER")
            if output is None and save_path is not None:
                output = os.path.join(save_path, "telemetry", "traces")
            if trigger is not None and output is not None:
                profiler = OnDemandProfiler(
                    trigger,
                    output,
                    num_steps=targs.profile_steps,
                    use_signal=targs.profile_on_sigusr1,
                )
            else:
                log_rank_0(
                    logging.WARNING,
                    "on-demand profiling disabled: no trigger/output path (set "
                    "logging_args.telemetry.profile_trigger_path/profile_output_path or "
                    "save_args.save_path)",
                )

    return Telemetry(
        sink_path=sink_path,
        experiments_tracker=experiments_tracker,
        model_tflops_per_step=model_tflops_per_step,
        peak_tflops_per_device=peak_override or detect_peak_tflops_per_device(),
        devices_per_group=devices_per_group,
        profiler=profiler,
        config_hash=stable_config_hash(args),
    )
