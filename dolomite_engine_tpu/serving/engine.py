"""Continuous-batching serving engine: one jitted decode step over a paged KV pool.

The legacy path (`generation_utils.generate_tokens`) is one-shot: a batch arrives
together, shares one set of python-static sampling params, and stalls until its slowest
row finishes. This engine is the Orca/vLLM-style fix with fully static shapes:

- the KV cache is a **paged pool** by default (`kv_cache.PagedKVCachePool`): fixed-size
  pages shared across slots, per-slot page tables threaded through the jitted decode
  step, HBM scaling with resident tokens instead of ``num_slots * max_len``
  (``paged=False`` keeps the PR-4 dense slot pool for A/B);
- **prefix caching** (`prefix_cache.PrefixCache`): page-aligned prompt prefixes that are
  already resident are shared read-only (refcounted) instead of re-prefilled; a partially
  matching tail page is copied at page granularity (COW) and only the miss suffix is
  computed;
- **prefill is chunked**: prompts are computed `prefill_chunk_tokens` at a time
  (scheduler knob), interleaved with decode steps, so a long arrival no longer stalls the
  inter-token latency of running requests;
- **decode** is a single jitted step over the whole ``[num_slots]`` batch — per-slot
  cache positions, page-table rows, RNG streams, and per-slot **traced** sampling params
  (`ops/sampling.sample_tokens_vectorized`), so one compiled program serves any request
  mix and compiles exactly once for the lifetime of the engine;
- the **scheduler** admits waiting requests into freed slots at every step boundary
  (FCFS, bounded queue, wall-clock deadlines), page-availability-aware in paged mode;
- **speculative decoding** (optional): a drafter proposes up to K tokens per slot —
  n-gram/prompt-lookup self-drafting (`speculate_ngram=True`, no extra model) or a
  smaller greedy draft model (`draft_model=`/`draft_params=`) — and ONE jitted verify
  step scores all K+1 positions per slot (static K, per-slot traced acceptance in
  `ops/sampling.speculative_accept`), committing accepted drafts plus a bonus token.
  Rejected tail writes roll back through the frontier/trash-page discipline: per-slot
  lengths only advance past K/V the target actually committed, so stale speculative
  writes are masked and overwritten. Greedy outputs stay bit-exact vs `generate_tokens`;
  sampled outputs follow the exact target distribution (deterministic-proposal
  rejection sampling).

Tokens stream out through per-request callbacks the moment the host sees them (one
device->host sync per step — the price of streaming and EOS detection, identical to the
legacy path's end-of-call fetch amortized over steps).

Numerics: a request decoded through the engine reproduces an equivalent single-request
`generate_tokens` call token-for-token — with the paged pool, prefix hits, and chunked
prefill all active (same per-step RNG split discipline, same processor encodings; see
tests/test_serving.py + tests/test_serving_paged.py for the bit-exact parity suites).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops.pallas import active_kernel_backends
from ..ops.sampling import sample_tokens_vectorized, speculative_accept
from ..utils.program_signature import (
    ProgramSignature,
    capture_jit_signature,
    emit_program_signature_record,
)
from ..utils.telemetry import QuantileSketch, Telemetry, get_telemetry
from ..utils.tracing import RequestTrace
from .kv_cache import TRASH_PAGE, HostSwapPool, PagedKVCachePool, SlotKVCachePool
from .prefix_cache import PrefixCache, PrefixMatch
from .speculation import DraftModelDrafter, NgramDrafter
from .scheduler import (
    Request,
    RequestState,
    RequestStatus,
    SamplingParams,
    Scheduler,
    TierSLO,
)

_DEFAULT = object()  # "use the engine default" sentinel for per-request eos overrides


@dataclass
class EngineStats:
    """Cumulative host-side accounting: rates for telemetry records and the bench harness.

    `prefill_seconds`/`decode_seconds` are wall time inside the respective jitted calls
    (including the host fetch that forces completion); `prefill_tokens` counts prompt
    tokens actually COMPUTED (prefix-cache hits are skipped work and show up in
    `prefix_hit_tokens` instead); `decode_tokens` counts tokens emitted by decode steps.
    The first token of each request is sampled inside prefill — it shows up in `ttft_s`
    samples, not in either rate. Cumulative over the engine's lifetime, like the
    telemetry window counters.

    Latency samples (`ttft_s`, per-tier TTFT/ITL) are held in bounded
    :class:`~dolomite_engine_tpu.utils.telemetry.QuantileSketch` reservoirs rather than
    raw lists, so host memory stays O(capacity) per series on a long-running serve;
    means stay exact (running sum) and p99 is nearest-rank over a uniform subsample —
    bit-identical to the unbounded computation until a series exceeds the reservoir
    capacity (4096 samples).
    """

    prefill_seconds: float = 0.0
    decode_seconds: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    decode_steps: int = 0
    ttft_s: QuantileSketch = field(default_factory=QuantileSketch)
    admitted: int = 0
    completed: int = 0
    rejected: int = 0
    cancelled: int = 0
    prefix_hit_tokens: int = 0
    prefix_miss_tokens: int = 0
    peak_active: int = 0
    draft_tokens_proposed: int = 0
    draft_tokens_accepted: int = 0
    # contention-aware scheduling (docs/SERVING.md "Scheduling under contention"):
    # preemptions counts slot evictions (swap or drop-and-recompute); swapped pages
    # count page moves through the host pool; session_hits counts admissions whose
    # live session had resident prefix pages to reuse
    preemptions: int = 0
    pages_swapped_out: int = 0
    pages_swapped_in: int = 0
    session_hits: int = 0
    # per-tier latency samples: TTFT per admitted request, mean inter-token latency per
    # finished request (the quantities the per-tier SLOs target)
    ttft_s_by_tier: dict[int, QuantileSketch] = field(default_factory=dict)
    itl_s_by_tier: dict[int, QuantileSketch] = field(default_factory=dict)
    admitted_by_tier: dict[int, int] = field(default_factory=dict)
    completed_by_tier: dict[int, int] = field(default_factory=dict)
    preempted_by_tier: dict[int, int] = field(default_factory=dict)

    def prefill_tok_s(self) -> float | None:
        if self.prefill_seconds <= 0:
            return None
        return self.prefill_tokens / self.prefill_seconds

    def decode_tok_s(self) -> float | None:
        if self.decode_seconds <= 0:
            return None
        return self.decode_tokens / self.decode_seconds

    def mean_ttft_s(self) -> float | None:
        return self.ttft_s.mean()

    def prefix_hit_rate(self) -> float | None:
        total = self.prefix_hit_tokens + self.prefix_miss_tokens
        if total == 0:
            return None
        return self.prefix_hit_tokens / total

    def accept_rate(self) -> float | None:
        """Fraction of proposed draft tokens the target accepted (speculation only)."""
        if self.draft_tokens_proposed == 0:
            return None
        return self.draft_tokens_accepted / self.draft_tokens_proposed

    def accepted_tokens_per_step(self) -> float | None:
        """Mean accepted draft tokens per decode (verify) step — total emitted tokens
        per step is this + 1 (the bonus token every verified slot always emits)."""
        if self.decode_steps == 0:
            return None
        return self.draft_tokens_accepted / self.decode_steps

    def ttft_p99_s(self, tier: int) -> float | None:
        """p99 TTFT for one tier (the per-tier SLO quantity; None without samples)."""
        return _percentile(self.ttft_s_by_tier.get(tier, []), 0.99)

    def itl_mean_s(self, tier: int) -> float | None:
        samples = self.itl_s_by_tier.get(tier)
        return samples.mean() if samples is not None else None


def _percentile(samples, q: float) -> float | None:
    """Nearest-rank percentile over a list or QuantileSketch (deterministic, no
    interpolation — bench-stable)."""
    if not samples:
        return None
    ordered = sorted(samples)
    rank = min(len(ordered) - 1, max(0, int(-(-q * len(ordered) // 1)) - 1))
    return ordered[rank]


def _rederive_rng_carry(rng, steps: int) -> np.ndarray:
    """Re-derive a slot's PRNG carry after ``steps`` consumed splits of ``rng``.

    Every sampling site advances a slot's rng the same way — one ``jax.random.split``
    whose row 0 becomes the carry (the sampling prefill chunk splits the request key
    directly; decode/verify steps split the per-slot row via ``jax.vmap(split)``,
    which is bit-identical to splitting each row alone). The carry is therefore a pure
    split-chain of the request key and ``RequestState.rng_steps`` counts its length,
    so this fold lets a *surviving* replica continue a migrated request's sample
    stream bit-exact using no device state from the replica that died
    (`ServingEngine.adopt_inflight`)."""
    key = rng
    for _ in range(steps):
        key = jax.random.split(key)[0]
    return np.asarray(key)


@dataclass
class _ResumeState:
    """Decode context captured at preemption: what it takes to continue the request
    token-for-token. ``next_token`` is the last emitted (not yet cache-written) token
    the next decode step feeds; ``rng`` the per-slot carry; ``resident`` how many
    sequence positions were written to the KV pool. ``swapped`` means the page bytes
    are parked in the host swap pool (restore = byte copy); otherwise the prefix
    ``(prompt + tokens)[:resident]`` is recomputed through the radix cache."""

    next_token: int
    rng: Any  # np [2] uint32 PRNG carry
    resident: int
    swapped: bool


@dataclass
class _PrefillTask:
    """A slot whose prefix is still being computed (chunked prefill in flight).

    ``prefill_ids`` is the token span the chunks must compute: the prompt for a fresh
    request, ``(prompt + generated)[:resident]`` for a drop-and-recompute resume (whose
    final chunk then restores decode state instead of sampling a first token)."""

    state: RequestState
    encoded: tuple  # (do_sample, temperature, top_k, top_p) dense encoding
    pos: int  # next prefill position to compute (prefix-cache hits start it past 0)
    prefill_ids: list[int]
    resume: _ResumeState | None = None


class ServingEngine:
    """Drive a decoder-only dolomite model as a continuously-batched token service.

    Args:
        model: the flax module (unrolled, standard attention KV caches — not scan_layers,
            not the RNN hybrid's recurrent caches).
        params: parameter pytree (bare ``params`` tree or full variables dict).
        num_slots: decode batch width == max concurrent requests.
        max_len: per-slot cache length; every request needs
            ``len(prompt) + max_new_tokens <= max_len``.
        prefill_bucket_multiple: prompts (paged: prefill chunks) are right-padded to the
            next multiple for the bucketed prefill jit (one compile per distinct bucket).
        max_waiting: waiting-queue bound; `submit` raises
            :class:`~dolomite_engine_tpu.serving.scheduler.QueueFullError` beyond it.
        eos_token_id / pad_token_id: engine defaults (per-request eos override on submit).
        record_interval: emit a ``serving`` telemetry record every N decode steps
            (0 = only on :meth:`drain`).
        paged: use the paged KV pool (default) or the dense PR-4 slot pool.
        page_size: tokens per KV page (positive multiple of 8).
        num_pages: physical pages in the pool (page 0 is reserved as trash). Default
            matches the dense pool's capacity; set it to your HBM budget to oversubscribe
            slots — admission reserves worst-case pages so decode can never run out.
        kv_dtype: paged-pool page storage format (serving/kv_cache.KV_DTYPES):
            ``"bf16"`` halves page bytes vs fp32, ``"int8"``/``"fp8"`` store quantized
            pages with per-(page, kv-head) fp32 scales (quantize-on-scatter,
            dequantize-on-read; ops/kv_quant.py) — roughly double the sustainable slots
            again at a fixed HBM budget, at tolerance-level accuracy. None keeps
            `cache_dtype` / the model dtype. Paged mode only.
        prefill_chunk_tokens: per-step prefill token budget (positive multiple of 8).
            With speculation on, the verify step's K+1 computed positions per decoding
            slot count against the same budget (`Scheduler.prefill_budget`).
        prefix_caching: keep finished requests' page-aligned prefixes resident and share
            them with matching future prompts (paged mode only).
        preemption: what happens to a low-tier slot when a higher-tier request cannot
            admit, or when an oversubscribed pool runs physically dry: ``"off"`` (never
            evict — the classic reserve-everything engine), ``"swap"`` (park the
            victim's KV pages in a host-memory pool through one jitted gather/scatter
            pair and restore them byte-identical on resume), or ``"recompute"``
            (release the pages — registered in the radix prefix cache first, so resume
            is usually a cheap re-attach — and rebuild the slot through chunked
            prefill). Either way a resumed request continues token-for-token identical
            to an unpreempted run. Paged mode only.
        oversubscribe_ratio: admission may promise up to ``ratio * allocatable`` pages
            (>= 1.0). Worst-case reservations strand capacity — most requests finish
            well short of ``prompt + max_new`` — so oversubscribing admits more
            concurrent work; preemption makes the physical shortfall safe, hence
            ``ratio > 1`` requires ``preemption != "off"``.
        session_ttl_s: multi-turn retention window. A finished request with a
            ``session_id`` pins its prefix pages (exempt from LRU eviction) until the
            session goes idle for this long; each new turn refreshes the TTL.
        tier_slos: per-priority-tier latency targets
            (:class:`~dolomite_engine_tpu.serving.scheduler.TierSLO`): the TTFT target
            orders the chunked-prefill budget (least headroom first) and both targets
            are reported next to the measured per-tier latencies in serving telemetry.
        speculate_ngram: n-gram / prompt-lookup self-drafting — propose up to `draft_k`
            tokens per slot by matching the slot's recent suffix against its own
            prompt+generation history (host-side, no extra model).
        draft_model / draft_params: a smaller supported model (+ its params) that drafts
            `draft_k` greedy tokens per slot per step. Mutually exclusive with
            `speculate_ngram`; must share the target's tokenizer/vocab.
        draft_k: draft tokens proposed per engine step (K >= 1); the verify step scores
            K+1 positions per slot and compiles once per engine lifetime.
        ngram_max: longest suffix length tried by the n-gram drafter (down to 1).
        mesh: run every jitted engine program (prefill chunks, decode, verify) under this
            device mesh — the TP/EP-sharded replica path (serving/cluster/sharded.py).
            Params must already be placed per the mesh (`load_pretrained_params` /
            `cluster.sharded.shard_params`); the KV pool is sharded along kv heads.
        sharding_rules: logical-axis rules bound while tracing under `mesh` (the
            engine-side mirror of `ModelWrapper.apply_scope`), so the models'
            `logical_constraint` calls resolve. Required when `mesh` is given.
        replica_id: stamped on every ``serving`` telemetry record — which replica of a
            router fleet (serving/cluster/router.py) produced it. None = standalone.
        trace_requests: per-request distributed tracing (utils/tracing.py): every
            submitted request carries a span tree — queue wait, admission, prefill
            chunks, decode/verify, preemption park/resume, disaggregated handoff — and
            emits one ``trace`` telemetry record at finish. Off by default and
            zero-cost when off: no trace objects exist, no extra records are written,
            outputs and compile counts are byte-identical (asserted in tests).
        signature_records: self-report the compiled programs: the first ``serving``
            telemetry record emitted after any program traced also writes one
            ``program_signature`` record (utils/program_signature.py; lowering-only —
            cost, donation, HLO features — so no extra compiles). Off by default: the
            lowering re-trace is not free on large models.
        prefill_only: run this engine as a disaggregation PrefillWorker (paged mode
            only): requests are admitted and chunk-prefilled as usual, the first token
            streams out, but instead of decoding, finished prefills park for
            `take_ready_handoffs` — a DecodeWorker adopts the KV pages via
            `serving/cluster/disagg.KVHandoff`.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        num_slots: int,
        max_len: int,
        prefill_bucket_multiple: int = 64,
        max_waiting: int = 128,
        eos_token_id: int | None = None,
        pad_token_id: int = 0,
        cache_dtype=None,
        rng: jax.Array | None = None,
        record_interval: int = 0,
        clock=time.monotonic,
        paged: bool = True,
        page_size: int = 16,
        num_pages: int | None = None,
        kv_dtype: str | None = None,
        prefill_chunk_tokens: int = 512,
        prefix_caching: bool = True,
        preemption: str = "off",
        oversubscribe_ratio: float = 1.0,
        session_ttl_s: float = 300.0,
        tier_slos: dict[int, TierSLO] | None = None,
        speculate_ngram: bool = False,
        draft_model: Any = None,
        draft_params: Any = None,
        draft_k: int = 4,
        ngram_max: int = 3,
        mesh: Any = None,
        sharding_rules: Any = None,
        replica_id: int | None = None,
        prefill_only: bool = False,
        trace_requests: bool = False,
        signature_records: bool = False,
        slo_monitor: Any = None,
        flight_recorder: Any = None,
    ) -> None:
        if mesh is not None and sharding_rules is None:
            raise ValueError(
                "mesh requires sharding_rules (ModelWrapper.sharding_rules() or "
                "cluster.sharded.inference_sharding_rules())"
            )
        if prefill_only and not paged:
            raise ValueError("prefill_only (disaggregation) requires the paged KV pool")
        if kv_dtype is not None and not paged:
            raise ValueError("kv_dtype (quantized/low-bit KV) requires the paged KV pool")
        if prefill_only and (speculate_ngram or draft_model is not None):
            raise ValueError("prefill_only workers do not decode, so cannot speculate")
        if preemption not in ("off", "swap", "recompute"):
            raise ValueError(
                f"preemption must be 'off', 'swap', or 'recompute', got {preemption!r}"
            )
        if preemption != "off" and not paged:
            raise ValueError("preemption requires the paged KV pool")
        if preemption != "off" and prefill_only:
            raise ValueError(
                "prefill_only workers park finished prefills for handoff and never "
                "contend on decode pages; run them with preemption='off'"
            )
        if oversubscribe_ratio < 1.0:
            raise ValueError(
                f"oversubscribe_ratio must be >= 1.0, got {oversubscribe_ratio}"
            )
        if oversubscribe_ratio > 1.0 and preemption == "off":
            raise ValueError(
                "oversubscribe_ratio > 1.0 reserves pages that are not physically "
                "backed; that is only safe with preemption enabled ('swap' or "
                "'recompute')"
            )
        if session_ttl_s <= 0:
            raise ValueError(f"session_ttl_s must be positive, got {session_ttl_s}")
        if prefill_bucket_multiple <= 0 or prefill_bucket_multiple % 8 != 0:
            raise ValueError(
                f"prefill_bucket_multiple must be a positive multiple of 8, got "
                f"{prefill_bucket_multiple}"
            )
        if speculate_ngram and draft_model is not None:
            raise ValueError(
                "speculate_ngram and draft_model are mutually exclusive draft sources"
            )
        if draft_model is not None and draft_params is None:
            raise ValueError("draft_model requires draft_params")
        if (speculate_ngram or draft_model is not None) and draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        config = getattr(model, "config", None)
        n_positions = getattr(config, "n_positions", None)
        if n_positions is not None and max_len > n_positions:
            raise ValueError(f"max_len={max_len} exceeds model n_positions={n_positions}")

        self.model = model
        self._variables = {"params": params} if "params" not in params else params
        self.default_eos = eos_token_id
        self.pad_token_id = pad_token_id
        self.cache_dtype = cache_dtype
        self.prefill_bucket_multiple = prefill_bucket_multiple
        self.record_interval = record_interval
        self.paged = paged
        self.mesh = mesh
        self.sharding_rules = sharding_rules
        self.replica_id = replica_id
        self.prefill_only = prefill_only
        self.trace_requests = trace_requests
        self.signature_records = signature_records
        # live observability plane (docs/OBSERVABILITY.md "Live metrics"): both default
        # to None and every hook below is a single `is None` check, so the off path's
        # records/compiles are byte-identical to an engine built without them
        self.slo_monitor = slo_monitor  # utils/diagnostics.ServingSLOMonitor
        self.flight_recorder = flight_recorder  # utils/diagnostics.FlightRecorder
        # program name -> (jitted fn, abstract example args), recorded at each program's
        # first invocation so `program_signatures()` can re-lower the exact same shapes
        self._program_records: dict[str, tuple[Any, tuple]] = {}
        self._signatures_emitted = False
        # which backend the chunked-prefill attention lowers through — stamped on
        # prefill_chunk trace spans so a timeline attributes compute to the kernel tier
        self._prefill_backend = active_kernel_backends().get("prefill_attention", "xla")
        # admission-attempt scratch (valid only while tracing the head's admission):
        # pop timestamp and victims evicted on the head's behalf this attempt
        self._admit_t0: float | None = None
        self._admit_victims = 0
        # prefill-only mode: finished prefills parked here (slot + pages still resident)
        # until a DecodeWorker adopts their KV (serving/cluster/disagg.py)
        self._ready_handoffs: list[RequestState] = []

        self.preemption = preemption
        self.session_ttl_s = session_ttl_s
        if paged:
            self.pool: Any = PagedKVCachePool(
                model, num_slots, max_len, page_size, num_pages, cache_dtype, mesh=mesh,
                kv_dtype=kv_dtype, oversubscribe_ratio=oversubscribe_ratio,
            )
            self.prefix = PrefixCache(page_size) if prefix_caching else None
            self._swap = HostSwapPool(self.pool) if preemption == "swap" else None
        else:
            self.pool = SlotKVCachePool(model, num_slots, max_len, cache_dtype, mesh=mesh)
            self.prefix = None
            self._swap = None
        self.scheduler = Scheduler(
            max_waiting=max_waiting, clock=clock, prefill_chunk_tokens=prefill_chunk_tokens,
            tier_slos=tier_slos,
        )
        self.stats = EngineStats()
        self._step_count = 0
        self._last_record_step = 0
        self._base_rng = jax.random.PRNGKey(0) if rng is None else rng

        num = self.pool.num_slots
        # dense per-slot state, host-resident (mutated at admission/finish, shipped to the
        # decode jit each step; shapes are static so no recompiles)
        self._tokens = np.zeros(num, np.int32)
        self._rngs = np.array(jax.random.split(jax.random.PRNGKey(0), num))
        self._do_sample = np.zeros(num, bool)
        self._temperature = np.ones(num, np.float32)
        self._top_k = np.zeros(num, np.int32)
        self._top_p = np.ones(num, np.float32)
        self._slot_states: dict[int, RequestState] = {}
        # chunked prefill in flight (paged mode): FCFS order + per-slot progress
        self._prefill_tasks: dict[int, _PrefillTask] = {}
        self._prefill_order: list[int] = []

        self._prefill_fns: dict[int, Any] = {}  # dense mode: whole-prompt bucket -> jit
        self._chunk_fns: dict[tuple[int, bool], Any] = {}  # paged: (width, final) -> jit
        # donate the cache pool: decode rewrites it in place instead of copying
        # [layers, num_slots, max_len] (dense) / [layers, num_pages, page_size] (paged)
        # of K/V every step
        decode_impl = self._decode_impl_paged if paged else self._decode_impl
        self._decode_step = jax.jit(decode_impl, donate_argnums=(1,))

        # speculative decoding: drafter (host-side or a small model) + ONE jitted verify
        # step scoring K+1 positions per slot — replaces the decode step when enabled
        self.speculating = bool(speculate_ngram or draft_model is not None)
        self.draft_k = draft_k
        self._ngram = NgramDrafter(draft_k, ngram_max) if speculate_ngram else None
        self._draft = (
            DraftModelDrafter(
                draft_model,
                draft_params,
                num_slots=num,
                max_len=max_len,
                draft_k=draft_k,
                pad_token_id=pad_token_id,
                prefill_bucket_multiple=prefill_bucket_multiple,
                cache_dtype=cache_dtype,
            )
            if draft_model is not None
            else None
        )
        verify_impl = self._verify_impl_paged if paged else self._verify_impl
        self._verify_step = (
            jax.jit(verify_impl, donate_argnums=(1,)) if self.speculating else None
        )

    def _scope(self):
        """Context every device call runs under: the replica's mesh (classic resource
        env, which `parallel.sharding.logical_constraint` resolves inside jit) plus the
        logical-axis rules. Meshless engines get a no-op stack, so the single-device
        path is untouched. Tracing happens on each jit's first call — always inside
        `step()`/admission, hence always inside this scope."""
        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(self.mesh)
            stack.enter_context(nn.logical_axis_rules(self.sharding_rules))
        return stack

    # ------------------------------------------------------------------ jitted programs

    def _decode_impl(self, variables, caches, tokens, lengths, rngs, do_sample, temperature, top_k, top_p):
        out = self.model.apply(
            variables,
            tokens[:, None],
            position_ids=lengths[:, None],
            kv_caches=caches,
            cache_index=lengths,
        )
        logits = out.logits[:, -1]
        split = jax.vmap(jax.random.split)(rngs)  # [S, 2, 2]: row 0 carries, row 1 samples
        next_tokens = sample_tokens_vectorized(
            logits, split[:, 1], do_sample, temperature, top_k, top_p
        )
        return out.kv_caches, next_tokens, split[:, 0]

    def _decode_impl_paged(
        self, variables, caches, page_table, tokens, lengths, rngs, do_sample, temperature, top_k, top_p
    ):
        # one shared [S, max_pages] table serves every layer; rows of slots that are idle
        # or mid-prefill are zeroed by the host, so their garbage token lands in trash.
        # (**c carries the quantized pools' scale arrays along with the pages)
        kv = [{**c, "page_table": page_table} for c in caches]
        out = self.model.apply(
            variables,
            tokens[:, None],
            position_ids=lengths[:, None],
            kv_caches=kv,
            cache_index=lengths,
        )
        logits = out.logits[:, -1]
        split = jax.vmap(jax.random.split)(rngs)
        next_tokens = sample_tokens_vectorized(
            logits, split[:, 1], do_sample, temperature, top_k, top_p
        )
        new_caches = [
            {k: v for k, v in c.items() if k != "page_table"} for c in out.kv_caches
        ]
        return new_caches, next_tokens, split[:, 0]

    def _verify_impl(
        self, variables, caches, tokens, lengths, num_drafts, rngs, do_sample, temperature, top_k, top_p
    ):
        """Speculative verify over the dense slot pool: score the [S, K+1] window (last
        committed token + K drafts) at each row's own cache frontier in ONE call, then
        accept/resample in-graph. The K+1 writes land at per-row positions; rejected
        tails stay behind the advanced frontier (masked) until overwritten."""
        width = tokens.shape[1]
        positions = lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        out = self.model.apply(
            variables,
            tokens,
            position_ids=positions,
            kv_caches=caches,
            cache_index=lengths,
        )
        accepted, bonus, carry = speculative_accept(
            out.logits, tokens[:, 1:], num_drafts, rngs, do_sample, temperature, top_k, top_p
        )
        return out.kv_caches, accepted, bonus, carry

    def _verify_impl_paged(
        self, variables, caches, page_table, tokens, lengths, num_drafts, rngs, do_sample, temperature, top_k, top_p
    ):
        """Paged verify: identical acceptance, but the K+1 writes scatter through each
        row's page table — unmapped window positions (idle rows, overhang past the
        request's worst-case pages) land in the trash page."""
        kv = [{**c, "page_table": page_table} for c in caches]
        width = tokens.shape[1]
        positions = lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        out = self.model.apply(
            variables,
            tokens,
            position_ids=positions,
            kv_caches=kv,
            cache_index=lengths,
        )
        accepted, bonus, carry = speculative_accept(
            out.logits, tokens[:, 1:], num_drafts, rngs, do_sample, temperature, top_k, top_p
        )
        new_caches = [
            {k: v for k, v in c.items() if k != "page_table"} for c in out.kv_caches
        ]
        return new_caches, accepted, bonus, carry

    def _get_prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:

            def prefill(variables, ids, mask, length, rng, do_sample, temperature, top_k, top_p):
                # right-padded prompt: token i sits at cache position i, so the slot's
                # validity frontier is just its length — no per-slot pad offsets
                position_ids = jnp.arange(bucket, dtype=jnp.int32)[None, :]
                caches = self.model.init_kv_caches(1, bucket, self.cache_dtype)
                out = self.model.apply(
                    variables,
                    ids,
                    position_ids=position_ids,
                    attention_mask=mask,
                    kv_caches=caches,
                    cache_index=0,  # static 0: keeps the prefill fast path
                )
                last = jax.lax.dynamic_slice_in_dim(out.logits, length - 1, 1, axis=1)[:, 0]
                carry, step_rng = jax.random.split(rng)
                token = sample_tokens_vectorized(
                    last,
                    step_rng[None],
                    do_sample[None],
                    temperature[None],
                    top_k[None],
                    top_p[None],
                )
                return token[0], carry, out.kv_caches

            fn = self._prefill_fns[bucket] = jax.jit(prefill)
        return fn

    def _get_chunk_fn(self, width: int, final: bool):
        """Chunked-prefill program for one chunk bucket width: scatter the chunk's K/V
        into the slot's pages (pad tail -> trash) while attending causally over the whole
        resident prefix. The FINAL chunk additionally samples the request's first token
        with the same rng-split discipline as `generate_tokens` prefill."""
        key = (width, final)
        fn = self._chunk_fns.get(key)
        if fn is None:

            def chunk(variables, caches, table_row, ids, mask, start, num_real, rng, do_sample, temperature, top_k, top_p):
                kv = [{**c, "page_table": table_row} for c in caches]
                position_ids = (start + jnp.arange(width, dtype=jnp.int32))[None, :]
                out = self.model.apply(
                    variables,
                    ids,
                    position_ids=position_ids,
                    attention_mask=mask,
                    kv_caches=kv,
                    cache_index=start,
                )
                new_caches = [
                    {k: v for k, v in c.items() if k != "page_table"}
                    for c in out.kv_caches
                ]
                if not final:
                    return new_caches
                last = jax.lax.dynamic_slice_in_dim(out.logits, num_real - 1, 1, axis=1)[:, 0]
                carry, step_rng = jax.random.split(rng)
                token = sample_tokens_vectorized(
                    last,
                    step_rng[None],
                    do_sample[None],
                    temperature[None],
                    top_k[None],
                    top_p[None],
                )
                return new_caches, token[0], carry

            fn = self._chunk_fns[key] = jax.jit(chunk, donate_argnums=(1,))
        return fn

    # ------------------------------------------------------------------ submission

    def submit(
        self,
        prompt_ids: list[int],
        max_new_tokens: int,
        sampling: SamplingParams | None = None,
        eos_token_id: int | None = _DEFAULT,
        deadline_s: float | None = None,
        on_token=None,
        on_finish=None,
        rng: jax.Array | None = None,
        priority: int = 0,
        session_id: str | None = None,
        trace: RequestTrace | None = None,
    ) -> RequestState:
        """Enqueue a request (tier-then-FCFS; ``priority`` 0 is the top tier). A
        ``session_id`` marks the request as one turn of a conversation: its prefix
        pages are pinned against LRU eviction until the session's TTL lapses, so the
        next turn re-attaches instead of re-prefilling. Raises QueueFullError at the
        queue bound and ValueError when the request cannot fit a slot."""
        prompt_ids = list(map(int, prompt_ids))
        if not prompt_ids:
            raise ValueError("empty prompt")
        if max_new_tokens <= 0:
            raise ValueError(f"max_new_tokens must be positive, got {max_new_tokens}")
        if priority < 0:
            raise ValueError(f"priority must be >= 0 (0 is the top tier), got {priority}")
        if len(prompt_ids) + max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"request needs {len(prompt_ids)} prompt + {max_new_tokens} new tokens "
                f"> max_len={self.pool.max_len}"
            )
        if self.paged:
            worst_pages = -(-(len(prompt_ids) + max_new_tokens) // self.pool.page_size)
            if worst_pages > self.pool.num_pages - 1:
                raise ValueError(
                    f"request needs {worst_pages} page(s) worst-case but the pool has "
                    f"{self.pool.num_pages - 1} allocatable page(s)"
                )
        if rng is None:
            self._base_rng, rng = jax.random.split(self._base_rng)
        request = Request(
            prompt_ids=prompt_ids,
            max_new_tokens=int(max_new_tokens),
            sampling=sampling or SamplingParams(),
            eos_token_id=self.default_eos if eos_token_id is _DEFAULT else eos_token_id,
            rng=rng,
            deadline_s=deadline_s,
            on_token=on_token,
            on_finish=on_finish,
            priority=int(priority),
            session_id=session_id,
        )
        try:
            state = self.scheduler.submit(request)
        except Exception:
            self.stats.rejected += 1
            get_telemetry().count("serving_requests_rejected")
            raise
        if trace is None and self.trace_requests:
            trace = RequestTrace(request_id=request.request_id, clock=self.scheduler.clock)
        if trace is not None:
            state.trace = trace
            trace.request_id = request.request_id
            root = trace.ensure_root(
                t0=state.submit_t,
                tier=request.priority,
                prompt_tokens=len(prompt_ids),
                max_new_tokens=request.max_new_tokens,
                replica_id=self.replica_id,
            )
            trace.open["queue_wait"] = trace.begin(
                "queue_wait", parent=root, t0=state.submit_t, tier=request.priority, segment=0
            )
        return state

    # ------------------------------------------------------------------ engine loop

    def has_work(self) -> bool:
        """Whether stepping can still make progress. Parked handoffs (prefill_only) are
        NOT progressable work — a DecodeWorker has to adopt them — so a drained
        PrefillWorker with only parked slots reports idle instead of spinning."""
        if self.scheduler.queue_depth > 0:
            return True
        parked = {state.slot for state in self._ready_handoffs}
        return any(slot not in parked for slot in self._slot_states)

    def step(self) -> bool:
        """One scheduler iteration: reap deadline-expired slots, admit waiting requests
        into free slots, advance chunked prefills up to the budget (paged mode), run one
        decode step over the slot batch. Returns whether any work remains.

        Observability hooks ride on the end of the step: the wall time feeds the
        registry's step-time quantile sketch (in-memory only, no record), the flight
        recorder ring gets one entry (and a dump if the step raised), and the SLO
        burn-rate monitor observes the engine's signals. All three are no-ops on the
        off path (`get_telemetry()` null / recorder and monitor None)."""
        t0 = time.perf_counter()
        try:
            with self._scope():
                self._step_in_scope()
        except Exception as error:
            if self.flight_recorder is not None:
                from ..utils.diagnostics import crash_reason

                self.flight_recorder.record(
                    self._step_count,
                    replica_id=self.replica_id,
                    queue_depth=self.scheduler.queue_depth,
                    slots_active=self.pool.num_active,
                    error=repr(error),
                )
                self.flight_recorder.dump(crash_reason(error), error=error)
            raise
        get_telemetry().observe("serving/step_s", time.perf_counter() - t0)
        if self.flight_recorder is not None:
            self.flight_recorder.record(
                self._step_count,
                replica_id=self.replica_id,
                queue_depth=self.scheduler.queue_depth,
                slots_active=self.pool.num_active,
                completed=self.stats.completed,
                preemptions=self.stats.preemptions or None,
            )
        if self.slo_monitor is not None:
            self.slo_monitor.observe_engine(self)
        if (
            self.record_interval
            and self._step_count - self._last_record_step >= self.record_interval
        ):
            self.emit_serving_record()
        return self.has_work()

    def _step_in_scope(self) -> None:
        self._cancel_expired_running()
        if self.paged:
            self._admit_paged()
            if self.prefill_only:
                # no decode competes for the budget; parked handoff slots never decode
                self._run_prefill_chunks(self.scheduler.prefill_chunk_tokens)
                self.stats.peak_active = max(self.stats.peak_active, self.pool.num_active)
                return
            # decode's computed tokens count against the shared per-step budget: a plain
            # decode costs 1 token per decoding slot, a verify step K+1 (it really does
            # score the whole window) — prefill chunks get what is left
            decoding = sum(1 for s in self._slot_states if s not in self._prefill_tasks)
            per_slot = self.draft_k + 1 if self.speculating else 1
            self._run_prefill_chunks(self.scheduler.prefill_budget(per_slot * decoding))
            if any(slot not in self._prefill_tasks for slot in self._slot_states):
                if self.speculating:
                    self._verify_once_paged()
                else:
                    self._decode_once_paged()
        else:
            self._admit()
            if self._slot_states:
                if self.speculating:
                    self._verify_once_dense()
                else:
                    self._decode_once()
        self.stats.peak_active = max(self.stats.peak_active, self.pool.num_active)

    def drain(self) -> None:
        """Run until every submitted request finished; emit a final serving record."""
        while self.step():
            pass
        self.emit_serving_record()

    @property
    def decode_compiles(self) -> int:
        """Number of compiled decode-step variants (the static-shape invariant: 1)."""
        return int(self._decode_step._cache_size())

    @property
    def verify_compiles(self) -> int:
        """Compiled verify-step variants — like the decode step, one per (K, width),
        i.e. exactly 1 for an engine's lifetime regardless of request churn."""
        return 0 if self._verify_step is None else int(self._verify_step._cache_size())

    @property
    def draft_compiles(self) -> int:
        """Compiled draft-model step variants (0 without a draft model, else 1)."""
        return 0 if self._draft is None else self._draft.draft_compiles

    @property
    def chunk_compiles(self) -> int:
        """Total compiled chunk-prefill variants across all (width, samples) buckets —
        preempt/resume churn must not grow this once the buckets are warm."""
        return sum(int(fn._cache_size()) for fn in self._chunk_fns.values())

    # ---------------------------------------------------------- program signatures

    def _note_program(self, name: str, fn: Any, args: tuple) -> None:
        """Record a jitted program's example arg shapes at its first invocation (one
        dict lookup per call afterwards). Shapes are static for an engine's lifetime,
        so the recorded abstract args reproduce exactly the program that served."""
        if name in self._program_records:
            return
        # under a mesh keep where the placed arrays live (weights, pool); the per-call host
        # scalars are uncommitted and follow them, as they do in the real call
        sharded = self.mesh is not None
        self._program_records[name] = (
            fn,
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding if sharded and x.committed else None
                ),
                args,
            ),
        )

    def program_signatures(
        self, compile: bool = True, names: tuple[str, ...] | None = None
    ) -> dict[str, ProgramSignature]:
        """Perf signatures of every jitted program this engine has run (decode, verify,
        chunk-prefill and prefill buckets), re-lowered from the recorded example shapes
        under the engine's mesh scope — the one accessor `tools/perf_ledger.py` and the
        telemetry record read instead of per-program plumbing. Each signature carries
        its program's live compile count (`decode_compiles`-family parity is asserted
        in tests). ``compile=False`` skips XLA compilation (no ``memory`` section);
        ``names`` restricts capture to those programs (each capture re-compiles)."""
        out: dict[str, ProgramSignature] = {}
        with self._scope():
            for name, (fn, abstract_args) in sorted(self._program_records.items()):
                if names is not None and name not in names:
                    continue
                sig = capture_jit_signature(fn, abstract_args, name=name, compile=compile)
                sig.compiles = int(fn._cache_size())
                out[name] = sig
        return out

    def emit_program_signatures(self) -> None:
        """Write the ``program_signature`` telemetry record for this engine's programs
        (lowering-only signatures: cost/donation/HLO features, no extra compiles)."""
        telemetry = get_telemetry()
        if not isinstance(telemetry, Telemetry) or not self._program_records:
            return
        self._signatures_emitted = True
        emit_program_signature_record(
            telemetry, "serving_engine", self.program_signatures(compile=False)
        )

    # ------------------------------------------------------------------ dense internals

    def _admit(self) -> None:
        admit, dead = self.scheduler.admissible(self.pool.num_free)
        for state in dead:
            self._finish(state, RequestStatus.cancelled)
        for state in admit:
            self._prefill_into_slot(state)

    def _prefill_into_slot(self, state: RequestState) -> None:
        request = state.request
        slot = self.pool.allocate()
        assert slot is not None, "scheduler admitted beyond the free-slot count"
        prompt_len = len(request.prompt_ids)
        multiple = self.prefill_bucket_multiple
        bucket = min(-(-prompt_len // multiple) * multiple, self.pool.max_len)

        ids = np.full((1, bucket), self.pad_token_id, np.int32)
        ids[0, :prompt_len] = request.prompt_ids
        mask = np.zeros((1, bucket), np.int32)
        mask[0, :prompt_len] = 1

        do_sample, temperature, top_k, top_p = request.sampling.encoded()
        tr = state.trace
        if tr is not None:
            self._admit_t0 = None
            self._admit_victims = 0
            t_adm = self._trace_admitted(state)
            tr.open["prefill"] = tr.begin(
                "prefill", parent=tr.root, t0=t_adm, slot=slot, tokens=prompt_len, resume=False
            )
        t0 = time.perf_counter()
        prefill_fn = self._get_prefill_fn(bucket)
        prefill_args = (
            self._variables,
            jnp.asarray(ids),
            jnp.asarray(mask),
            jnp.asarray(prompt_len, jnp.int32),
            request.rng,
            jnp.asarray(do_sample),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            jnp.asarray(top_p, jnp.float32),
        )
        self._note_program(f"prefill[b={bucket}]", prefill_fn, prefill_args)
        token, carry, prefill_caches = prefill_fn(*prefill_args)
        self.pool.write_prefill(slot, prefill_caches, prompt_len)
        first_token = int(token)  # host fetch: forces completion, ends the TTFT clock
        self.stats.prefill_seconds += time.perf_counter() - t0
        self.stats.prefill_tokens += prompt_len
        self._count_admission(state, session_hit=False)
        get_telemetry().count("serving_prefill_tokens", prompt_len)

        state.slot = slot
        state.status = RequestStatus.running
        state.first_token_t = self.scheduler.clock()
        if state.ttft_s is not None:
            self.stats.ttft_s.append(state.ttft_s)
            self.stats.ttft_s_by_tier.setdefault(
                request.priority, QuantileSketch()
            ).append(state.ttft_s)
            get_telemetry().observe("serving/ttft_s", state.ttft_s)
        self._slot_states[slot] = state
        self._tokens[slot] = first_token
        self._rngs[slot] = np.array(carry)
        state.rng_steps = 1  # prefill consumed one split of request.rng
        self._do_sample[slot] = do_sample
        self._temperature[slot] = temperature
        self._top_k[slot] = top_k
        self._top_p[slot] = top_p

        if tr is not None:
            pf = tr.open.pop("prefill", None)
            if pf is not None:
                tr.end(pf, t1=state.first_token_t)
            if state.ttft_s is not None:
                tr.root.attrs["ttft_s"] = round(state.ttft_s, 6)
            self._trace_begin_decode(state, state.first_token_t)
        if self.speculating:
            self._spec_start(slot, request.prompt_ids)
        self._deliver(state, first_token)

    def _decode_once(self) -> None:
        t0 = time.perf_counter()
        active = list(self._slot_states.keys())
        decode_args = (
            self._variables,
            self.pool.caches,
            jnp.asarray(self._tokens),
            jnp.asarray(self.pool.lengths),
            jnp.asarray(self._rngs),
            jnp.asarray(self._do_sample),
            jnp.asarray(self._temperature),
            jnp.asarray(self._top_k),
            jnp.asarray(self._top_p),
        )
        self._note_program("decode", self._decode_step, decode_args)
        caches, next_tokens, new_rngs = self._decode_step(*decode_args)
        self.pool.caches = caches
        tokens = np.asarray(next_tokens)  # host fetch: the streaming sync point
        self._rngs = np.array(new_rngs)  # copy: slots mutate their key at admission
        self._step_count += 1
        self.stats.decode_steps += 1
        self.stats.decode_seconds += time.perf_counter() - t0
        self._emit_decoded(active, tokens)

    # ------------------------------------------------------------------ paged internals

    def _admit_paged(self) -> None:
        """Admit tier-then-FCFS while slot rows AND (possibly oversubscribed) pages are
        available. Worst-case pages (minus prefix-cache hits) are reserved up front;
        prefix-cache-only pages are evicted LRU to make room. When the head cannot fit
        and preemption is on, strictly-lower-tier running slots are evicted (swap or
        drop-and-recompute) until it does — a blocked head still blocks its own and
        lower tiers (no skip-ahead), but never a higher tier (per-tier queues)."""
        if self.prefix is not None:
            self.prefix.expire_sessions(self.scheduler.clock())
        while True:
            state = self.scheduler.pop_next()
            if state is None:
                return
            if self.scheduler.expired(state):
                self._finish(state, RequestStatus.cancelled)
                continue
            # tracing: the admission span covers pop -> installed, incl. the victim
            # eviction loop below; a blocked attempt records nothing (queue stays open)
            self._admit_t0 = (
                self.scheduler.clock() if state.trace is not None else None
            )
            self._admit_victims = 0
            if self._try_admit(state):
                continue
            # blocked: evict strictly-lower-tier victims, one at a time, until the head
            # fits or no such victim remains (then it waits at its tier's head)
            admitted = False
            while self.preemption != "off":
                victim = self._pick_victim(below_tier=state.request.priority)
                if victim is None:
                    break
                self._preempt(victim)
                self._admit_victims += 1
                if self._try_admit(state):
                    admitted = True
                    break
            if not admitted:
                self.scheduler.push_front(state)
                return

    def _try_admit(self, state: RequestState) -> bool:
        """One admission attempt: claim a slot, reserve pages, set up the prefill task
        (or restore a swapped-out victim). Rolls back and returns False when slot rows
        or pages are short — the caller decides between waiting and preempting."""
        if self.pool.num_free == 0:
            return False
        if state.resume is not None and state.resume.swapped:
            return self._try_restore_swapped(state)
        request = state.request
        resume = state.resume
        # drop-and-recompute resume: re-run prefill over the already-emitted prefix
        # (token budget and worst-case pages are unchanged — the sequence is the same)
        prefill_ids = (
            (request.prompt_ids + state.tokens)[: resume.resident]
            if resume is not None
            else request.prompt_ids
        )
        page_size = self.pool.page_size
        worst_pages = -(-(len(request.prompt_ids) + request.max_new_tokens) // page_size)
        if self.prefix is not None:
            match = self.prefix.match(prefill_ids)
        else:
            match = PrefixMatch(nodes=[], cow=None, cow_len=0, resume_pos=0)
        # attach the hit pages FIRST (refcount 2: index + slot) and pin the COW donor,
        # so the eviction pass below can never reclaim the pages we are about to use
        slot = self.pool.allocate()
        for i, node in enumerate(match.nodes):
            self.pool.attach_shared(slot, i, node.page)
        if match.cow is not None:
            self.pool.incref(match.cow.page)

        needed = worst_pages - len(match.nodes)
        shortfall = needed - self.pool.available_pages
        reclaimed = 0
        if shortfall > 0 and self.prefix is not None:
            reclaimed = self.prefix.evict(shortfall, self.pool)
        if needed > self.pool.available_pages:
            # not enough pages yet: roll back (free decrefs the attached hit pages)
            if match.cow is not None:
                self.pool.decref(match.cow.page)
            self.pool.free(slot)
            return False

        self.pool.reserve(slot, needed)
        if match.cow is not None:
            # copy-on-write at page granularity: the partially matching tail page is
            # device-copied into a private page; the miss suffix is recomputed over it
            dst = self._alloc_page_reclaiming(slot, len(match.nodes))
            self.pool.copy_page(match.cow.page, dst)
            self.pool.decref(match.cow.page)

        do_sample, temperature, top_k, top_p = request.sampling.encoded()
        state.slot = slot
        state.status = RequestStatus.running
        self._slot_states[slot] = state
        self._do_sample[slot] = do_sample
        self._temperature[slot] = temperature
        self._top_k[slot] = top_k
        self._top_p[slot] = top_p
        self._prefill_tasks[slot] = _PrefillTask(
            state=state,
            encoded=(do_sample, temperature, top_k, top_p),
            pos=match.resume_pos,
            prefill_ids=prefill_ids,
            resume=resume,
        )
        self._prefill_order.append(slot)

        hit = match.resume_pos
        self.stats.prefix_hit_tokens += hit
        self.stats.prefix_miss_tokens += len(prefill_ids) - hit
        if hit:
            get_telemetry().count("serving_prefix_hit_tokens", hit)
        get_telemetry().count("serving_prefix_miss_tokens", len(prefill_ids) - hit)
        if resume is None:
            self._count_admission(state, session_hit=hit > 0)
        tr = state.trace
        if tr is not None:
            now = self._trace_admitted(
                state,
                prefix_hit_tokens=hit,
                pages_reserved=needed,
                pages_reclaimed=reclaimed,
                resume=resume is not None,
            )
            tr.open["prefill"] = tr.begin(
                "prefill",
                parent=tr.phase_parent or tr.root,
                t0=now,
                slot=slot,
                tokens=len(prefill_ids) - hit,
                resume=resume is not None,
            )
        return True

    def _try_restore_swapped(self, state: RequestState) -> bool:
        """Re-admit a swap-preempted request: its page bytes come back from the host
        pool into freshly allocated private pages, decode state is reinstalled, and the
        request continues exactly where it stopped — no prefill, no resampling."""
        request = state.request
        resume = state.resume
        page_size = self.pool.page_size
        used = -(-resume.resident // page_size)
        worst_pages = -(-(len(request.prompt_ids) + request.max_new_tokens) // page_size)
        if worst_pages > self.pool.available_pages:
            shortfall = worst_pages - self.pool.available_pages
            if self.prefix is None or not self.prefix.evict(shortfall, self.pool):
                return False
            if worst_pages > self.pool.available_pages:
                return False
        # the `used` restored pages must exist PHYSICALLY right now (the rest of the
        # reservation materializes later, covered by reclamation-at-allocation)
        if self.pool.physical_free < used:
            if self.prefix is not None:
                self.prefix.evict(used - self.pool.physical_free, self.pool)
            if self.pool.physical_free < used:
                return False
        slot = self.pool.allocate()
        self.pool.reserve(slot, worst_pages)
        pages = [self.pool.alloc_page(slot, i) for i in range(used)]
        moved = self._swap.swap_in(request.request_id, pages)
        self.pool.lengths[slot] = resume.resident

        do_sample, temperature, top_k, top_p = request.sampling.encoded()
        state.slot = slot
        state.status = RequestStatus.running
        state.resume = None
        self._slot_states[slot] = state
        self._tokens[slot] = resume.next_token
        self._rngs[slot] = np.asarray(resume.rng)
        self._do_sample[slot] = do_sample
        self._temperature[slot] = temperature
        self._top_k[slot] = top_k
        self._top_p[slot] = top_p
        if self.speculating:
            self._spec_start(slot, request.prompt_ids + state.tokens)
        self.stats.pages_swapped_in += moved
        get_telemetry().count("serving_pages_swapped_in", moved)
        tr = state.trace
        if tr is not None:
            now = self._trace_admitted(
                state, pages_swapped_in=moved, pages_reserved=worst_pages, resume=True
            )
            park = tr.open.pop("preempt_park", None)
            if park is not None:
                tr.end(park, t1=now, pages_swapped_in=moved)
            tr.phase_parent = None
            self._trace_begin_decode(state, now)
        return True

    def _count_admission(self, state: RequestState, session_hit: bool) -> None:
        """First-admission accounting (resumes don't re-count): admitted counters,
        per-tier breakdown, and session touch/hit tracking."""
        request = state.request
        tier = request.priority
        self.stats.admitted += 1
        self.stats.admitted_by_tier[tier] = self.stats.admitted_by_tier.get(tier, 0) + 1
        get_telemetry().count("serving_requests_admitted")
        if request.session_id is not None and self.prefix is not None:
            live = self.prefix.touch_session(
                request.session_id, self.scheduler.clock(), self.session_ttl_s
            )
            if live and session_hit:
                self.stats.session_hits += 1
                get_telemetry().count("serving_session_hits")

    # ------------------------------------------------------------------ tracing

    def _trace_admitted(self, state: RequestState, **attrs) -> float:
        """Close the open queue segment and record the admission span (pop -> now,
        incl. the victim-eviction loop). Returns the admission end timestamp so the
        caller starts the next phase exactly where admission ended — contiguous phases
        are what make the critical-path TTFT sum close (utils/tracing.critical_path)."""
        tr = state.trace
        now = self.scheduler.clock()
        t_pop = self._admit_t0 if self._admit_t0 is not None else now
        queue = tr.open.pop("queue_wait", None)
        if queue is not None:
            tr.end(queue, t1=t_pop)
        adm = tr.begin(
            "admission",
            parent=tr.phase_parent or tr.root,
            t0=t_pop,
            tier=state.request.priority,
            victims_evicted=self._admit_victims,
            **attrs,
        )
        tr.end(adm, t1=now)
        return now

    def _trace_begin_decode(self, state: RequestState, t0: float) -> None:
        """Open a decode-phase span for one residency segment; `_emit_decoded` /
        `_emit_verified` aggregate per-token segments into its tokens/steps attrs
        (mean ITL = duration / tokens)."""
        tr = state.trace
        tr.open["decode"] = tr.begin(
            "decode",
            parent=tr.root,
            t0=t0,
            slot=state.slot,
            segment=state.preemptions,
            replica_id=self.replica_id,
            tokens=0,
            steps=0,
        )

    # --------------------------------------------------------------- preemption

    def _pick_victim(
        self, below_tier: int | None = None, exclude: set[int] | None = None
    ) -> RequestState | None:
        """The next slot to evict: lowest priority first (highest tier number), most
        recent arrival within a tier (LIFO — the request with the least sunk service).
        `below_tier` restricts to strictly lower tiers than the beneficiary (admission
        preemption never evicts its own tier); `exclude` protects slots mid-allocation.
        Parked handoffs are never victims (their pages belong to an in-flight transfer).
        """
        parked = {state.slot for state in self._ready_handoffs}
        candidates = [
            state
            for slot, state in self._slot_states.items()
            if slot not in (exclude or ())
            and slot not in parked
            and (below_tier is None or state.request.priority > below_tier)
        ]
        if not candidates:
            return None
        return max(candidates, key=lambda state: (state.request.priority, state.seq))

    def _preempt(self, state: RequestState) -> None:
        """Evict a running slot and re-enqueue its request at its stable FCFS position.
        Swap mode parks the page bytes host-side (byte-identical restore); recompute
        mode registers the pages in the prefix cache (usually a free re-attach on
        resume, a cheap chunked recompute if evicted meanwhile) and releases them. A
        slot still mid-prefill just restarts its prefill — no decode state exists yet."""
        slot = state.slot
        assert slot is not None and self._slot_states.get(slot) is state
        t_evict = self.scheduler.clock() if state.trace is not None else None
        task = self._prefill_tasks.pop(slot, None)
        if slot in self._prefill_order:
            self._prefill_order.remove(slot)
        if self.speculating:
            self._spec_stop(slot)
        if task is not None:
            # mid-prefill: keep what the chunks already computed by indexing the full
            # pages below the progress frontier, then restart the (cheap, prefix-hit)
            # prefill from scratch; decode state was never installed
            if self.prefix is not None and task.pos >= self.pool.page_size:
                full = (task.pos // self.pool.page_size) * self.pool.page_size
                self.prefix.register(
                    task.prefill_ids[:full],
                    [int(p) for p in self.pool.page_table[slot]],
                    self.pool,
                )
            state.resume = task.resume  # a preempted resume stays a resume
        else:
            resident = int(self.pool.lengths[slot])
            if self.preemption == "swap":
                used = -(-resident // self.pool.page_size)
                pages = [int(p) for p in self.pool.page_table[slot, :used]]
                moved = self._swap.swap_out(state.request.request_id, pages)
                self.stats.pages_swapped_out += moved
                get_telemetry().count("serving_pages_swapped_out", moved)
                swapped = True
            else:
                if self.prefix is not None:
                    self._register_prefix(state, slot)
                swapped = False
            state.resume = _ResumeState(
                next_token=int(self._tokens[slot]),
                rng=self._rngs[slot].copy(),
                resident=resident,
                swapped=swapped,
            )
        self.pool.free(slot)
        del self._slot_states[slot]
        state.slot = None
        state.status = RequestStatus.waiting
        state.preemptions += 1
        tier = state.request.priority
        self.stats.preemptions += 1
        self.stats.preempted_by_tier[tier] = self.stats.preempted_by_tier.get(tier, 0) + 1
        get_telemetry().count("serving_preemptions")
        tr = state.trace
        if tr is not None:
            # close the interrupted residency, open the park span, and nest the
            # re-enqueue's queue segment under it — the resume's admission/prefill
            # spans re-parent under the park too (tr.phase_parent) until it ends
            for name in ("prefill", "decode"):
                span = tr.open.pop(name, None)
                if span is not None:
                    tr.end(span, t1=t_evict, preempted=True)
            resume = state.resume
            resident = (
                task.pos if task is not None
                else (resume.resident if resume is not None else 0)
            )
            park = tr.begin(
                "preempt_park",
                parent=tr.root,
                t0=t_evict,
                mode=self.preemption,
                mid_prefill=task is not None,
                resident=resident,
            )
            if resume is not None and resume.swapped:
                pages_out = -(-resume.resident // self.pool.page_size)
                park.attrs["pages_swapped_out"] = pages_out
                park.attrs["swap_bytes"] = int(round(pages_out * self.pool.page_bytes))
            tr.open["preempt_park"] = park
            tr.phase_parent = park
            tr.open["queue_wait"] = tr.begin(
                "queue_wait",
                parent=park,
                t0=self.scheduler.clock(),
                tier=tier,
                segment=state.preemptions,
            )
        self.scheduler.push_front(state)

    def _alloc_page_reclaiming(self, slot: int, index: int) -> int:
        """`alloc_page` that survives an oversubscribed pool running physically dry:
        reclaim (prefix-LRU eviction, then preemption, then pinned-session eviction as
        the last resort) until a page is actually free, then map it."""
        if self.pool.physical_free == 0:
            self._reclaim_physical(1, protect=slot)
        return self.pool.alloc_page(slot, index)

    def _reclaim_physical(self, need: int, protect: int | None) -> None:
        """Free at least `need` physical pages: evict unpinned prefix-cache leaves,
        preempt the lowest-priority victim (whose recompute-registered pages become
        evictable in turn), and only as a last resort evict session-pinned pages. The
        `protect` slot (the one being allocated for) is never preempted, so the oldest
        highest-priority request always makes progress and the loop terminates."""
        while self.pool.physical_free < need:
            if self.prefix is not None:
                self.prefix.evict(need - self.pool.physical_free, self.pool)
                if self.pool.physical_free >= need:
                    return
            victim = None
            if self.preemption != "off":
                victim = self._pick_victim(exclude={protect} if protect is not None else None)
            if victim is not None:
                self._preempt(victim)
                continue
            if self.prefix is not None and self.prefix.evict(
                need - self.pool.physical_free, self.pool, include_pinned=True
            ):
                continue
            raise RuntimeError(
                f"cannot reclaim {need} KV page(s): no evictable prefix pages and no "
                f"preemptable slots (preemption={self.preemption!r})"
            )

    def _prefill_priority_key(self, slot: int, now: float):
        """Chunked-prefill budget order: tier first, then TTFT-SLO headroom (least
        first — a tier with a target spends its budget where it is closest to missing),
        then FCFS. Tiers without a target order purely tier-then-FCFS."""
        state = self._prefill_tasks[slot].state
        headroom = self.scheduler.ttft_headroom(state, now)
        return (
            state.request.priority,
            float("inf") if headroom is None else headroom,
            state.seq,
        )

    def _run_prefill_chunks(self, budget: int | None = None) -> None:
        """Advance in-flight prefills in tier-then-SLO-headroom-then-FCFS order,
        spending at most `budget` REAL prefix tokens this step (default: the
        scheduler's `prefill_chunk_tokens`; the engine step passes
        `Scheduler.prefill_budget`, which nets out decode's verified tokens) — decode
        for already-running slots resumes right after, so their ITL stays bounded no
        matter how long the arriving prompt is."""
        if budget is None:
            budget = self.scheduler.prefill_chunk_tokens
        page_size = self.pool.page_size
        view_len = self.pool.max_pages_per_slot * page_size
        while budget > 0 and self._prefill_order:
            now = self.scheduler.clock()
            slot = min(self._prefill_order, key=lambda s: self._prefill_priority_key(s, now))
            task = self._prefill_tasks[slot]
            state = task.state
            prefill_ids = task.prefill_ids
            prefill_len = len(prefill_ids)
            take = min(prefill_len - task.pos, budget)
            final = task.pos + take == prefill_len
            # a resume's final chunk only recomputes K/V — decode state is restored
            # from the preemption context, never resampled
            samples = final and task.resume is None
            multiple = self.prefill_bucket_multiple
            width = -(-take // multiple) * multiple

            # map fresh pages under the chunk's real positions before the device write
            # (reclaiming first if the oversubscribed pool ran physically dry)
            pages_mapped = 0
            for index in range(task.pos // page_size, (task.pos + take - 1) // page_size + 1):
                if self.pool.page_table[slot, index] == TRASH_PAGE:
                    self._alloc_page_reclaiming(slot, index)
                    pages_mapped += 1
            if self._slot_states.get(slot) is not state:
                continue  # reclamation preempted this very task; re-pick
            tr = state.trace
            chunk_span = None
            if tr is not None:
                chunk_span = tr.begin(
                    "prefill_chunk",
                    parent=tr.open.get("prefill"),
                    t0=self.scheduler.clock(),
                    tokens=take,
                    width=width,
                    pages_written=pages_mapped,
                    backend=self._prefill_backend,
                    final=final,
                )

            ids = np.full((1, width), self.pad_token_id, np.int32)
            ids[0, :take] = prefill_ids[task.pos : task.pos + take]
            mask = np.zeros((1, view_len), np.int32)
            mask[0, : task.pos + take] = 1  # resident prefix + this chunk's real tokens

            do_sample, temperature, top_k, top_p = task.encoded
            t0 = time.perf_counter()
            chunk_fn = self._get_chunk_fn(width, samples)
            chunk_args = (
                self._variables,
                self.pool.caches,
                jnp.asarray(self.pool.page_table[slot : slot + 1]),
                jnp.asarray(ids),
                jnp.asarray(mask),
                jnp.asarray(task.pos, jnp.int32),
                jnp.asarray(take, jnp.int32),
                state.request.rng,
                jnp.asarray(do_sample),
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(top_k, jnp.int32),
                jnp.asarray(top_p, jnp.float32),
            )
            self._note_program(
                f"chunk[w={width},final={bool(samples)}]", chunk_fn, chunk_args
            )
            result = chunk_fn(*chunk_args)
            if samples:
                self.pool.caches, token, carry = result
                first_token = int(token)  # host fetch: ends the TTFT clock
            else:
                self.pool.caches = result
                jax.block_until_ready(self.pool.caches[0]["k"])
            self.stats.prefill_seconds += time.perf_counter() - t0
            self.stats.prefill_tokens += take
            get_telemetry().count("serving_prefill_tokens", take)
            task.pos += take
            budget -= take
            if chunk_span is not None:
                tr.end(chunk_span)

            if not final:
                continue
            self.pool.lengths[slot] = prefill_len
            self._prefill_order.remove(slot)
            del self._prefill_tasks[slot]
            if task.resume is not None:
                # recompute-resume complete: reinstall the captured decode context —
                # same next token, same rng carry — and continue token-for-token
                self._tokens[slot] = task.resume.next_token
                self._rngs[slot] = np.asarray(task.resume.rng)
                state.resume = None
                if self.speculating:
                    self._spec_start(slot, state.request.prompt_ids + state.tokens)
                if tr is not None:
                    # recompute-resume complete: the park span ends here and decode
                    # re-opens as a fresh top-level residency segment
                    now = self.scheduler.clock()
                    pf = tr.open.pop("prefill", None)
                    if pf is not None:
                        tr.end(pf, t1=now)
                    park = tr.open.pop("preempt_park", None)
                    if park is not None:
                        tr.end(park, t1=now)
                    tr.phase_parent = None
                    self._trace_begin_decode(state, now)
                continue
            state.first_token_t = self.scheduler.clock()
            if state.ttft_s is not None:
                self.stats.ttft_s.append(state.ttft_s)
                tier = state.request.priority
                self.stats.ttft_s_by_tier.setdefault(tier, QuantileSketch()).append(
                    state.ttft_s
                )
                get_telemetry().observe("serving/ttft_s", state.ttft_s)
            self._tokens[slot] = first_token
            self._rngs[slot] = np.array(carry)
            state.rng_steps = 1  # the sampling chunk consumed one split of request.rng
            if self.speculating:
                self._spec_start(slot, prefill_ids)
            if tr is not None:
                # prefill phase ends exactly at the measured first token, so the
                # critical-path sum closes against the recorded ttft_s. A request that
                # was preempted MID-prefill re-prefilled under its park span — the park
                # (whose child this phase was) also ends here, keeping the top-level
                # phases contiguous across the eviction
                pf = tr.open.pop("prefill", None)
                if pf is not None:
                    tr.end(pf, t1=state.first_token_t)
                park = tr.open.pop("preempt_park", None)
                if park is not None:
                    tr.end(park, t1=state.first_token_t)
                tr.phase_parent = None
                if state.ttft_s is not None:
                    tr.root.attrs["ttft_s"] = round(state.ttft_s, 6)
                if not self.prefill_only:
                    self._trace_begin_decode(state, state.first_token_t)
            self._deliver(state, first_token)
            if self.prefill_only and not state.done:
                # park for handoff: the slot (and its pages) stays resident until a
                # DecodeWorker adopts the KV and `release_handoff` frees it
                self._ready_handoffs.append(state)
                if tr is not None:
                    tr.open["handoff"] = tr.begin(
                        "handoff",
                        parent=tr.root,
                        t0=state.first_token_t,
                        src_replica=self.replica_id,
                    )

    def _decode_once_paged(self) -> None:
        page_size = self.pool.page_size
        # map the page under each decoding row's write position first: under
        # oversubscription this can preempt a (lower-priority) decoding slot to
        # reclaim pages, so membership is re-checked and the views are built after
        for slot in [s for s in self._slot_states if s not in self._prefill_tasks]:
            state = self._slot_states.get(slot)
            if state is None or slot in self._prefill_tasks:
                continue  # preempted (or re-admitted into prefill) by reclamation
            index = int(self.pool.lengths[slot]) // page_size
            if self.pool.page_table[slot, index] == TRASH_PAGE:
                self._alloc_page_reclaiming(slot, index)
        decoding = [s for s in self._slot_states if s not in self._prefill_tasks]
        if not decoding:
            return
        # per-step table/length views: idle and mid-prefill rows are zeroed so their
        # garbage write lands in the trash page instead of live pages
        table = np.zeros_like(self.pool.page_table)
        lengths = np.zeros(self.pool.num_slots, np.int32)
        for slot in decoding:
            table[slot] = self.pool.page_table[slot]
            lengths[slot] = int(self.pool.lengths[slot])

        t0 = time.perf_counter()
        decode_args = (
            self._variables,
            self.pool.caches,
            jnp.asarray(table),
            jnp.asarray(self._tokens),
            jnp.asarray(lengths),
            jnp.asarray(self._rngs),
            jnp.asarray(self._do_sample),
            jnp.asarray(self._temperature),
            jnp.asarray(self._top_k),
            jnp.asarray(self._top_p),
        )
        self._note_program("decode", self._decode_step, decode_args)
        caches, next_tokens, new_rngs = self._decode_step(*decode_args)
        self.pool.caches = caches
        tokens = np.asarray(next_tokens)  # host fetch: the streaming sync point
        self._rngs = np.array(new_rngs)
        self._step_count += 1
        self.stats.decode_steps += 1
        self.stats.decode_seconds += time.perf_counter() - t0
        self._emit_decoded(decoding, tokens)

    # ------------------------------------------------------------------ speculation

    def _spec_start(self, slot: int, prompt_ids: list[int]) -> None:
        if self._ngram is not None:
            self._ngram.start(slot, prompt_ids)
        if self._draft is not None:
            self._draft.start(slot, prompt_ids)

    def _spec_stop(self, slot: int) -> None:
        if self._ngram is not None:
            self._ngram.stop(slot)
        if self._draft is not None:
            self._draft.stop(slot)

    def _collect_drafts(self, decoding: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Gather up to K draft tokens per decoding slot from the configured source.
        Returns (drafts [num_slots, K], num_drafts [num_slots]); a slot with 0 drafts
        (no n-gram match, idle, mid-prefill) degrades to plain decode inside the same
        verify step."""
        k = self.draft_k
        num = self.pool.num_slots
        drafts = np.zeros((num, k), np.int32)
        counts = np.zeros(num, np.int32)
        if self._draft is not None:
            # one jitted draft call for all slots: ingest the tokens committed since the
            # drafter last saw each slot (<= K+1 of them: accepted + bonus), draft K
            windows = np.full((num, k + 1), self.pad_token_id, np.int32)
            ingest = np.zeros(num, np.int32)
            for slot in decoding:
                state = self._slot_states[slot]
                committed = state.request.prompt_ids + state.tokens
                fresh = committed[int(self._draft.seen[slot]) :]
                assert len(fresh) <= k + 1, (len(fresh), k)
                windows[slot, : len(fresh)] = fresh
                ingest[slot] = len(fresh)
            proposed = self._draft.propose(windows, ingest)
            for slot in decoding:
                drafts[slot] = proposed[slot]
                counts[slot] = k
        elif self._ngram is not None:
            for slot in decoding:
                proposal = self._ngram.propose(slot)
                drafts[slot, : len(proposal)] = proposal
                counts[slot] = len(proposal)
        return drafts, counts

    def _verify_once_paged(self) -> None:
        page_size = self.pool.page_size
        k = self.draft_k
        # map pages under each row's verify window first (reclaiming can preempt a
        # lower-priority row mid-pass, so membership is re-checked and views built after)
        for slot in [s for s in self._slot_states if s not in self._prefill_tasks]:
            state = self._slot_states.get(slot)
            if state is None or slot in self._prefill_tasks:
                continue  # preempted by reclamation
            position = int(self.pool.lengths[slot])
            # map pages under the verify window, capped at the request's worst-case
            # token count (what admission reserved for): the window overhang past it
            # scatters to trash — those drafts could never be committed anyway
            total = len(state.request.prompt_ids) + state.request.max_new_tokens
            last = min(position + k, total - 1)
            for index in range(position // page_size, last // page_size + 1):
                if self.pool.page_table[slot, index] == TRASH_PAGE:
                    self._alloc_page_reclaiming(slot, index)
        decoding = [s for s in self._slot_states if s not in self._prefill_tasks]
        if not decoding:
            return
        drafts, num_drafts = self._collect_drafts(decoding)
        table = np.zeros_like(self.pool.page_table)
        lengths = np.zeros(self.pool.num_slots, np.int32)
        for slot in decoding:
            table[slot] = self.pool.page_table[slot]
            lengths[slot] = int(self.pool.lengths[slot])

        tokens = np.zeros((self.pool.num_slots, k + 1), np.int32)
        tokens[:, 0] = self._tokens
        tokens[:, 1:] = drafts
        w0 = self.scheduler.clock()
        t0 = time.perf_counter()
        verify_args = (
            self._variables,
            self.pool.caches,
            jnp.asarray(table),
            jnp.asarray(tokens),
            jnp.asarray(lengths),
            jnp.asarray(num_drafts),
            jnp.asarray(self._rngs),
            jnp.asarray(self._do_sample),
            jnp.asarray(self._temperature),
            jnp.asarray(self._top_k),
            jnp.asarray(self._top_p),
        )
        self._note_program("verify", self._verify_step, verify_args)
        caches, accepted, bonus, new_rngs = self._verify_step(*verify_args)
        self.pool.caches = caches
        accepted = np.asarray(accepted)  # host fetch: the streaming sync point
        bonus = np.asarray(bonus)
        self._rngs = np.array(new_rngs)
        self._step_count += 1
        self.stats.decode_steps += 1
        self.stats.decode_seconds += time.perf_counter() - t0
        self._emit_verified(
            decoding, drafts, num_drafts, accepted, bonus, w0, self.scheduler.clock()
        )

    def _verify_once_dense(self) -> None:
        decoding = list(self._slot_states.keys())
        k = self.draft_k
        drafts, num_drafts = self._collect_drafts(decoding)
        tokens = np.zeros((self.pool.num_slots, k + 1), np.int32)
        tokens[:, 0] = self._tokens
        tokens[:, 1:] = drafts
        w0 = self.scheduler.clock()
        t0 = time.perf_counter()
        verify_args = (
            self._variables,
            self.pool.caches,
            jnp.asarray(tokens),
            jnp.asarray(self.pool.lengths),
            jnp.asarray(num_drafts),
            jnp.asarray(self._rngs),
            jnp.asarray(self._do_sample),
            jnp.asarray(self._temperature),
            jnp.asarray(self._top_k),
            jnp.asarray(self._top_p),
        )
        self._note_program("verify", self._verify_step, verify_args)
        caches, accepted, bonus, new_rngs = self._verify_step(*verify_args)
        self.pool.caches = caches
        accepted = np.asarray(accepted)
        bonus = np.asarray(bonus)
        self._rngs = np.array(new_rngs)
        self._step_count += 1
        self.stats.decode_steps += 1
        self.stats.decode_seconds += time.perf_counter() - t0
        self._emit_verified(
            decoding, drafts, num_drafts, accepted, bonus, w0, self.scheduler.clock()
        )

    def _emit_verified(
        self,
        decoding: list[int],
        drafts: np.ndarray,
        num_drafts: np.ndarray,
        accepted: np.ndarray,
        bonus: np.ndarray,
        window_t0: float | None = None,
        window_t1: float | None = None,
    ) -> None:
        """Commit a verify step's outcome per slot: deliver the accepted drafts in
        order, then the bonus token, honoring EOS/budget mid-window (tokens after a
        finishing token are DISCARDED — the stream matches non-speculative decode
        exactly). The cache frontier advances past the fed token plus the accepted
        drafts actually delivered; the bonus token's K/V is not written yet (it is the
        next step's fed token), and rejected-tail writes stay masked behind the
        frontier until the next window overwrites them."""
        emitted_total = proposed_total = accepted_total = 0
        for slot in decoding:
            state = self._slot_states.get(slot)
            if state is None:
                continue
            proposals = int(num_drafts[slot])
            acc = min(int(accepted[slot]), proposals)
            proposed_total += proposals
            accepted_total += acc
            plan = [int(drafts[slot, i]) for i in range(acc)] + [int(bonus[slot])]
            eos = state.request.eos_token_id
            budget = state.request.max_new_tokens - state.num_generated
            emit: list[int] = []
            for token in plan:
                emit.append(token)
                if (eos is not None and token == eos) or len(emit) >= budget:
                    break
            self.pool.lengths[slot] += 1 + min(len(emit), acc)
            self._tokens[slot] = emit[-1]
            state.rng_steps += 1  # one verify step = one split of the slot's rng row
            emitted_total += len(emit)
            tr = state.trace
            if tr is not None:
                span = tr.open.get("decode")
                if span is not None:
                    span.attrs["tokens"] += len(emit)
                    span.attrs["steps"] += 1
                    if proposals and window_t0 is not None:
                        window = tr.begin(
                            "verify_window",
                            parent=span,
                            t0=window_t0,
                            proposed=proposals,
                            accepted=acc,
                        )
                        tr.end(window, t1=window_t1)
            for token in emit:
                self._deliver(state, token)
                if state.done:
                    break
        self.stats.decode_tokens += emitted_total
        self.stats.draft_tokens_proposed += proposed_total
        self.stats.draft_tokens_accepted += accepted_total
        if emitted_total:
            get_telemetry().count("serving_decode_tokens", emitted_total)
        if proposed_total:
            get_telemetry().count("serving_draft_tokens_proposed", proposed_total)
        if accepted_total:
            get_telemetry().count("serving_draft_tokens_accepted", accepted_total)

    # ------------------------------------------------------------------ shared internals

    def _emit_decoded(self, active: list[int], tokens: np.ndarray) -> None:
        emitted = 0
        for slot in active:
            state = self._slot_states.get(slot)
            if state is None:
                continue
            # the token fed this step is now in the cache; the slot's frontier advances
            self.pool.lengths[slot] += 1
            token = int(tokens[slot])
            self._tokens[slot] = token
            state.rng_steps += 1  # this step split the slot's rng row once
            emitted += 1
            if state.trace is not None:
                span = state.trace.open.get("decode")
                if span is not None:  # per-token segments aggregate into the ITL span
                    span.attrs["tokens"] += 1
                    span.attrs["steps"] += 1
            self._deliver(state, token)
        self.stats.decode_tokens += emitted
        if emitted:
            get_telemetry().count("serving_decode_tokens", emitted)

    def _deliver(self, state: RequestState, token: int) -> None:
        """Stream one token and apply the per-request termination rules (EOS counts as an
        emitted token, matching `generation_utils._trim_after_eos` semantics)."""
        state.tokens.append(token)
        if self._ngram is not None and state.slot is not None:
            self._ngram.extend(state.slot, token)  # emitted tokens feed future lookups
        if state.request.on_token is not None:
            state.request.on_token(token)
        eos = state.request.eos_token_id
        if (eos is not None and token == eos) or (
            state.num_generated >= state.request.max_new_tokens
        ):
            self._finish(state, RequestStatus.completed)

    def _cancel_expired_running(self) -> None:
        for state in [s for s in self._slot_states.values() if self.scheduler.expired(s)]:
            self._finish(state, RequestStatus.cancelled)

    def _finish(self, state: RequestState, status: RequestStatus) -> None:
        state.status = status
        state.finish_t = self.scheduler.clock()
        if self._swap is not None:
            self._swap.drop(state.request.request_id)  # finished while swapped out
        if self._ready_handoffs:
            self._ready_handoffs = [s for s in self._ready_handoffs if s is not state]
        if state.slot is not None:
            slot = state.slot
            self._prefill_tasks.pop(slot, None)
            if slot in self._prefill_order:
                self._prefill_order.remove(slot)
            if self.prefix is not None:
                self._register_prefix(state, slot)
            if self.speculating:
                self._spec_stop(slot)
            self.pool.free(slot)
            del self._slot_states[slot]
        tier = state.request.priority
        if status == RequestStatus.completed:
            self.stats.completed += 1
            self.stats.completed_by_tier[tier] = (
                self.stats.completed_by_tier.get(tier, 0) + 1
            )
            get_telemetry().count("serving_requests_completed")
        else:
            self.stats.cancelled += 1
            get_telemetry().count("serving_requests_cancelled")
        if state.first_token_t is not None and state.num_generated > 1:
            itl = (state.finish_t - state.first_token_t) / (state.num_generated - 1)
            self.stats.itl_s_by_tier.setdefault(tier, QuantileSketch()).append(itl)
            get_telemetry().observe("serving/itl_s", itl)
        tr = state.trace
        if tr is not None:
            # close whatever phase the request died in, then the root, and emit the
            # whole tree as ONE trace record (the finishing engine owns emission — for
            # a disaggregated request that is the decode worker, so both workers'
            # spans land in the same record)
            for name in ("queue_wait", "prefill", "decode", "handoff", "preempt_park"):
                span = tr.open.pop(name, None)
                if span is not None:
                    tr.end(span, t1=state.finish_t)
            tr.end(
                tr.root,
                t1=state.finish_t,
                status=str(status),
                generated_tokens=state.num_generated,
                preemptions=state.preemptions,
            )
            get_telemetry().emit_record(
                "trace",
                step=self._step_count,
                trace_id=tr.trace_id,
                request_id=state.request.request_id,
                spans=tr.span_records(),
            )
        if state.request.on_finish is not None:
            state.request.on_finish(state)

    def _register_prefix(self, state: RequestState, slot: int) -> None:
        """Index the slot's full pages before they are released: generated tokens are
        registered too, so a multi-turn follow-up whose prompt embeds this reply hits.
        A request with a session id additionally pins the chain until the session's TTL
        lapses — the conversation's next turn re-attaches even under LRU pressure."""
        written = int(self.pool.lengths[slot])
        if written <= 0:
            return  # cancelled mid-prefill: nothing committed
        prompt = state.request.prompt_ids
        resident = (prompt + state.tokens[: written - len(prompt)])[:written]
        self.prefix.register(
            resident, [int(p) for p in self.pool.page_table[slot]], self.pool
        )
        if state.request.session_id is not None:
            self.prefix.pin_session(
                state.request.session_id, resident, self.scheduler.clock(), self.session_ttl_s
            )

    # ------------------------------------------------------ disaggregation (cluster/)

    def prefix_match_len(self, prompt_ids: list[int]) -> int:
        """Resident-prefix tokens this engine could reuse for `prompt_ids` — the
        router's affinity probe. Side-effect free (no LRU promotion); 0 when prefix
        caching is off."""
        return 0 if self.prefix is None else self.prefix.probe_len(prompt_ids)

    @property
    def pending_handoffs(self) -> int:
        """Finished prefills parked for adoption (prefill_only mode; else 0)."""
        return len(self._ready_handoffs)

    def take_ready_handoffs(self) -> list[RequestState]:
        """Pop every parked finished prefill (FCFS order). prefill_only mode only; the
        caller must `handoff_payload` + transfer + `release_handoff` each one (or
        re-park via `park_handoff` when no DecodeWorker has capacity)."""
        ready, self._ready_handoffs = self._ready_handoffs, []
        return ready

    def park_handoff(self, state: RequestState) -> None:
        """Return an un-placeable handoff to the FRONT of the parked queue (FCFS)."""
        self._ready_handoffs.insert(0, state)

    def handoff_payload(self, state: RequestState) -> tuple[int, np.ndarray, int, list[int]]:
        """Host-side handoff bundle for a parked prefill: (first_token, rng_carry,
        resident_length, physical source pages in chain order). The pages stay alive —
        and their K/V unchanged — until `release_handoff`."""
        slot = state.slot
        assert slot is not None, "handoff payload for a request without a slot"
        length = int(self.pool.lengths[slot])
        used = -(-length // self.pool.page_size)
        pages = [int(p) for p in self.pool.page_table[slot, :used]]
        assert TRASH_PAGE not in pages, "handoff of an unmapped prefix page"
        return int(self._tokens[slot]), self._rngs[slot].copy(), length, pages

    def release_handoff(self, state: RequestState, slot: int) -> None:
        """Free a handed-off request's source `slot` WITHOUT finishing the request: its
        prefix pages are registered in the local prefix index first (future arrivals
        with the same prompt skip prefill here — which is what makes prefill affinity
        work), then the slot and its remaining reservation return to the pool. The slot
        is passed explicitly because `adopt_prefilled` on the decode side has already
        repointed ``state.slot`` at the destination."""
        assert self._slot_states.get(slot) is state, "release of a slot the state does not hold"
        if self.prefix is not None:
            self._register_prefix(state, slot)
        self.pool.free(slot)
        del self._slot_states[slot]

    def adopt_prefilled(self, state: RequestState, *, first_token: int, rng_carry, length: int) -> list[int] | None:
        """Admit a request whose prefill ran on another engine (the DecodeWorker side of
        disaggregation). Reserves the request's remaining worst-case pages, maps `used`
        fresh private pages for the transferred prefix, and installs the decode-loop
        state exactly as a local final prefill chunk would have — so decode from here is
        token-for-token identical to the monolithic engine. Returns the destination
        physical pages (chain order) for the KVHandoff to fill, or None when this
        worker lacks slot/page capacity (the caller keeps FCFS by re-parking)."""
        assert self.paged and not self.prefill_only
        request = state.request
        page_size = self.pool.page_size
        used = -(-length // page_size)
        worst = -(-(length + request.max_new_tokens) // page_size)
        if self.pool.num_free == 0:
            return None
        shortfall = worst - self.pool.available_pages
        if shortfall > 0 and self.prefix is not None:
            self.prefix.evict(shortfall, self.pool)
        if worst > self.pool.available_pages:
            return None
        slot = self.pool.allocate()
        self.pool.reserve(slot, worst)
        pages = [self.pool.alloc_page(slot, i) for i in range(used)]
        self.pool.lengths[slot] = length

        do_sample, temperature, top_k, top_p = request.sampling.encoded()
        state.slot = slot
        state.status = RequestStatus.running
        self._slot_states[slot] = state
        self._tokens[slot] = first_token
        self._rngs[slot] = np.asarray(rng_carry)
        self._do_sample[slot] = do_sample
        self._temperature[slot] = temperature
        self._top_k[slot] = top_k
        self._top_p[slot] = top_p
        if self.speculating:
            # the drafter's history must include tokens the prefill side already emitted
            self._spec_start(slot, request.prompt_ids + state.tokens)
        self.stats.admitted += 1
        get_telemetry().count("serving_requests_admitted")
        if state.trace is not None:
            # decode resumes on THIS worker; the handoff span (opened on the prefill
            # side) is closed by the disaggregation driver once the page transfer lands
            self._trace_begin_decode(state, self.scheduler.clock())
        return pages

    # -------------------------------------------------- crash migration (cluster/)

    def inflight_request_ids(self) -> list[int]:
        """Request ids this engine still owes tokens to (waiting + running), sorted —
        the router's drain-timeout diagnostics and wait() accounting."""
        ids = {state.request.request_id for state in self.scheduler.waiting}
        ids.update(state.request.request_id for state in self._slot_states.values())
        return sorted(ids)

    def release_inflight(self) -> list[RequestState]:
        """Strip EVERY unfinished request out of this engine and return them in
        (tier, FCFS seq) order for adoption elsewhere (`Router._recover_dead` /
        `Router.drain_replica`).

        Host-only bookkeeping by design: the engine may have just crashed mid-step, so
        its device state (KV pages, per-slot rows) is assumed corrupt — nothing is read
        from it and no prefix is registered. Each returned state is reset to a
        slot-less ``waiting`` request; `adopt_inflight` on the destination rebuilds the
        resume context from the host-side token log alone."""
        released = list(self.scheduler.waiting)
        while self.scheduler.pop_next() is not None:
            pass
        running = sorted(
            self._slot_states.items(), key=lambda kv: (kv[1].tier, kv[1].seq)
        )
        for slot, state in running:
            self._prefill_tasks.pop(slot, None)
            if slot in self._prefill_order:
                self._prefill_order.remove(slot)
            if self.speculating:
                self._spec_stop(slot)
            self.pool.free(slot)
            released.append(state)
        self._slot_states.clear()
        self._ready_handoffs = []
        for state in released:
            if self._swap is not None:
                self._swap.drop(state.request.request_id)
            state.slot = None
            state.status = RequestStatus.waiting
            state.resume = None  # rebuilt from the token log at adoption
        released.sort(key=lambda s: (s.tier, s.seq))
        return released

    def adopt_inflight(self, state: RequestState) -> None:
        """Admit a request released from ANOTHER replica (`release_inflight`), mid-
        generation or not. A request that already emitted tokens re-enters through the
        drop-and-recompute resume path: the resume context is rebuilt purely from host
        state — next token fed is the last emitted one, the resident prefix is
        ``(prompt + tokens)[:-1]`` (everything except that un-cache-written tail), and
        the rng carry is re-derived by replaying ``rng_steps`` splits of the request
        key — so chunked prefill recomputes the committed prefix (radix-cache hits
        welcome) and decode continues token-for-token as if the crash never happened.
        Raises QueueFullError when this engine's queue is at bound (the router's retry
        budget spills to the next candidate)."""
        if state.tokens and not self.paged:
            raise ValueError("adopting a mid-generation request requires a paged engine")
        if state.tokens:
            state.resume = _ResumeState(
                next_token=int(state.tokens[-1]),
                rng=_rederive_rng_carry(state.request.rng, state.rng_steps),
                resident=len(state.request.prompt_ids) + len(state.tokens) - 1,
                swapped=False,
            )
        else:
            state.resume = None
        self.scheduler.adopt(state)

    def swap_params(self, params) -> None:
        """Install a new parameter pytree (rolling weight update while parked by
        `Router.drain_replica`; the tree structure must match — compiled programs are
        reused, so the swap costs no recompilation)."""
        self._variables = {"params": params} if "params" not in params else params

    # ------------------------------------------------------------------ telemetry

    def emit_serving_record(self) -> None:
        """Write one ``serving`` telemetry record — instantaneous queue/slot/page state
        plus cumulative rates and counters (no-op sink when no telemetry is installed)."""
        telemetry = get_telemetry()
        stats = self.stats
        self._last_record_step = self._step_count
        if self.signature_records and not self._signatures_emitted and self._program_records:
            # engine-build self-report, once, lazily (programs trace on first use)
            self.emit_program_signatures()
        telemetry.gauge("serving/queue_depth", self.scheduler.queue_depth)
        telemetry.gauge("serving/slot_occupancy", self.pool.occupancy)
        kv_bytes = round(self.pool.kv_bytes_per_token, 2)
        telemetry.gauge("serving/kv_bytes_per_token", kv_bytes)
        pages_in_use = fragmentation = None
        if self.paged:
            pages_in_use = self.pool.pages_in_use
            fragmentation = round(self.pool.page_fragmentation, 4)
            telemetry.gauge("serving/pages_in_use", pages_in_use)
            telemetry.gauge("serving/page_fragmentation", fragmentation)
        accept_rate = accepted_per_step = None
        if self.speculating:
            rate = stats.accept_rate()
            accept_rate = 0.0 if rate is None else round(rate, 4)
            per_step = stats.accepted_tokens_per_step()
            accepted_per_step = 0.0 if per_step is None else round(per_step, 3)
            telemetry.gauge("serving/accept_rate", accept_rate)
            telemetry.gauge("serving/accepted_tokens_per_step", accepted_per_step)
        # contention breakdown: one entry per tier that has seen traffic or is waiting,
        # with the measured latencies next to their SLO targets
        depth_by_tier = self.scheduler.queue_depth_by_tier()
        tiers: dict[str, dict] = {}
        for tier in sorted(
            set(depth_by_tier)
            | set(stats.admitted_by_tier)
            | set(stats.ttft_s_by_tier)
            | set(self.scheduler.tier_slos)
        ):
            slo = self.scheduler.slo(tier)
            p99 = stats.ttft_p99_s(tier)
            itl = stats.itl_mean_s(tier)
            tiers[str(tier)] = {
                "queue_depth": depth_by_tier.get(tier, 0),
                "admitted": stats.admitted_by_tier.get(tier, 0),
                "completed": stats.completed_by_tier.get(tier, 0),
                "preempted": stats.preempted_by_tier.get(tier, 0),
                "ttft_p99_ms": None if p99 is None else round(p99 * 1e3, 3),
                "ttft_target_ms": (
                    None if slo.ttft_target_s is None else round(slo.ttft_target_s * 1e3, 3)
                ),
                "itl_mean_ms": None if itl is None else round(itl * 1e3, 3),
                "itl_target_ms": (
                    None if slo.itl_target_s is None else round(slo.itl_target_s * 1e3, 3)
                ),
            }
            telemetry.gauge(
                f"serving/priority_queue_depth/tier{tier}", depth_by_tier.get(tier, 0)
            )
            if p99 is not None:
                telemetry.gauge(f"serving/ttft_p99_ms/tier{tier}", round(p99 * 1e3, 3))
        ttft = stats.mean_ttft_s()
        prefill_rate = stats.prefill_tok_s()
        decode_rate = stats.decode_tok_s()
        telemetry.emit_record(
            "serving",
            step=self._step_count,
            replica_id=self.replica_id,
            queue_depth=self.scheduler.queue_depth,
            slots_active=self.pool.num_active,
            num_slots=self.pool.num_slots,
            pages_in_use=pages_in_use,
            pages_total=self.pool.num_pages - 1 if self.paged else None,
            page_fragmentation=fragmentation,
            kv_dtype=getattr(self.pool, "kv_dtype", None),
            kv_bytes_per_token=kv_bytes,
            ttft_ms=None if ttft is None else round(ttft * 1e3, 3),
            prefill_tok_s=None if prefill_rate is None else round(prefill_rate, 1),
            decode_tok_s=None if decode_rate is None else round(decode_rate, 1),
            accept_rate=accept_rate,
            accepted_tokens_per_step=accepted_per_step,
            preemptions=stats.preemptions,
            pages_swapped_out=stats.pages_swapped_out,
            pages_swapped_in=stats.pages_swapped_in,
            session_hits=stats.session_hits,
            sessions_live=0 if self.prefix is None else self.prefix.sessions_live,
            tiers=tiers,
            kernels=active_kernel_backends(),
            counters={
                "admitted": stats.admitted,
                "completed": stats.completed,
                "rejected": stats.rejected,
                "cancelled": stats.cancelled,
                "prefill_tokens": stats.prefill_tokens,
                "decode_tokens": stats.decode_tokens,
                "decode_steps": stats.decode_steps,
                "prefix_hit_tokens": stats.prefix_hit_tokens,
                "prefix_miss_tokens": stats.prefix_miss_tokens,
                "draft_tokens_proposed": stats.draft_tokens_proposed,
                "draft_tokens_accepted": stats.draft_tokens_accepted,
            },
        )


def serve_batch(engine: ServingEngine, request_specs: list[dict]) -> list[RequestState]:
    """Offline driver: feed every spec through the engine with queue backpressure and
    drain. Results come back in submission order regardless of completion order — this is
    what `generate.py` delegates to instead of its stall-on-slowest chunked loop."""
    from .scheduler import QueueFullError

    states: list[RequestState] = []
    i = 0
    while i < len(request_specs):
        try:
            states.append(engine.submit(**request_specs[i]))
            i += 1
        except QueueFullError:
            engine.step()  # make room: decode progresses, slots free, queue drains
    engine.drain()
    return states
