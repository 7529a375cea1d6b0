"""Compiled-program performance signatures: hardware-free perf facts for jitted programs.

A *signature* is the structured, platform-tagged summary of what XLA actually built for
one jitted program: ``cost_analysis`` flops / bytes accessed, ``memory_analysis``
temp/argument/output/alias bytes (the static buffer assignment — meaningful on CPU, where
wall-clock TPU claims are not), the donation map (how many inputs alias outputs), the
input/output sharding specs, and an HLO feature section — a top-K op histogram, the
largest value shape in the program, named shape presence checks (e.g. "the chunked-CE
grad program never materializes a ``[B,S,V]`` fp32 logits buffer"), and the Pallas
kernels the compiled program really contains (``tpu_kernels``, from its custom calls).

Three consumers share this one extraction path (no private ``memory_analysis()`` /
``cost_analysis()`` plumbing elsewhere):

- ``tools/perf_ledger.py`` captures a canonical program suite into ``PERF_LEDGER.json``
  and diffs the current tree against it with per-metric tolerances — the CPU-tier
  regression gate (docs/OBSERVABILITY.md "Perf ledger").
- ``tools/scaling_report.py`` / ``tools/doctor.py`` read their HBM/flops columns from
  signatures.
- ``ServingEngine.program_signatures()`` and the train loops' flagged capture self-report
  what compiled into the telemetry sink (``program_signature`` record kind).

Everything here is lowering/compilation introspection only — no program is ever executed.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Mapping, Sequence

import jax

# StableHLO / HLO element-type token -> bytes per element, for largest-buffer accounting
_DTYPE_BYTES: dict[str, int] = {
    "pred": 1,
    "i8": 1, "s8": 1, "ui8": 1, "u8": 1,
    "i16": 2, "s16": 2, "ui16": 2, "u16": 2,
    "i32": 4, "s32": 4, "ui32": 4, "u32": 4,
    "i64": 8, "s64": 8, "ui64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# Default per-metric drift tolerances for :func:`diff_signatures`, keyed by the flattened
# metric path. Values: a float = relative tolerance (|cur - base| <= tol * max(|base|, 1));
# 0.0 = exact; None = informational only, never gated. Unlisted numeric paths are exact.
# Rationale: flops/arg/output bytes are shape-determined (exact-ish across minor lowering
# drift, so flops gets 1%); temp bytes move with fusion decisions (2%); bytes-accessed is
# the noisiest cost model output (5%); donation, compile counts, and shape checks are the
# regressions this ledger exists to catch — exact.
DEFAULT_TOLERANCES: dict[str, float | None] = {
    "cost.flops": 0.01,
    "cost.bytes_accessed": 0.05,
    "memory.temp_size_in_bytes": 0.02,
    "memory.argument_size_in_bytes": 0.0,
    "memory.output_size_in_bytes": 0.0,
    "memory.alias_size_in_bytes": 0.0,
    # code size jitters with compiler version and is not a model-perf fact
    "memory.generated_code_size_in_bytes": None,
    "hlo.largest_buffer.bytes": 0.02,
    # the shape string rides along for attribution; the bytes gate covers regressions
    "hlo.largest_buffer.shape": None,
    "donation.donated_inputs": 0.0,
    "compiles": 0.0,
}


# --------------------------------------------------------------------- HLO features


def shape_tokens(dims: Sequence[int], dtype: str) -> tuple[str, str]:
    """The two spellings of one array shape: StableHLO (``2x64x199xf32`` inside
    ``tensor<...>``) and post-compile HLO (``f32[2,64,199]``; signed ints spell ``s32``
    there rather than StableHLO's ``i32``)."""
    dims = [int(d) for d in dims]
    stablehlo = "x".join([*map(str, dims), dtype])
    hlo_dtype = f"s{dtype[1:]}" if re.fullmatch(r"i\d+", dtype) else dtype
    hlo = f"{hlo_dtype}[{','.join(map(str, dims))}]"
    return stablehlo, hlo


def hlo_has_shape(text: str, dims: Sequence[int], dtype: str) -> bool:
    """Whether an array of exactly ``dims`` x ``dtype`` appears anywhere in the program
    text (either StableHLO or compiled-HLO spelling)."""
    stablehlo, hlo = shape_tokens(dims, dtype)
    return f"tensor<{stablehlo}>" in text or hlo in text or stablehlo in text


def hlo_op_histogram(text: str, top_k: int = 20) -> dict[str, int]:
    """Top-K op histogram of a StableHLO module text (``stablehlo.dot_general`` -> count).
    Ties broken by name so the result is deterministic."""
    counts: dict[str, int] = {}
    for match in re.finditer(r"\b(?:stablehlo|mhlo|chlo)\.([a-zA-Z_0-9]+)", text):
        op = match.group(1)
        if op in ("num_partitions", "num_replicas"):  # module attrs, not ops
            continue
        counts[op] = counts.get(op, 0) + 1
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    return dict(ranked[:top_k])


def hlo_largest_buffer(text: str) -> dict[str, Any] | None:
    """The largest value shape appearing in the program text, by byte size — the
    cheap, dump-free proxy for "largest live buffer" (a value that exists in the program
    is a buffer the schedule has to place somewhere)."""
    best_bytes = -1
    best_shape = None
    seen: set[str] = set()
    # StableHLO tensors and compiled-HLO shapes; scalars (no dims) are skipped
    for match in re.finditer(
        r"tensor<((?:\d+x)+)([a-z0-9]+)>|\b([a-z0-9]{2,8})\[([\d,]+)\]", text
    ):
        token = match.group(0)
        if token in seen:
            continue
        seen.add(token)
        if match.group(1) is not None:
            dims = [int(d) for d in match.group(1).rstrip("x").split("x")]
            dtype = match.group(2)
        else:
            dtype = match.group(3)
            dims = [int(d) for d in match.group(4).split(",")]
        elem = _DTYPE_BYTES.get(dtype)
        if elem is None:
            continue
        size = elem
        for d in dims:
            size *= d
        if size > best_bytes:
            best_bytes = size
            best_shape, _ = shape_tokens(dims, dtype)
    if best_shape is None:
        return None
    return {"shape": best_shape, "bytes": int(best_bytes)}


def hlo_tpu_kernels(compiled_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled TPU program: its ``tpu_custom_call`` instructions,
    counted by instruction name, which XLA takes from the innermost name scope around
    the ``pallas_call`` — this package's kernels sit in
    ``jax.named_scope("pallas_<family>")``, jax's splash kernels in a scope of their own
    kernel name. Empty off-TPU, where kernels run interpreted and leave no custom call:
    a family reported as ``pallas`` that is missing here did not lower as a kernel."""
    counts: dict[str, int] = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        match = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line)
        name = match.group(1) if match else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return dict(sorted(counts.items()))


def hlo_collectives(compiled_text: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """``[(collective, result dims, inside a while body?)]`` of a compiled program's text:
    its all-gathers, all-reduces, reduce-scatters and all-to-alls (async ``-start`` forms
    included; a tuple-shaped result reports its first member). The flag says whether the
    instruction sits in a computation some ``while`` names as its body — a collective a
    loop repeats every trip."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", compiled_text))
    found: list[tuple[str, tuple[int, ...], bool]] = []
    computation = None
    for line in compiled_text.splitlines():
        header = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            computation = header.group(1)
        op = re.search(
            r"= \(?\w+\[([\d,]*)\]\S* (all-gather|all-reduce|reduce-scatter|all-to-all)(?:-start)?\(",
            line,
        )
        if op:
            dims = tuple(int(d) for d in op.group(1).split(",") if d)
            found.append((op.group(2), dims, computation in bodies))
    return found


def _count_donated_inputs(lowered_text: str) -> int:
    """Donated inputs, from the lowering's argument attributes: ``tf.aliasing_output``
    marks an input aliased onto an output, ``jax.buffer_donor`` a donation the aliaser
    could not place. One marker per donated tree leaf."""
    return lowered_text.count("tf.aliasing_output") + lowered_text.count(
        "jax.buffer_donor"
    )


# --------------------------------------------------------------------- signature


@dataclasses.dataclass
class ProgramSignature:
    """One jitted program's compiled-perf facts (JSON-stable; see module docstring)."""

    name: str
    platform: str
    compiled: bool
    cost: dict[str, float]
    memory: dict[str, int]
    donation: dict[str, int]
    in_sharding_specs: list[str]
    out_sharding_specs: list[str]
    hlo: dict[str, Any]
    compiles: int | None = None

    def to_json(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, payload: Mapping[str, Any]) -> "ProgramSignature":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in payload.items() if k in fields})


def _normalize_cost(cost: Any) -> dict[str, float]:
    if not cost:
        return {}
    out: dict[str, float] = {}
    for key in ("flops", "bytes accessed", "transcendentals"):
        value = cost.get(key)
        if value:
            out[key.replace(" ", "_")] = float(value)
    return out


def _sharding_specs(shardings: Any) -> list[str]:
    """Sorted unique leaf sharding spec strings (a lost PartitionSpec is a drift)."""
    leaves = jax.tree.leaves(shardings)
    return sorted({str(s) for s in leaves})


def extract_signature(
    lowered: Any,
    compiled: Any = None,
    *,
    name: str,
    shape_checks: Mapping[str, tuple[Sequence[int], str]] | None = None,
    hlo_top_k: int = 20,
) -> ProgramSignature:
    """Build a signature from an already-lowered (and optionally compiled)
    ``jax.stages`` pair. ``shape_checks`` maps a check name to ``(dims, dtype)``; the
    stored boolean is "this exact shape appears in the lowered program"."""
    text = lowered.as_text()
    checks = {
        check: hlo_has_shape(text, dims, dtype)
        for check, (dims, dtype) in (shape_checks or {}).items()
    }
    hlo = {
        "op_histogram": hlo_op_histogram(text, top_k=hlo_top_k),
        "largest_buffer": hlo_largest_buffer(text),
        "checks": checks,
    }
    donation = {"donated_inputs": _count_donated_inputs(text)}

    cost: dict[str, float] = {}
    memory: dict[str, int] = {}
    in_specs: list[str] = []
    out_specs: list[str] = []
    if compiled is not None:
        hlo["tpu_kernels"] = hlo_tpu_kernels(compiled.as_text())
        try:
            cost = _normalize_cost(compiled.cost_analysis())
        except Exception:
            cost = {}
        try:
            analysis = compiled.memory_analysis()
        except Exception:
            analysis = None
        if analysis is not None:
            for field in (
                "temp_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                value = getattr(analysis, field, None)
                if value is not None:
                    memory[field] = int(value)
        try:
            in_specs = _sharding_specs(compiled.input_shardings)
            out_specs = _sharding_specs(compiled.output_shardings)
        except Exception:
            pass
    if not cost:
        try:
            cost = _normalize_cost(lowered.cost_analysis())
        except Exception:
            cost = {}
    return ProgramSignature(
        name=name,
        platform=jax.default_backend(),
        compiled=compiled is not None,
        cost=cost,
        memory=memory,
        donation=donation,
        in_sharding_specs=in_specs,
        out_sharding_specs=out_specs,
        hlo=hlo,
    )


def capture_jit_signature(
    fn: Any,
    args: Sequence[Any] = (),
    *,
    name: str,
    compile: bool = True,
    shape_checks: Mapping[str, tuple[Sequence[int], str]] | None = None,
) -> ProgramSignature:
    """Lower (and by default compile) an already-``jax.jit``-wrapped callable on ``args``
    — concrete arrays or ``ShapeDtypeStruct``s — and extract its signature. Never
    executes the program; with ``compile=False`` the signature carries cost + HLO
    features but no ``memory_analysis`` section (tracing only, much cheaper)."""
    lowered = fn.lower(*args)
    compiled = lowered.compile() if compile else None
    return extract_signature(lowered, compiled, name=name, shape_checks=shape_checks)


def capture_program_signature(
    fn: Callable,
    *args: Any,
    name: str,
    compile: bool = True,
    shape_checks: Mapping[str, tuple[Sequence[int], str]] | None = None,
    jit_kwargs: Mapping[str, Any] | None = None,
) -> ProgramSignature:
    """Convenience wrapper: jit a plain callable (``jit_kwargs`` forwards e.g.
    ``donate_argnums``) and capture its signature on ``args``."""
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn, **(jit_kwargs or {}))
    return capture_jit_signature(
        fn, args, name=name, compile=compile, shape_checks=shape_checks
    )


# --------------------------------------------------------------------- diffing


@dataclasses.dataclass
class Drift:
    """One gated metric that moved past its tolerance between baseline and current."""

    program: str
    metric: str
    baseline: Any
    current: Any
    allowed: float | None

    def __str__(self) -> str:
        if isinstance(self.baseline, (int, float)) and isinstance(
            self.current, (int, float)
        ):
            delta = self.current - self.baseline
            rel = delta / max(abs(self.baseline), 1.0)
            detail = f"{self.baseline} -> {self.current} ({rel:+.2%}"
            if self.allowed:
                detail += f", allowed ±{self.allowed:.2%}"
            detail += ")"
        else:
            detail = f"{self.baseline!r} -> {self.current!r}"
        return f"{self.program}: {self.metric}: {detail}"


def _gated_metrics(sig: Mapping[str, Any]) -> dict[str, Any]:
    """Flatten a signature JSON dict into the metric paths the diff gates on."""
    metrics: dict[str, Any] = {}
    for section in ("cost", "memory", "donation"):
        for key, value in (sig.get(section) or {}).items():
            metrics[f"{section}.{key}"] = value
    hlo = sig.get("hlo") or {}
    for key, value in (hlo.get("checks") or {}).items():
        metrics[f"hlo.checks.{key}"] = value
    largest = hlo.get("largest_buffer") or {}
    for key in ("bytes", "shape"):
        if key in largest:
            metrics[f"hlo.largest_buffer.{key}"] = largest[key]
    if sig.get("compiles") is not None:
        metrics["compiles"] = sig["compiles"]
    for side in ("in_sharding_specs", "out_sharding_specs"):
        if sig.get(side):
            metrics[side] = list(sig[side])
    return metrics


def diff_signatures(
    baseline: Mapping[str, Any],
    current: Mapping[str, Any],
    tolerances: Mapping[str, float | None] | None = None,
    program: str | None = None,
) -> list[Drift]:
    """Gated drift between two signature JSON dicts of the same program. Numeric metrics
    compare relatively (``|cur - base| <= tol * max(|base|, 1)``); everything else —
    booleans, sharding-spec lists, shape strings — compares exactly. A metric present on
    only one side is a drift. Tolerance ``None`` skips the metric entirely."""
    tols = dict(DEFAULT_TOLERANCES)
    tols.update(tolerances or {})
    program = program or current.get("name") or baseline.get("name") or "?"
    base_metrics = _gated_metrics(baseline)
    cur_metrics = _gated_metrics(current)
    drifts: list[Drift] = []
    for metric in sorted(set(base_metrics) | set(cur_metrics)):
        tol = tols.get(metric, 0.0)
        if tol is None:
            continue
        base = base_metrics.get(metric)
        cur = cur_metrics.get(metric)
        if isinstance(base, (int, float)) and isinstance(cur, (int, float)) and not (
            isinstance(base, bool) or isinstance(cur, bool)
        ):
            allowed = tol * max(abs(base), 1.0)
            if abs(cur - base) > allowed:
                drifts.append(Drift(program, metric, base, cur, tol))
        elif base != cur:
            drifts.append(Drift(program, metric, base, cur, tol if tol else None))
    return drifts


def diff_programs(
    baseline: Mapping[str, Mapping[str, Any]],
    current: Mapping[str, Mapping[str, Any]],
    tolerances: Mapping[str, float | None] | None = None,
) -> tuple[list[Drift], list[str]]:
    """Diff two ``{program name -> signature json}`` maps. Returns ``(drifts, notes)``:
    a baseline program missing from the current capture is a drift (a program the suite
    lost is exactly the "claim silently stopped being checked" failure mode); a new
    current program is a note — run ``--update`` to absorb it into the baseline."""
    drifts: list[Drift] = []
    notes: list[str] = []
    for name in sorted(baseline):
        if name not in current:
            drifts.append(Drift(name, "program", "present", "missing", None))
            continue
        drifts.extend(
            diff_signatures(baseline[name], current[name], tolerances, program=name)
        )
    for name in sorted(set(current) - set(baseline)):
        notes.append(f"{name}: new program (not in baseline; --update to absorb)")
    return drifts, notes


# --------------------------------------------------------------------- telemetry


def emit_program_signature_record(
    telemetry: Any, source: str, signatures: Mapping[str, ProgramSignature]
) -> None:
    """Write one ``program_signature`` telemetry record: the run self-reports what
    compiled (utils/telemetry.py RECORD_SCHEMA; tools/telemetry_summary.py renders the
    "programs:" line)."""
    telemetry.emit_record(
        "program_signature",
        source=source,
        platform=jax.default_backend(),
        programs=[sig.to_json() for sig in signatures.values()],
    )
