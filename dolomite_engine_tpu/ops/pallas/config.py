"""Central kernel-backend selection: ONE KernelConfig instead of scattered env sniffing.

Every hand-written kernel in this package sits behind a per-op-family switch with the
plain-XLA lowering as the numerical reference:

- ``splash_attention``: the GQA-native Pallas splash kernel for full-sequence causal
  attention (`ops/attention.py`).
- ``paged_attention``: the ragged paged-attention decode kernel (`paged_attention.py`) —
  serving decode/verify reads K/V straight through the page table instead of
  gather-then-mask.
- ``prefill_attention``: the chunked-prefill flash kernel (`prefill_attention.py`) —
  prefill chunks read the resident prefix through the page table with online softmax
  instead of the worst-case gathered view.
- ``paged_kv_quant``: the page-quantization encode kernel (`kv_quant.py`) behind the
  quantized paged KV pool's quantize-on-scatter (`ops/kv_quant.quantize_pages`);
  byte-identical to the XLA reference encoding.
- ``rmsnorm``: the fused RMSNorm(+residual add) kernel (`rmsnorm.py`) inside the
  transformer block.
- ``moe_dispatch``: the grouped-GEMM MoE dispatch (`moe.py`) replacing the dense
  all-experts einsum.
- ``fused_ce``: the vocab-tiled online-logsumexp chunk kernel (`fused_ce.py`) inside the
  chunked fused LM-head loss (`ops/loss.fused_linear_cross_entropy`).
- ``fused_rope_qkv``: the fused QKV-split + rotary-embedding kernel (`rope_qkv.py`)
  behind the one rope+QKV call site shared by training forward and the serving
  prefill/decode/verify programs (`ops/rope.split_qkv_apply_rope`).

Selection precedence: an explicitly installed config (``install_kernel_config`` — wired
from the ``kernel_args`` YAML block in `arguments.py` by the CLI entry points) beats the
``DOLOMITE_KERNELS`` env var, which beats the ``auto`` platform default. The env var is
a comma list of ``family[=backend]`` pairs; a bare family name means ``pallas`` and the
literal item ``auto`` resets every family to the platform default::

    DOLOMITE_KERNELS=paged_attention,rmsnorm=pallas python tools/serve.py ...
    DOLOMITE_KERNELS=auto python tools/serve.py ...          # pure platform defaults
    DOLOMITE_KERNELS=auto,moe_dispatch=xla python ...        # defaults, one demotion

``auto`` (the default everywhere since the promotion-defaults round) resolves through
:data:`_PLATFORM_PROMOTIONS` — per-family tables keyed on the detected platform (TPU
generation vs CPU/GPU), so the families with proven hardware wins lower as Pallas on TPU
without per-run flags while CPU runs (tier-1, parity tests, emulator benches) keep the
all-XLA reference lowering. Explicit ``xla``/``pallas`` spellings — YAML or env — always
override the table.

Call sites gate on :func:`use_pallas`, which resolves ``auto`` and folds in the
capability probe (`utils/packages.pallas_import_error`): a family that resolves to
Pallas on a build where Pallas does not import RAISES — nothing degrades to XLA while
the record still says ``pallas``. Tests override per-family via the
:func:`kernel_overrides` context manager; telemetry reports the RESOLVED map
(:func:`active_kernel_backends`) so every run records what actually lowered.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, fields, replace

from ...enums import KernelBackend

KERNEL_FAMILIES = (
    "splash_attention",
    "paged_attention",
    "prefill_attention",
    "paged_kv_quant",
    "rmsnorm",
    "moe_dispatch",
    "fused_ce",
    "fused_rope_qkv",
)

# Families promoted to Pallas per detected platform when a family resolves to ``auto``.
# Keys: "tpu" is the generic TPU row; "tpu:<gen>" rows override it for one generation;
# anything else (cpu, gpu) promotes nothing — XLA stays the reference there.
#
# A family is in the generic TPU row only if the TPU compiler accepts it at real widths
# (`tests/ops/test_tpu_compile.py` compiles each for a described v5e) — the argument for
# each is architectural (one HBM round-trip instead of three: rmsnorm, fused rope+QKV;
# GQA without KV repetition: splash); none has an on-chip A/B yet (not measured).
# `paged_attention` and `prefill_attention` are OUT: Mosaic refuses both kernels
# (ROADMAP.md S5 has the shapes and messages), so serving decode/prefill lower through
# the XLA gather path on TPU until they are rewritten. `moe_dispatch` and `fused_ce`
# stay on XLA pending on-chip A/Bs. v2/v3 keep only the conservative pair: their cores
# have half the VMEM of v4+ (8 MB), which the splash block sizes were not tuned for.
_PLATFORM_PROMOTIONS: dict[str, frozenset[str]] = {
    "tpu": frozenset(
        {
            "splash_attention",
            "paged_kv_quant",
            "rmsnorm",
            "fused_rope_qkv",
        }
    ),
    "tpu:v2": frozenset({"rmsnorm", "fused_rope_qkv"}),
    "tpu:v3": frozenset({"rmsnorm", "fused_rope_qkv"}),
}


@dataclass(frozen=True)
class KernelConfig:
    """Backend per op family; ``auto`` resolves to the platform promotion table (always
    ``xla`` off-TPU, so the reference lowering stays the no-flags default on CPU)."""

    splash_attention: KernelBackend = KernelBackend.auto
    paged_attention: KernelBackend = KernelBackend.auto
    prefill_attention: KernelBackend = KernelBackend.auto
    paged_kv_quant: KernelBackend = KernelBackend.auto
    rmsnorm: KernelBackend = KernelBackend.auto
    moe_dispatch: KernelBackend = KernelBackend.auto
    fused_ce: KernelBackend = KernelBackend.auto
    fused_rope_qkv: KernelBackend = KernelBackend.auto


assert tuple(f.name for f in fields(KernelConfig)) == KERNEL_FAMILIES

_LOCK = threading.Lock()
_INSTALLED: KernelConfig | None = None
_PLATFORM_KEY: str | None = None  # cached; reset via _reset_platform_cache (tests)


def _normalize_tpu_kind(device_kind: str) -> str:
    """"TPU v5 lite" / "TPU v5e" / "TPU v4" -> "v5e" / "v5e" / "v4"."""
    kind = device_kind.lower().replace("tpu", "").strip()
    kind = kind.replace(" lite", "e").replace("lite", "e").replace(" ", "")
    return kind


def _detect_platform_key() -> str:
    """"tpu:<generation>" on TPU, else the jax backend name ("cpu", "gpu").

    Cached per process: resolving it initializes the backend, which every consumer of
    this module does anyway before the first trace."""
    global _PLATFORM_KEY
    if _PLATFORM_KEY is None:
        import jax

        backend = jax.default_backend()
        if backend == "tpu":
            # a TPU whose kind cannot be read is an error, not a generic-row default
            backend = f"tpu:{_normalize_tpu_kind(jax.devices()[0].device_kind)}"
        _PLATFORM_KEY = backend
    return _PLATFORM_KEY


def _reset_platform_cache() -> None:
    global _PLATFORM_KEY
    _PLATFORM_KEY = None


def platform_default_backend(family: str) -> KernelBackend:
    """What ``auto`` resolves to for `family` on the detected platform: the generation
    row if present, else the generic "tpu" row on any TPU, else ``xla``."""
    key = _detect_platform_key()
    if key.startswith("tpu"):
        promoted = _PLATFORM_PROMOTIONS.get(key, _PLATFORM_PROMOTIONS["tpu"])
        if family in promoted:
            return KernelBackend.pallas
    return KernelBackend.xla


def _coerce_backend(value) -> KernelBackend:
    if isinstance(value, KernelBackend):
        return value
    try:
        return KernelBackend(str(value))
    except ValueError:
        raise ValueError(
            f"unknown kernel backend '{value}' (expected one of "
            f"{[b.value for b in KernelBackend]})"
        ) from None


def _config_from_env() -> KernelConfig:
    overrides: dict[str, KernelBackend] = {}
    spec = os.environ.get("DOLOMITE_KERNELS", "")
    for item in filter(None, (part.strip() for part in spec.split(","))):
        if item == "auto":
            # explicit platform-defaults spelling: reset every family to auto (useful
            # as a prefix before per-family demotions)
            overrides = {family: KernelBackend.auto for family in KERNEL_FAMILIES}
            continue
        family, sep, backend = item.partition("=")
        family = family.strip()
        if family not in KERNEL_FAMILIES:
            raise ValueError(
                f"DOLOMITE_KERNELS names unknown kernel family '{family}' "
                f"(expected one of {KERNEL_FAMILIES})"
            )
        overrides[family] = _coerce_backend(backend.strip()) if sep else KernelBackend.pallas
    return KernelConfig(**overrides)


def get_kernel_config() -> KernelConfig:
    """The active (UNRESOLVED) config: installed > ``DOLOMITE_KERNELS`` env > all-auto
    default. ``auto`` entries resolve per platform at the `use_pallas` /
    `resolved_kernel_backend` layer."""
    installed = _INSTALLED
    return installed if installed is not None else _config_from_env()


def install_kernel_config(config: KernelConfig | dict | None) -> None:
    """Install the process-wide config (None reverts to env/default resolution).

    Accepts a mapping of family -> backend-name too, which is how the ``kernel_args``
    block from `arguments.py` arrives."""
    global _INSTALLED
    if config is not None and not isinstance(config, KernelConfig):
        unknown = set(config) - set(KERNEL_FAMILIES)
        if unknown:
            raise ValueError(
                f"unknown kernel famil{'ies' if len(unknown) > 1 else 'y'} "
                f"{sorted(unknown)} (expected one of {KERNEL_FAMILIES})"
            )
        config = KernelConfig(
            **{name: _coerce_backend(value) for name, value in config.items()}
        )
    with _LOCK:
        _INSTALLED = config


def kernel_backend(family: str) -> KernelBackend:
    """The configured backend for `family`, ``auto`` included (unresolved)."""
    return getattr(get_kernel_config(), family)


def resolved_kernel_backend(family: str) -> KernelBackend:
    """``xla`` or ``pallas`` for `family`: the configured backend with ``auto`` resolved
    through the platform promotion table."""
    backend = kernel_backend(family)
    if backend is KernelBackend.auto:
        backend = platform_default_backend(family)
    return backend


def use_pallas(family: str) -> bool:
    """True when `family` resolves to Pallas — an explicit ``pallas`` or a TPU ``auto``
    promotion. A build whose Pallas import fails cannot honour either, and that is an
    error here rather than a quiet XLA run that still reports ``pallas``."""
    if resolved_kernel_backend(family) is not KernelBackend.pallas:
        return False
    from ...utils.packages import pallas_import_error

    error = pallas_import_error()
    if error is not None:
        raise RuntimeError(
            f"kernel family '{family}' resolves to pallas but jax.experimental.pallas "
            "does not import in this build"
        ) from error
    return True


def active_kernel_backends() -> dict[str, str]:
    """family -> backend-name map of what lowers right now (telemetry `run_start` and
    `serving` records), with ``auto`` resolved."""
    return {
        family: (KernelBackend.pallas if use_pallas(family) else KernelBackend.xla).value
        for family in KERNEL_FAMILIES
    }


@contextmanager
def kernel_overrides(**families: str | KernelBackend):
    """Temporarily override per-family backends (tests, benchmark A/Bs); restores the
    previously installed config — including "nothing installed" — on exit."""
    previous = _INSTALLED
    base = get_kernel_config()
    install_kernel_config(
        replace(base, **{name: _coerce_backend(value) for name, value in families.items()})
    )
    try:
        yield
    finally:
        install_kernel_config(previous)
