"""Optional-package probes with warn-once semantics.

Parity: reference `dolomite_engine/utils/packages.py` (is_*_available x12 for apex, deepspeed,
flash-attn, ...). The TPU build has a much smaller optional surface: experiment trackers and
colored logging. GPU-only deps from the reference have no probe here because their functionality
is built in (Pallas kernels replace flash-attn/scattermoe/apex; XLA replaces torch.compile).
"""

import importlib.util
from functools import cache


@cache
def _is_available(name: str) -> bool:
    return importlib.util.find_spec(name) is not None


def is_aim_available() -> bool:
    return _is_available("aim")


def is_wandb_available() -> bool:
    return _is_available("wandb")


def is_colorlog_available() -> bool:
    return _is_available("colorlog")


def is_transformers_available() -> bool:
    return _is_available("transformers")


def is_torch_available() -> bool:
    # only used by HF-interop converters for reading torch-format checkpoints
    return _is_available("torch")


# ---------------------------------------------------------------------- Pallas / TPU
# The ONE capability probe every kernel call site consumes (ops/attention.py splash,
# ops/pallas/*): probed once per process instead of per-call try-imports.


@cache
def pallas_import_error() -> ImportError | None:
    """The error `jax.experimental.pallas` (+ the TPU dialect) raises on import in this
    build, or None when both import."""
    try:
        import jax.experimental.pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401
    except ImportError as error:
        return error
    return None


@cache
def pallas_interpret_mode() -> bool:
    """Whether Pallas kernels must run in interpret mode on this backend.

    True off-TPU (CPU tier-1 parity tests, local debugging), False on real TPUs where
    Mosaic compiles the kernel — no environment switch can turn a TPU run into an
    interpreted one (a kernel under debug takes its own ``interpret=`` argument).
    Cached: the backend cannot change mid-process."""
    import jax

    return jax.default_backend() != "tpu"
