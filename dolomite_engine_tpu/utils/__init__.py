"""Generic utilities (reference `dolomite_engine/utils/`)."""

import logging
import os

import jax

from .diagnostics import (
    EWMADetector,
    FlightRecorder,
    HealthMonitor,
    StragglerDetector,
    build_model_report,
    emit_model_report,
    per_group_health,
)
from .fault_tolerance import (
    StallWatchdog,
    install_preemption_handler,
    preemption_requested,
    request_preemption,
    reset_preemption,
    run_crash_hooks,
    uninstall_preemption_handler,
)
from .logger import (
    get_logger,
    log_rank_0,
    print_rank_0,
    print_ranks_all,
    run_rank_n,
    set_logger,
    warn_rank_0,
)
from .mixed_precision import dtype_to_string, normalize_dtype_string, string_to_dtype
from .packages import (
    is_aim_available,
    is_colorlog_available,
    is_torch_available,
    is_transformers_available,
    is_wandb_available,
    pallas_import_error,
    pallas_interpret_mode,
)
from .pydantic import BaseArgs
from .retry import TRANSIENT_IO_ERRORS, retry_io
from .safetensors import SafeTensorsWeightsManager
from .telemetry import (
    OnDemandProfiler,
    Telemetry,
    collect_memory_gauges,
    detect_peak_tflops_per_device,
    get_telemetry,
    install_telemetry,
    profiler_call_at_step_boundary,
    stable_config_hash,
    uninstall_telemetry,
)
from .tracking import ExperimentsTracker, ProgressBar
from .yaml import dump_yaml, load_yaml

_DISTRIBUTED_INITIALIZED = False


def init_distributed(timeout_minutes: int | None = None) -> None:
    """Initialize the JAX distributed runtime for multi-host training.

    Parity: reference `dolomite_engine/utils/__init__.py:28-58` (`init_distributed`) does the NCCL
    rendezvous via `torch.distributed.init_process_group`. On TPU pods, coordination is instead
    `jax.distributed.initialize()`, which auto-discovers the coordinator from the TPU metadata
    (or `JAX_COORDINATOR_ADDRESS` etc. when launched manually). Single-process runs skip it.
    """
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return

    # heuristics: initialize when launched as one process of a multi-process job — explicit
    # coordinator env (manual launch), or a TPU pod slice (metadata server populates
    # TPU_WORKER_HOSTNAMES with every host of the slice; jax's cluster auto-detection then
    # supplies coordinator/process_id without further config)
    tpu_workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    multiprocess_env = any(
        os.environ.get(k) is not None
        for k in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS")
    ) or len([h for h in tpu_workers.split(",") if h.strip()]) > 1
    if multiprocess_env:
        kwargs = {}
        if timeout_minutes is not None:
            kwargs["initialization_timeout"] = timeout_minutes * 60
        # manual rendezvous (off-GCP pods, scripts/pretrain_pod.sh): JAX's cluster
        # auto-detection covers TPU metadata/SLURM/OMPI; plain-env launches pass these
        coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS")
        if coordinator is not None:
            kwargs["coordinator_address"] = coordinator
            if os.environ.get("JAX_PROCESS_COUNT") is not None:
                kwargs["num_processes"] = int(os.environ["JAX_PROCESS_COUNT"])
            if os.environ.get("JAX_PROCESS_INDEX") is not None:
                kwargs["process_id"] = int(os.environ["JAX_PROCESS_INDEX"])
        jax.distributed.initialize(**kwargs)

    enable_compilation_cache()

    _DISTRIBUTED_INITIALIZED = True
    log_rank_0(
        logging.INFO,
        f"initialized JAX runtime: {jax.process_count()} process(es), {jax.device_count()} device(s)",
    )


# where the compile cache lives when JAX_COMPILATION_CACHE_DIR is unset: one fixed,
# git-ignored directory at the root of the checkout. The directory is part of the cache
# key, so it never depends on ~, a temp name, a pid or a time — a path that moves never hits
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compilation_cache",
)


def enable_compilation_cache() -> str | None:
    """Persistent XLA compilation cache for every trainer/CLI/bench entry point; returns
    the directory in use, or None when the cache stays off.

    TPU compiles of a full train step run 20-60s; the cache makes every restart after the
    first (crash recovery, preemption resume, config-identical relaunch, a second process
    in the same chip call) skip straight to execution. Placement is the environment's:
    with `JAX_COMPILATION_CACHE_DIR` set, jax itself reads the variable and this function
    sets NO path; unset, the cache goes to :data:`DEFAULT_COMPILATION_CACHE_DIR`. Opt out
    with `DOLOMITE_COMPILATION_CACHE=0`.
    """
    toggle = os.environ.get("DOLOMITE_COMPILATION_CACHE", "")
    if toggle == "0":
        return None
    # default: TPU only. XLA:CPU caches AOT machine code and warns (worst case SIGILL) when
    # the loading host's CPU features differ from the compiling host's — not worth it for
    # sub-second CPU-test compiles. `DOLOMITE_COMPILATION_CACHE=1` force-enables anywhere.
    if toggle != "1" and jax.default_backend() != "tpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    # default min-compile-time is 1s which already excludes trivial CPU-test programs;
    # make it explicit so the behavior is pinned across jax upgrades
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
