"""One process, one cell, once:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell to its configuration, traffic mix and driver by name (``spec.py``), fails
unless JAX finds the TPUs the cell asks for (``--tiny`` is a CPU rehearsal at toy widths and
can never print ``"correct": true``), runs the driver, and prints as the last line of its
standard output one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, when traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()  # set-up counts from here: before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from .harness import Check, CompileCounter, Context, RunResult, device_record, fullest_memory_stats, say  # noqa: E402


def execute(workload: str, seed: int, seconds: float, trace: bool, tiny: bool = False, spec=None, control: bool = False,
            traffic_overrides: dict | None = None, skip_check: bool = False) -> tuple[dict, list]:
    """Run one cell; returns the result line and the numbers compared for ``correct``.
    Exits (SystemExit) where the accelerator the cell asks for is missing."""
    from .spec import Spec

    spec = spec or Spec.load()
    cell = spec.cell(workload)
    cell.traffic.update(traffic_overrides or {})  # tools only (the rate sweep): never the command

    import jax

    say(f"benchmark: [{time.perf_counter() - PROCESS_START:7.2f} s] jax imported")
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    say(f"benchmark: cell {cell.name} (config {cell.config_name}, traffic {cell.traffic_name}), seed {seed}, "
        f"{seconds} s, trace {int(trace)}; jax {jax.__version__}; {platform} / {kind} x {len(devices)}")
    peaks = None
    if not tiny:
        if platform != "tpu":
            raise SystemExit(f"benchmark: no TPU: jax found {platform} devices")
        peaks = spec.peaks(kind)  # an unknown kind is an error
        if len(devices) != cell.chips:
            raise SystemExit(f"benchmark: the cell asks for {cell.chips} chip(s), jax found {len(devices)}")
    elif len(devices) < cell.chips:
        raise SystemExit(f"benchmark: the cell asks for {cell.chips} device(s), jax found {len(devices)}")

    from dolomite_engine_tpu.utils import enable_compilation_cache, pallas_interpret_mode

    if platform == "tpu" and pallas_interpret_mode():
        raise SystemExit("benchmark: Pallas kernels would run interpreted on the TPU")
    # JAX_COMPILATION_CACHE_DIR if set, else the checkout's fixed .jax_compilation_cache/
    say(f"benchmark: compile cache {enable_compilation_cache()}")

    out_dir = os.path.join(spec.root, ".benchmark_out", cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    context = Context(
        cell=cell, spec=spec, seed=seed, seconds=seconds, trace=trace, tiny=tiny, out_dir=out_dir,
        process_start=PROCESS_START, peaks=peaks, compiles=CompileCounter(), control=control, skip_check=skip_check,
    )
    context.mark("devices found, the program's package imported")
    result: RunResult = spec.driver(cell.traffic).run(context)

    checks = list(result.checks)
    checks.append(
        Check("compilations_in_window", context.compiles.in_window, 0, context.compiles.in_window == 0,
              "; ".join(context.compiles.names))
    )
    for check in checks:
        say(f"check: {check.name} = {check.value!r} (limit {check.limit!r}) -> {'ok' if check.ok else 'NOT OK'} {check.note}")
    correct = all(c.ok for c in checks if not c.name.startswith("control_")) and not tiny and not skip_check

    memory_stats = result.memory_stats or fullest_memory_stats(devices)
    device = device_record(devices, memory_stats)
    line = {"correct": bool(correct), "attempted": int(result.attempted), "failed": int(result.failed)}
    metrics: dict = {}
    if not trace:
        for metric in cell.end_to_end:
            metrics[metric["name"]] = {"value": result.end_to_end[metric["name"]], "unit": metric["unit"]}
    else:
        if result.trace is None:
            raise RuntimeError("a traced run produced no trace")
        device["busy_s"] = result.trace.busy_s
        device["window_s"] = result.trace.window_s
        result.memory_stats = memory_stats
        for metric in cell.per_layer:
            value = spec.layer_metric(metric["name"]).read(result, context)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        line["breakdown"] = result.trace.breakdown()
    line["metrics"] = metrics
    line["device"] = device
    shutil.rmtree(out_dir, ignore_errors=True)
    return line, checks


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--tiny", action="store_true", help="CPU rehearsal at toy widths; never correct")
    options = parser.parse_args(argv)
    line, _ = execute(options.workload, options.seed, options.seconds, bool(options.trace), options.tiny)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
