"""Resolve ``BENCHMARK.json`` to files by name: the harness knows no cell, configuration,
traffic mix or metric by name. A later PR adds files and appends entries.

    workloads[].config   -> benchmark/configs/<config>.json   (BENCHMARK.json configs[].file)
    workloads[].traffic  -> benchmark/traffic/<traffic>.json
    traffic file "driver"-> benchmark/drivers/<driver>.py      (run(ctx) -> RunResult)
    metric name          -> benchmark/layer_metrics/<name>.py  (read(run) -> number | None)
    workloads[].name     -> benchmark/limits/<name>.json       (the limits of ``correct``)
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric names hold dots, so they are no module names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell may report with --trace 1


@dataclass
class Spec:
    root: str = ROOT
    bench_dir: str = HERE
    data: dict = field(default_factory=dict)

    @classmethod
    def load(cls, root: str = ROOT, bench_dir: str | None = None) -> "Spec":
        bench_dir = bench_dir or os.path.join(root, "benchmark")
        return cls(root, bench_dir, load_json(os.path.join(root, "BENCHMARK.json")))

    def cell(self, name: str) -> Cell:
        entries = [w for w in self.data["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in self.data["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: {known})")
        entry = entries[0]
        (config_entry,) = [c for c in self.data["configs"] if c["name"] == entry["config"]]
        config = load_json(os.path.join(self.root, config_entry["file"]))
        traffic = load_json(os.path.join(self.bench_dir, "traffic", entry["traffic"] + ".json"))
        limits_path = os.path.join(self.bench_dir, "limits", name + ".json")
        limits = load_json(limits_path) if os.path.exists(limits_path) else {}

        def reported(metric: dict, default_all: bool) -> bool:
            if "workloads" in metric:
                return name in metric["workloads"]
            return default_all

        end_to_end = [m for m in self.data["end_to_end"] if reported(m, True)]
        e2e_names = {m["name"] for m in end_to_end}
        per_layer = [m for m in self.data["per_layer"] if reported(m, m["moves"] in e2e_names)]
        return Cell(
            name, entry["chips"], entry["why"], entry["config"], entry["traffic"],
            config, traffic, limits, end_to_end, per_layer,
        )

    def driver(self, traffic: dict):
        name = traffic["driver"]
        return load_module(os.path.join(self.bench_dir, "drivers", name + ".py"), f"benchmark_driver_{name}")

    def layer_metric(self, name: str):
        path = os.path.join(self.bench_dir, "layer_metrics", name + ".py")
        return load_module(path, "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table or device_kind.startswith("_"):
            raise KeyError(
                f"device kind {device_kind!r} is not in benchmark/peaks.json: a device the table "
                "does not know is an error, not a default"
            )
        return table[device_kind]
