"""The harness is driven by data: a cell, a configuration, a traffic mix, a driver and a
per-layer metric are added as new files plus appended entries, never by editing a file. And
BENCHMARK.json keeps to the static rules of its contract."""

import json
import os
import re
import shutil

import pytest

from benchmark import run as bench_run
from benchmark.spec import ROOT, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TESTDATA = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb.gz")

FAKE_DRIVER = '''
from benchmark.harness import Check, RunResult
from benchmark.reduce_trace import reduce_trace

def run(ctx):
    result = RunResult(
        attempted=ctx.cell.traffic["requests"], failed=0,
        end_to_end={"widgets_per_s": ctx.cell.config["pretrained_config"]["n_embd"] / ctx.seconds, "setup_s": 1.5},
        checks=[Check("answer_gap", 0.0, ctx.cell.limits["answer_gap"], True)],
        facts={"seed": ctx.seed},
    )
    if ctx.trace:
        result.trace = reduce_trace(%r)
    return result
''' % TESTDATA

NEW_METRIC = '''
def read(result, ctx):
    return 100.0 * result.trace.scope_seconds("pallas_rmsnorm") / result.trace.busy_s
'''

SILENT_METRIC = '''
def read(result, ctx):
    return None  # finds nothing to read here: the harness leaves it out of the line
'''


@pytest.fixture()
def extended(tmp_path):
    """A copy of the benchmark's data with one of everything ADDED and no file changed."""
    root = tmp_path
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "drivers", "layer_metrics", "limits"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), bench / sub)
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench / "peaks.json")
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "widget-1b.json").write_text(json.dumps({"pretrained_config": {"n_embd": 1000}}))
    (bench / "traffic" / "widget_stream.json").write_text(json.dumps({"driver": "fake_widgets", "requests": 7}))
    (bench / "drivers" / "fake_widgets.py").write_text(FAKE_DRIVER)
    (bench / "layer_metrics" / "rmsnorm_share.widgets.py").write_text(NEW_METRIC)
    (bench / "layer_metrics" / "nothing_to_read.py").write_text(SILENT_METRIC)
    (bench / "limits" / "widget-1b.stream.json").write_text(json.dumps({"answer_gap": 0}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "widget-1b", "source": "https://example.org/widget", "file": "benchmark/configs/widget-1b.json", "reduced": [], "why": "test"})
    data["workloads"].append({"name": "widget-1b.stream", "config": "widget-1b", "traffic": "widget_stream", "chips": 1, "why": "test"})
    data["end_to_end"].append({"name": "widgets_per_s", "unit": "widgets/s", "better": "higher", "bound": 0.01, "source": "host_clock", "workloads": ["widget-1b.stream"]})
    data["per_layer"].append({"name": "rmsnorm_share.widgets", "unit": "%", "better": "lower", "source": "device_trace", "layer": "kernels", "moves": "widgets_per_s"})
    data["per_layer"].append({"name": "nothing_to_read", "unit": "%", "better": "lower", "source": "device_trace", "layer": "kernels", "moves": "widgets_per_s", "workloads": ["widget-1b.stream"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    yield Spec.load(str(root))
    assert {p: p.read_bytes() for p in before} == before  # nothing that was there was edited


def test_a_new_cell_resolves_to_its_new_files(extended):
    cell = extended.cell("widget-1b.stream")
    assert cell.config["pretrained_config"]["n_embd"] == 1000
    assert cell.traffic["driver"] == "fake_widgets" and cell.limits == {"answer_gap": 0}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "widgets_per_s"]
    # a metric without "workloads" is reported wherever its end-to-end metric is
    assert [m["name"] for m in cell.per_layer] == ["rmsnorm_share.widgets", "nothing_to_read"]
    # and the cells that were there do not see the new metrics
    old = extended.cell("train-3b-packed4k")
    assert "widgets_per_s" not in [m["name"] for m in old.end_to_end]
    assert "rmsnorm_share.widgets" not in [m["name"] for m in old.per_layer]


@pytest.mark.parametrize("trace", [False, True])
def test_a_new_cell_runs_and_its_last_line_has_the_contract_keys(extended, trace, capsys):
    line, checks = bench_run.execute("widget-1b.stream", 2**31 + 9, 10.0, trace, tiny=True, spec=extended)
    expected = {"correct", "attempted", "failed", "metrics", "device"} | ({"breakdown"} if trace else set())
    assert set(line) == expected
    assert line["correct"] is False  # a rehearsal is never correct, whatever the checks say
    assert all(c.ok for c in checks)
    assert line["attempted"] == 7 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        assert set(line["metrics"]) == {"rmsnorm_share.widgets"}  # the silent reader is left out
        assert line["metrics"]["rmsnorm_share.widgets"]["unit"] == "%"
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > line["device"]["busy_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        assert line["metrics"] == {
            "widgets_per_s": {"value": 100.0, "unit": "widgets/s"},
            "setup_s": {"value": 1.5, "unit": "s"},
        }
    json.dumps(line)  # one JSON object


def test_unknown_workload_and_device_kind_are_refused(extended):
    with pytest.raises(KeyError, match="no workload"):
        extended.cell("no-such-cell")
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        extended.peaks("TPU v99")
    with pytest.raises(KeyError):
        extended.peaks("_source")
    assert extended.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert extended.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_without_a_tpu_there_is_no_result(capsys):
    with pytest.raises(SystemExit) as error:
        bench_run.main(["--workload", "train-3b-packed4k", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert error.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


# ------------------------------------------------------------- BENCHMARK.json's static rules


@pytest.fixture(scope="module")
def data():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(data):
    assert set(data) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert data["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(data["run_seconds"], int) and 1 <= data["run_seconds"] <= 51
    assert len(data["command"]) <= 32 and not any(w.startswith("/") or ".." in w for w in data["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entry_keys(data):
    for entry in data["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(entry["name"]) and all(NAME.match(k) for k in entry["reduced"]) and len(entry["reduced"]) <= 16
        assert entry["file"].startswith("benchmark/") and os.path.exists(os.path.join(ROOT, entry["file"]))
        width = re.compile(r"(_dim|_rank)$|hidden|intermediate|n_embd|n_inner|head_dim|latent|state|proj")
        assert not any(width.search(k) for k in entry["reduced"])
    for entry in data["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
        assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for entry in data["end_to_end"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= entry["bound"] <= 0.1 and entry["source"] in ("host_clock", "device_trace")
    for entry in data["per_layer"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert 1 <= len(entry["layer"]) <= 200
    for entry in data["end_to_end"] + data["per_layer"]:
        assert NAME.match(entry["name"]) and UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in data[group]]
        assert len(names) == len(set(names))
    metric_names = [e["name"] for e in data["end_to_end"] + data["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in data["workloads"])
    assert four <= max(len(data["workloads"]) // 4, 1)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(data):
    spec = Spec.load()
    assert "setup_s" in [m["name"] for m in data["end_to_end"]]
    used = set()
    for entry in data["workloads"]:
        cell = spec.cell(entry["name"])
        used.add(cell.config_name)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert cell.limits, f"{cell.name} has no limits file"
        for metric in cell.per_layer:
            assert metric["moves"] in names
            assert callable(spec.layer_metric(metric["name"]).read)
        assert callable(spec.driver(cell.traffic).run)
    assert used == {c["name"] for c in data["configs"]}
    for metric in data["per_layer"]:
        assert metric["moves"] in [m["name"] for m in data["end_to_end"]]
        for name in metric.get("workloads", []):
            assert name in [w["name"] for w in data["workloads"]]


def test_roofline_and_mfu_metrics_are_shares_in_percent(data):
    for metric in data["per_layer"]:
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_files_under_paths_are_named_from_name_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in ("benchmark", os.path.join("tests", "benchmark")):
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                relative = os.path.relpath(os.path.join(folder, name), ROOT)
                assert allowed.match(relative) and len(relative) <= 200, relative
