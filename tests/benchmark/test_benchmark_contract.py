"""BENCHMARK.json against the files it names, and the result line."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402
from benchmark import readers, run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    # 2 + 14 runs a cell, with the full 24 cells, must fit the check
    n = 24
    cost = (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 2 * 90 + 1200
    assert cost <= 43200
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer") + (("source",) if "file" in entry else ()):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    for key in entry.get("reduced", []):
        assert NAME.match(key)


def test_entries_have_just_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [e["name"] for e in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_resolves(cfg):
    assert cfg["file"].startswith("benchmark/configs/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert body["guarantees"] and body["assumed"]
    for key in cfg["reduced"]:
        assert key in body and key in body["reduced_why"], key
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def _drivers_of(spec):
    yield spec["driver"]
    for key in ("foreground", "background"):
        if key in spec:
            yield from _drivers_of(spec[key])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_by_name(cell):
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    traffic = run.load_json("traffic", cell["traffic"] + ".json")
    assert traffic["trace"]["mode"] in ("rpc", "time", "window")
    for name in _drivers_of(traffic):
        mod = importlib.import_module(f"benchmark.drivers.{name}")
        for method in ("prepare", "warm", "run_window", "check_live",
                       "check_files"):
            assert callable(getattr(mod.Driver, method))
    e2e = run.cell_metrics(BENCH, cell["name"], traced=False)
    layer = run.cell_metrics(BENCH, cell["name"], traced=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in [e["name"] for e in e2e], m["name"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_resolves_by_name(metric):
    spec = readers.metric_spec(metric["name"])
    mod = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    # a reader that finds nothing to read returns nothing, never 0
    assert mod.read(hz.Obs(), spec.get("args", {})) is None
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if "moves" in metric:
        assert metric["moves"] in [e["name"] for e in BENCH["end_to_end"]]


def test_every_file_under_paths_is_named_plainly():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        listed = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard",
             path], cwd=ROOT, capture_output=True, text=True).stdout.split()
        for f in listed:
            assert ok.match(f), f


def test_run_py_holds_no_table_of_cells_or_metrics():
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        src = f.read()
    for name in CELLS + [m["name"] for m in METRICS if m["name"] != "setup_s"]:
        assert name not in src, name


COMPARED = {"shard_bytes_differ": {"value": 0, "limit": 0}}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 1}


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_key_set(traced):
    breakdown = {"device_ops": [["a", 0.1]], "idle_gaps": []} if traced \
        else None
    device = dict(DEVICE, busy_s=1.0, window_s=2.0) if traced else DEVICE
    line = hz.result_line(3, 0, {"setup_s": {"value": 1, "unit": "s"}},
                          device, COMPARED, breakdown)
    assert "\n" not in line
    out = json.loads(line)
    want = ["correct", "attempted", "failed", "metrics", "device"]
    if traced:
        want.append("breakdown")
    assert list(out) == want + ["compared"]      # `compared` comes last
    assert out["correct"] is True
    assert out["metrics"]["setup_s"] == {"value": 1.0, "unit": "s"}


@pytest.mark.parametrize("compared,want", [
    ({}, False),
    ({"a": {"value": 0, "limit": 0}}, True),
    ({"a": {"value": 0, "limit": 0}, "b": {"value": 1, "limit": 0}}, False),
    ({"a": {"value": None, "limit": 0}}, False),
])
def test_decide(compared, want):
    assert hz.decide(compared) is want
    assert ("OVER" in hz.compared_lines(compared)) is (
        not want and bool(compared))


def test_harness_and_drivers_import_no_jax():
    """The chip belongs to the server child: nothing the harness process
    imports may pull jax in (`-X importtime` names every import)."""
    code = ("import benchmark.run, benchmark.check, benchmark.faults\n"
            "import benchmark.drivers.ec_encode_loop\n"
            "import benchmark.drivers.ec_rebuild_loop\n"
            "import benchmark.drivers.weed_benchmark_phases\n"
            "import benchmark.drivers.background_under\n"
            "from benchmark.dataset import _engine; _engine()\n"
            "from seaweedfs_tpu.shell import ec_commands\n")
    for name in os.listdir(os.path.join(ROOT, "benchmark", "readers")):
        if name.endswith(".py") and name != "__init__.py":
            code += f"import benchmark.readers.{name[:-3]}\n"
    proc = subprocess.run(
        [sys.executable, "-X", "importtime", "-c", code], cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    imported = [ln.rsplit("|", 1)[-1].strip().split(".")[0]
                for ln in proc.stderr.splitlines()
                if ln.startswith("import time:")]
    assert "numpy" in imported
    assert not {"jax", "jaxlib"} & set(imported)
