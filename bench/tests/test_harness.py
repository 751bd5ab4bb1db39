"""The harness's own logic: lookup by name, the metric arithmetic, and one
unit of every path at a tiny size on XLA:CPU."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

import harness

CELLS = [w["name"] for w in
         harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == harness._by_name(
        harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"],
        name, "workload")["config"]
    for fn in ("setup", "unit", "free", "reference", "compare"):
        assert callable(getattr(cell.path, fn))
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert set(cell.readers) == names
    assert "setup_s" in names
    assert set(cell.options["limits"])


@pytest.mark.parametrize("what, bench_edit", [
    ("workload", lambda b: None),
    ("configuration", lambda b: b["workloads"][0].update(config="nope")),
])
def test_unknown_name_is_an_error(what, bench_edit):
    bm = harness.read_json(harness.ROOT / "BENCHMARK.json")
    bench_edit(bm)
    name = "nope" if what == "workload" else bm["workloads"][0]["name"]
    with pytest.raises(harness.BenchError, match=f"unknown {what}"):
        harness.load_cell(name, benchmark=bm)


def test_missing_path_file_is_an_error(tmp_path, monkeypatch):
    bm = harness.read_json(harness.ROOT / "BENCHMARK.json")
    name = bm["workloads"][0]["name"]
    opts = harness.read_json(harness.BENCH / "workloads" / f"{name}.json")
    monkeypatch.setattr(harness, "read_json", lambda p: (
        dict(opts, path="nope") if p.name == f"{name}.json"
        else json.loads(p.read_text())))
    with pytest.raises(harness.BenchError, match="no such file"):
        harness.load_cell(name, benchmark=bm)


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_gets_the_same_jobs_in_another_order(name, tiny_cell):
    import traffic
    cell = tiny_cell(name)
    a, b = (traffic.make_trace(cell.config, cell.traffic, s) for s in (4, 9))
    again = traffic.make_trace(cell.config, cell.traffic, 4)
    for k in traffic.COLUMNS:
        np.testing.assert_array_equal(a[k], again[k])
        np.testing.assert_array_equal(np.sort(a[k]), np.sort(b[k]))
    assert np.all(np.diff(a["arrival"]) >= 0)
    if cell.traffic["arrival"] == "closed":
        assert not a["arrival"].any()


def test_rate_is_work_of_whole_units_over_window():
    record = {"units": [{"work": 100}, {"work": 100}, {"work": 50}],
              "window_s": 2.5}
    assert harness.rate(record) == pytest.approx(100.0)


def test_p95_is_over_every_request_not_over_epochs():
    # two epochs: 90 requests at 1 ms and 10 at 100 ms. A p95 of epoch
    # latencies would read 95 ms; over requests, 5 of the 100 lie above
    # the 95th percentile, all of them in the slow epoch.
    lat = [np.full(90, 1e-3), np.full(10, 0.1)]
    assert harness.p95_over_requests(lat) == pytest.approx(0.1)
    lat = [np.full(96, 1e-3), np.full(4, 0.1)]
    assert harness.p95_over_requests(lat) == pytest.approx(1e-3)


def test_decide_p95_reader_in_ms():
    reader = harness.load_module(
        harness.BENCH / "end_to_end" / "decide_p95_ms.py", "decide_p95_ms")
    rec = {"units": [{"latencies_s": np.full(100, 0.02)}]}
    assert reader.value(rec) == pytest.approx(20.0)


@pytest.mark.parametrize("name", CELLS)
def test_unit_runs_on_cpu_at_tiny_size(name, tiny_cell, run_tiny):
    cell = tiny_cell(name)
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_main_entry_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_new_cell_by_adding_files_only(tmp_path):
    """A configuration, traffic, cell, path and per-layer metric added as
    new files (and entries of BENCHMARK.json) are found with no edit to
    any file of bench/."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = harness.read_json(harness.ROOT / "BENCHMARK.json")
    b = root / "bench"
    conf = harness.read_json(b / "configs" / "hadoop-paper.json")
    (b / "configs" / "toy.json").write_text(json.dumps(dict(conf, name="toy")))
    (b / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"arrival": "poisson", "population_seed": 1}))
    (b / "paths" / "toy_path.py").write_text(
        "def setup(ctx):\n    return ctx\n"
        "def unit(state, key):\n    return {'work': 7, 'out': {}}\n"
        "def free(state):\n    pass\n"
        "def reference(state, key, dt):\n    return {}\n"
        "def compare(state, key, got):\n    return {'same': 0.0}\n")
    (b / "workloads" / "toy-cell.json").write_text(json.dumps(
        {"path": "toy_path", "limits": {"same": 0.0}}))
    (b / "layer_metrics" / "toy_ms.py").write_text(
        "def value(reduced, record):\n    return None\n")
    bm["configs"].append({"name": "toy", "source": "x",
                          "file": "bench/configs/toy.json", "reduced": [],
                          "why": "x"})
    bm["workloads"].append({"name": "toy-cell", "config": "toy",
                            "traffic": "toy-mix", "chips": 1, "why": "x"})
    bm["end_to_end"][0].setdefault("workloads", []).append("toy-cell")
    bm["per_layer"].append({"name": "toy_ms", "unit": "ms",
                            "better": "lower", "source": "device_trace",
                            "layer": "toy", "moves":
                            bm["end_to_end"][0]["name"],
                            "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    script = (
        "import sys, time, jax\n"
        "sys.path.insert(0, 'bench')\n"
        "import harness\n"
        "c = harness.load_cell('toy-cell')\n"
        "r = harness.run(c, 5, 0.0, False, jax.devices(), time.perf_counter())\n"
        "print(sorted(c.readers), r['correct'], r['attempted'])\n")
    proc = subprocess.run([sys.executable, "-c", script], cwd=root,
                          capture_output=True, text=True, timeout=300,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stderr
    last = proc.stdout.strip().splitlines()[-1]
    assert "'toy_ms'" in last and last.endswith("True 7")
