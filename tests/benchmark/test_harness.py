"""``BENCHMARK.json`` against the files it names, and the entry point's
refusal to run without a TPU."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_config_mix_and_driver(cell):
    r = harness.resolve_cell(BENCH, cell)
    assert r["config_entry"]["file"].startswith("benchmarks/configs/")
    assert r["config"]["reduced"] == r["config_entry"]["reduced"]
    assert hasattr(r["driver"], "Driver")
    for method in ("setup", "window", "free", "check", "control_readings"):
        assert callable(getattr(r["driver"].Driver, method))
    assert set(r["traffic"]["limits"]) and r["cell"]["chips"] in (1, 4)
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCH, "per_layer", cell)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_has_a_file_a_reader_and_a_target(metric):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    spec = harness.load_json(harness.HERE, "metrics", metric + ".json")
    assert spec["layer"] == entry["layer"] and spec["moves"] == entry["moves"]
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert entry["moves"] in e2e
    for cell in entry.get("workloads", CELLS):
        assert entry["moves"] in {
            m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", cell)}
    # a reader with nothing to read returns nothing, never 0
    assert reader.read({"trace": None, "window": {}, "counters": {},
                        "peaks": None}, spec.get("params", {})) is None


def test_names_units_and_keys_are_what_the_driver_allows():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"]] + PER_LAYER
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert all(set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} for m in BENCH["per_layer"])
    assert any("mfu" in re.split(r"[._]", m) for m in PER_LAYER)
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(harness.ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (base, f)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_refuses_without_a_tpu():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert "refusing to run" in r.stderr
    assert '"correct"' not in r.stdout


def test_run_fails_in_a_directory_that_holds_only_the_benchmark(tmp_path):
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "analytics_zoo_tpu" in r.stderr and r.stdout.strip() == ""


def test_judge_needs_every_number_within_its_limit():
    ok = {"a": {"value": 0.1, "limit": 0.2}}
    assert harness.judge(ok)
    assert not harness.judge({})
    assert not harness.judge({**ok, "b": {"value": 0.3, "limit": 0.2}})
    assert not harness.judge({"a": {"value": float("nan"), "limit": 1.0}})
