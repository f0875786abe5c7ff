"""The data-driven harness: everything that belongs to one configuration,
one traffic mix or one per-layer metric is a file of its own, found by
the name ``BENCHMARK.json`` gives (benchmarks/README.md).  This file
resolves a cell to its files, refuses anything but the TPU the cell asks
for, drives the cell's driver through set-up, window and check, reads the
per-layer metrics of a traced run, and prints the result line."""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
WORK = os.path.join(ROOT, ".bench_work")      # git-ignored scratch


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return load_json(root, "BENCHMARK.json")


def resolve_cell(bench: Dict, name: str, root: str = ROOT) -> Dict:
    """A cell's entry, its configuration (entry and file), its traffic mix
    and its driver module, each found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root, entry["file"])
    traffic = load_json(root, "benchmarks", "traffic",
                        cell["traffic"] + ".json")
    driver = importlib.import_module(
        f"benchmarks.drivers.{config['pipeline']}_{config['job']}")
    return {"cell": cell, "config_entry": entry, "config": config,
            "traffic": traffic, "driver": driver}


def cell_metrics(bench: Dict, group: str, cell_name: str) -> List[Dict]:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports.  A per-layer metric without a ``workloads`` key belongs
    to every cell that reports the end-to-end metric it moves."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m: Dict) -> bool:
        if "workloads" in m:
            return cell_name in m["workloads"]
        return "moves" not in m or reports(e2e[m["moves"]])

    return [m for m in bench[group] if reports(m)]


def require_device(chips: int) -> Dict:
    """Refuse anything but a TPU with at least ``chips`` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"benchmark: needs a TPU with {chips} chip(s); JAX found "
            f"platform={devices[0].platform!r} count={len(devices)} — "
            f"refusing to run")
    return describe_device()


def describe_device() -> Dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip.  The TPU runtime keeps two pools: the
    allocator's buffers (``peak_bytes_in_use``: weights, state, inputs,
    answers) and the space it reserves for the running program's
    temporaries (``peak_bytes_reserved``: 8.1 GB of the train step's
    8.5 GB, PR 25).  A chip holds both at once, so the peak is their sum
    where the runtime reports the second."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))


def load_peaks(device_kind: str) -> Dict:
    table = load_json(HERE, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json — add it with its source")
    return table[device_kind]


class Tracer:
    """Starts and stops ``jax.profiler`` around the slice of the window a
    driver chooses; a run with ``--trace 0`` gets one that does nothing."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled, self.directory = enabled, directory
        self.running = self.done = False

    def start(self) -> None:
        if self.enabled and not self.running and not self.done:
            import jax

            # the device's lines and the benchmark's own annotations; the
            # Python tracer and the runtime's per-chunk host events made
            # a 20-step trace 850 MB (PR 25)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.running = True

    def stop(self) -> None:
        if self.running:
            import jax

            jax.profiler.stop_trace()
            self.running, self.done = False, True

    def reduction(self):
        from benchmarks import trace_reduce

        path = trace_reduce.find_xplane(self.directory) if self.done else None
        return trace_reduce.reduce_file(path) if path else None


def read_per_layer(bench: Dict, cell_name: str, ctx: Dict) -> Dict:
    """Each per-layer metric of this cell through its own reader.  A reader
    that finds nothing to read returns ``None`` and the metric is left
    out of the line."""
    out = {}
    for m in cell_metrics(bench, "per_layer", cell_name):
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(checks: Dict[str, Dict[str, float]]) -> bool:
    """``correct``: every number compared is a number and within its
    limit, and something was compared."""
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def drive(resolved: Dict, bench: Dict, seed: int, seconds: float, trace: bool,
          process_start: float, device: Dict,
          driver_args: Optional[Dict] = None, prepare=None) -> Dict:
    """Everything of a run but the look for a chip: set-up, the window,
    the peak, the per-layer metrics of a traced run, the check.  Returns
    the result line as a dict (``checks`` last).  ``prepare(driver)`` is
    the tests' way in: called on the new driver before its set-up."""
    cell, config, traffic = (resolved["cell"], resolved["config"],
                             resolved["traffic"])
    work = os.path.join(WORK, cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    tracer = Tracer(trace, os.path.join(work, "trace"))
    driver = resolved["driver"].Driver(config, traffic, seed, work,
                                       **(driver_args or {}))
    if prepare is not None:
        prepare(driver)
    driver.setup()
    window = driver.window(seconds, tracer)
    tracer.stop()
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    line: Dict[str, Any] = {"correct": False,
                            "attempted": int(window["attempted"]),
                            "failed": int(window["failed"])}
    if trace:
        red = tracer.reduction()
        ctx = {"trace": red, "config": config, "traffic": traffic,
               "window": window, "device": device,
               "counters": window.get("counters", {}),
               "peaks": load_peaks(device["kind"]) if red else None}
        line["metrics"] = read_per_layer(bench, cell["name"], ctx)
        if red is not None:
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            line["breakdown"] = {"device_ops": red.top_ops(10),
                                 "idle_gaps": red.idle_gaps(10)}
    else:
        values = dict(window["end_to_end"],
                      setup_s=window["t_open"] - process_start)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell_metrics(bench, "end_to_end", cell["name"])}
    line["device"] = device
    t0 = time.monotonic()
    driver.free()
    checks = driver.check()
    line["check_s"] = time.monotonic() - t0
    line["correct"] = judge(checks)
    line["checks"] = checks
    return line


def print_line(line: Dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: value {c['value']:.6g} limit {c['limit']:.6g} "
              f"{'ok' if c['value'] <= c['limit'] else 'OVER'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             process_start: float) -> int:
    bench = load_benchmark()
    resolved = resolve_cell(bench, workload)
    device = require_device(resolved["cell"]["chips"])
    line = drive(resolved, bench, seed, seconds, trace, process_start, device)
    print_line(line)
    return 0
