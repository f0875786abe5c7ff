"""Readings for the limits of ``correct``: many seeds of one cell in ONE
process (set-up is most of a run), each with a short window at the cell's
own load, and after each the control and the faults that the comparison
has to fail.

    python3 benchmarks/control.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 3] [--controls 1] [--driver-args '{"tier": 1}']

One JSON line a seed on standard output and in
``chiprun_out/control.<cell>.jsonl``: the program's numbers (``program``)
and, with ``--controls 1``, each control's and fault's.  The benchmark's
own runs never call this."""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--driver-args", default="{}")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    import analytics_zoo_tpu  # noqa: F401
    import jax

    from benchmarks import harness

    bench = harness.load_benchmark()
    resolved = harness.resolve_cell(bench, args.workload)
    device = harness.require_device(resolved["cell"]["chips"])
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(harness.ROOT, "chiprun_out",
                       f"control.{args.workload}{args.tag}.jsonl")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        work = os.path.join(harness.WORK, args.workload + ".control")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        driver = resolved["driver"].Driver(
            resolved["config"], resolved["traffic"], seed, work,
            **json.loads(args.driver_args))
        driver.setup()
        window = driver.window(args.seconds, harness.Tracer(False, work))
        driver.free()
        checks = driver.check()
        row = {"seed": seed, "attempted": window["attempted"],
               "failed": window["failed"],
               "end_to_end": window["end_to_end"],
               "correct": harness.judge(checks),
               "program": driver.numbers}
        if args.controls:
            row.update(driver.control_readings())
        row["seconds"] = time.monotonic() - t0
        line = json.dumps(row)
        print(line, flush=True)
        with open(out, "a") as f:
            f.write(line + "\n")
        del driver
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
