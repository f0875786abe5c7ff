"""The program's stages as a table, by hand: for every ``az/`` stage its
count, median and total ms, self ms (its time less its children's on the
same thread) and, from a trace, the device-idle ms under its self time.

    python3 benchmarks/span_table.py <file.xplane.pb | jax.profiler log dir> [<ring.json>]
    python3 benchmarks/span_table.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--out <dir>]

The second form runs the cell as ``run.py`` does (result line included),
then prints the table of the window's stage ring and, for a traced run,
of the trace it wrote, and writes ``<dir>/<cell>.trace<0|1>.ring.json``.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def table(lines, gaps=None) -> str:
    """``lines``: one list of (name, start s, end s) a thread.  With the
    device's idle ``gaps`` each stage also gets the idle ms under its
    self time, and each thread a last row: the idle ms under none of ITS
    stages (threads overlap in time, so their columns do not add up)."""
    from benchmarks import program_spans

    whole, own, idle, uncovered = {}, {}, {}, []
    total = sum(e - s for s, e in gaps or [])
    for events in lines:
        pieces = program_spans.self_pieces(events)
        for name, start, end in events:
            whole.setdefault(name, []).append(end - start)
        for name, start, end, _ in pieces:
            own[name] = own.get(name, 0.0) + end - start
        under = program_spans.overlap_by_name(pieces, gaps or [])
        for name, s in under.items():
            idle[name] = idle.get(name, 0.0) + s
        first = min(events, key=lambda e: e[1])[0]
        uncovered.append((first, total - sum(under.values())))
    rows = [f"{'stage':24s} {'count':>6s} {'median ms':>10s} {'total ms':>10s} "
            f"{'self ms':>10s}" + (f" {'idle ms':>10s}" if gaps else "")]
    for name in sorted(whole):
        rows.append(
            f"{name:24s} {len(whole[name]):6d} "
            f"{1e3 * statistics.median(whole[name]):10.3f} "
            f"{1e3 * sum(whole[name]):10.3f} {1e3 * own.get(name, 0.0):10.3f}"
            + (f" {1e3 * idle.get(name, 0.0):10.3f}" if gaps else ""))
    if gaps:
        rows.append(f"device idle {1e3 * total:.3f} ms; under no stage of "
                    "the thread whose first stage is " + ", ".join(
                        f"{first}: {1e3 * s:.3f} ms"
                        for first, s in uncovered))
    return "\n".join(rows)


def trace_table(path: str) -> str:
    from benchmarks import program_spans, trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    gaps = program_spans.device_gaps(trace_reduce.reduce_file(path))
    return table(program_spans.host_lines(path), gaps)


def ring_table(records) -> str:
    """``records``: [name, t0, t1, thread, attrs] each."""
    from benchmarks import program_spans

    out = table(program_spans.ring_lines(records))
    workers = [(t1 - t0, attrs) for name, t0, t1, _, attrs in records
               if name == program_spans.WORKER]
    if workers:
        alive = sum(w[0] for w in workers)
        keys = ("chain_s", "put_s", "walk_s")
        out += (f"\n{program_spans.WORKER}: {len(workers)} records, alive "
                f"{alive:.3f} s, " + ", ".join(
                    f"{k} {sum(w[1][k] for w in workers):.3f}" for k in keys)
                + f", groups {sum(w[1]['groups'] for w in workers)}, spills "
                f"{sum(w[1]['spills'] for w in workers)}")
    return out


def run(args) -> int:
    import analytics_zoo_tpu  # noqa: F401  (places the compile cache)
    from analytics_zoo_tpu.obs import stages

    from benchmarks import harness

    start = time.monotonic()
    bench = harness.load_benchmark()
    resolved = harness.resolve_cell(bench, args.workload)
    device = harness.require_device(resolved["cell"]["chips"])
    kept = {}

    def keep_window(driver):
        inner = driver.window

        def window(seconds, tracer):
            kept["window"] = inner(seconds, tracer)
            return kept["window"]
        driver.window = window

    line = harness.drive(resolved, bench, args.seed, args.seconds,
                         bool(args.trace), start, device,
                         prepare=keep_window)
    records = [list(r) for r in stages(since=kept["window"]["t_open"])]
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.workload}.trace{args.trace}"
                           ".ring.json"), "w") as f:
        json.dump(records, f)
    print(ring_table(records))
    if args.trace:
        print(trace_table(os.path.join(harness.WORK, args.workload, "trace")))
    harness.print_line(line)
    return 0


def main() -> int:
    if len(sys.argv) > 1 and not sys.argv[1].startswith("--"):
        print(trace_table(sys.argv[1]))
        if len(sys.argv) > 2:
            with open(sys.argv[2]) as f:
                print(ring_table(json.load(f)))
        return 0
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="chiprun_out")
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
