"""The spreads the bounds are set from, out of ``prove.sh``'s record:

    python3 benchmarks/spread.py chiprun_out/sets.<cell>.jsonl

For each end-to-end metric and each set: median, quartiles
(``statistics.quantiles(values, n=4)``) and the spread (distance between
the quartiles over the median); then the wider of the two spreads, five
times it, and how far the second set's median lies from the first's.
``setup_s`` leaves out each set's first run, which compiles."""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, q1, q3, (q3 - q1) / med


def main(path: str) -> int:
    rows = [json.loads(line) for line in open(path) if line.strip()]
    sets = {s: [r["line"] for r in rows if r["set"] == s] for s in (1, 2)}
    for s, lines in sets.items():
        bad = [l for l in lines if not l["correct"] or l["failed"]]
        print(f"set {s}: {len(lines)} runs, {len(bad)} not correct or with "
              f"failures")
    for name in sets[1][0]["metrics"]:
        out = {}
        for s, lines in sets.items():
            values = [l["metrics"][name]["value"] for l in lines]
            if name == "setup_s" and s == 1:
                print(f"{name}: first run of set 1 (compiles) {values[0]:.4g}")
                values = values[1:]
            out[s] = spread(values)
            print(f"{name} set {s}: median {out[s][0]:.6g} quartiles "
                  f"{out[s][1]:.6g} {out[s][2]:.6g} spread "
                  f"{100 * out[s][3]:.3f} % values "
                  f"{[round(v, 3) for v in values]}")
        wider = max(out[1][3], out[2][3])
        print(f"{name}: wider spread {100 * wider:.3f} %, five times "
              f"{100 * 5 * wider:.2f} %, second median / first "
              f"{out[2][0] / out[1][0]:.5f}")
    traced = [r["line"] for r in rows if r["set"] == "trace"]
    for l in traced:
        print("traced:", l["correct"], {k: round(v["value"], 4)
                                        for k, v in l["metrics"].items()},
              "busy", l["device"].get("busy_s"), "window",
              l["device"].get("window_s"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
