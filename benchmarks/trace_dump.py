"""Look at a trace by hand: every plane and line of an ``.xplane.pb`` with
its event count, span and most frequent names.

    python3 benchmarks/trace_dump.py <file.xplane.pb | jax.profiler log dir> \
        [<cut.json.gz> <seconds> [<pattern>]]

With a second and third argument it also writes the first ``seconds`` of
the device's activity as a cut (``trace_reduce.cut_file``), and with a
fourth it lists the operations whose HLO line matches the pattern.
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(path: str) -> int:
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            busy = sum(e.duration_ns for e in events)
            names = collections.Counter(e.name for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{lo * 1e-9:.6f}..{hi * 1e-9:.6f} s, sum "
                  f"{busy * 1e-9:.6f} s")
            for name, n in names.most_common(8):
                total = sum(e.duration_ns for e in events if e.name == name)
                print(f"    {n:6d} x {name[:90]!r} {total * 1e-9:.6f} s")
    red = trace_reduce.reduce_file(path)
    if len(sys.argv) > 3:
        trace_reduce.cut_file(path, sys.argv[2], float(sys.argv[3]))
        print("cut", sys.argv[2], os.path.getsize(sys.argv[2]), "bytes")
    if len(sys.argv) > 4:
        print("matching", sys.argv[4], red.matching_ops(sys.argv[4]))
    print("window_s", red.window_s, "busy_s", red.busy_s)
    print("top ops", red.top_ops(10))
    print("idle gaps", red.idle_gaps(10))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
