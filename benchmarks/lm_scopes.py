"""A look at a decoder-LM trace by hand:

    python3 benchmarks/lm_scopes.py <jax.profiler log dir | file.xplane.pb>

the runs and device seconds of the two step programs, the traced slice's
busy and whole seconds, and the operations that took most device time
with their whole HLO lines (how PR 28 found the sort and the gathers).
The trace's operations carry no ``op_name``, so scopes are not listed
here: ``hlo_scopes.py`` maps them from the compiled step's text."""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(path: str) -> int:
    from benchmarks import trace_reduce

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    red = trace_reduce.reduce_file(path)
    for program in ("jit_decode_step", "jit_prefill_step"):
        print(program, red.program(program))
    print("busy", red.busy_s, "window", red.window_s)
    total = collections.defaultdict(float)
    for name, _, seconds in red.devices[0].ops:
        total[name[:260]] += seconds
    for name, seconds in sorted(total.items(), key=lambda kv: -kv[1])[:16]:
        print(f"{seconds * 1e3:9.3f} ms {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
