"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last on
standard output.  Needs a TPU with as many chips as the cell asks for;
anything else is a non-zero exit and no result line."""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    # the program under test first: importing it places the compile cache
    # (analytics_zoo_tpu/__init__.py).  A directory that holds only the
    # benchmark fails right here, with no result line.
    import analytics_zoo_tpu  # noqa: F401

    from benchmarks import harness

    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
