#!/usr/bin/env python
"""az-analyze: the two-engine static invariant checker (ISSUE 10).

Source engine — AST rules over ``analytics_zoo_tpu/`` (one-clock,
one-placement-site, seeded-rng-only, no-host-sync-in-hot-path,
error-classes-complete), with in-source ``# az-allow: <rule> — <reason>``
waivers.  Program engine — every registered pipeline's jitted
train/eval program and the SSD/DS2 serving tiers traced to jaxprs and
audited (callbacks, TrainState donation, float64, collective
inventory vs the declared SpecSet mesh).

Usage::

    python tools/az_analyze.py --all          # both engines (tier-1)
    python tools/az_analyze.py --source       # AST rules only (fast)
    python tools/az_analyze.py --program      # jaxpr audits only
    python tools/az_analyze.py --list-rules   # the rule catalog

Diagnostics print one per line as ``file:line rule message``
(program findings as ``program:<target>:0 …``); applied waivers print
with their reasons — counted, never silent.  Exit status 1 on any
un-waived violation, 0 on a clean run.  ``docs/ANALYSIS.md`` is the
rule catalog + how-to-add-a-rule guide.
"""

import argparse
import os
import sys
import time

# static analysis traces programs, it never runs them: default to the
# CPU backend so an audit does not take the chip from a running job
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="az_analyze", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--source", action="store_true",
                   help="run the AST source engine")
    p.add_argument("--program", action="store_true",
                   help="run the jaxpr program engine")
    p.add_argument("--all", action="store_true",
                   help="run both engines (what tier-1 runs)")
    p.add_argument("--root", default=None,
                   help="source-scan root (default: the installed "
                        "analytics_zoo_tpu package)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the source-rule catalog and exit")
    args = p.parse_args(argv)

    from analytics_zoo_tpu.analysis import (SOURCE_RULES, format_violation,
                                            run_source_engine)

    if args.list_rules:
        for name, rule in sorted(SOURCE_RULES.items()):
            doc = " ".join((rule.__doc__ or "").split())
            print(f"{name}: {doc}")
        return 0

    run_source = args.source or args.all
    run_program = args.program or args.all
    if not (run_source or run_program):
        p.error("pick an engine: --source, --program, or --all")

    t0 = time.time()
    violations = []
    n_programs = 0
    if run_source:
        violations += run_source_engine(root=args.root)
    if run_program:
        from analytics_zoo_tpu.analysis.program import run_program_engine
        from analytics_zoo_tpu.analysis.targets import repo_audit_suite

        suite = repo_audit_suite()
        n_programs = len(suite)
        violations += run_program_engine(suite)

    unwaived = [v for v in violations if not v.waived]
    waived = [v for v in violations if v.waived]
    for v in unwaived:
        print(format_violation(v))
    for v in waived:
        print(format_violation(v))
    dt = time.time() - t0
    engines = "+".join(e for e, on in (("source", run_source),
                                       ("program", run_program)) if on)
    print(f"az-analyze [{engines}]: {len(unwaived)} violation(s), "
          f"{len(waived)} waived, {n_programs} program(s) audited "
          f"in {dt:.1f}s")
    return 1 if unwaived else 0


if __name__ == "__main__":
    sys.exit(main())
