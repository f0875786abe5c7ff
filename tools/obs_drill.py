"""One-command telemetry-spine drill: bank a seeded serve-drill flight
recording as ``OBS_r01.json``.

The serve drill's overload/failover scenario (same seeded arrival
script, burst window, replica crash + wedge, fp→int8 ladder as
``tools/serve_drill.py``) runs with the ``obs.Observability`` spine
armed: every request's life is a rooted span trace (``request`` →
``queue`` → ``dispatch``), replica fences trip the black-box dump, and
drill completion dumps the full ring.  The artifact pins (a) **span
conservation** — every request trace is one rooted tree and the root
statuses reconcile EXACTLY with ``ServingRuntime.accounting()``;
(b) **byte-identical replay** — the whole scenario runs twice from the
seed and the JSONL dump's sha256 must match (everything runs on the
VirtualClock).  What the spine costs a step is a chip reading: PERF.md
(PR 26).

Usage::

    python tools/obs_drill.py                # full drill -> OBS_r01.json
    python tools/obs_drill.py --smoke        # CI-sized (~seconds)
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REVISION = "r01"


def traced_scenario(seed: int, smoke: bool, dump_path=None,
                    make_slo=None):
    """One drill-shaped scenario (burst + crash + wedge + ladder) with
    the obs spine armed; returns ``(runtime, obs, script_len)``.
    ``make_slo(obs)`` (optional) builds a fresh
    ``analytics_zoo_tpu.obs.slo.SloEvaluator`` per run (the evaluator
    is stateful, and the replay-identity check re-runs the scenario) —
    the ladder then steps on SLO burn instead of the raw overload flag
    (``tools/az_trace.py`` banks that variant as ``OBS_r02.json``)."""
    from analytics_zoo_tpu.obs import Observability
    from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec
    from analytics_zoo_tpu.serving.ladder import LadderPolicy
    from tools.serve_drill import (build_arrival_script, drill_tiers,
                                   run_scenario)

    scale = 4 if smoke else 1
    tiers = drill_tiers(seed)
    tier_speeds = [t.speed for t in tiers]
    script, _burst = build_arrival_script(
        random.Random(seed), smoke,
        ChaosMonkey([FaultSpec("burst_load", 400 // scale,
                               batches=600 // scale,
                               detail={"rate_x": 4.0})]))
    monkey = ChaosMonkey([
        FaultSpec("replica_crash", 60 // scale, batches=4,
                  detail={"replica": 0}),
        FaultSpec("slow_forward", 120 // scale, batches=4,
                  detail={"replica": 1, "delay_s": 5.0}),
    ])
    # capacity sized so NOTHING is dropped: ~3 spans per scripted
    # request + batch spans + pool events + the post-load recovery
    # submissions run_scenario adds — conservation over a ring that
    # evicted early spans would be vacuous
    capacity = len(script) * 4 + 2048
    obs = Observability(capacity=capacity, dump_path=dump_path)
    rt = run_scenario(script, tiers, tier_speeds, shed=True, chaos=monkey,
                      queue_capacity=64,
                      ladder_policy=LadderPolicy(down_after=2, up_after=6,
                                                 depth_high=2),
                      obs=obs,
                      slo=make_slo(obs) if make_slo is not None else None)
    return rt, obs, len(script)


def obs_drill(seed: int, smoke: bool, flight_path=None) -> dict:
    from analytics_zoo_tpu.obs import render_prometheus, span_conservation

    rt, obs, n_script = traced_scenario(seed, smoke, dump_path=flight_path)
    text = obs.dump("drill_complete")
    digest = hashlib.sha256(text.encode()).hexdigest()

    # byte-identical replay: the ENTIRE flight recording re-derives from
    # the seed (virtual clock + deterministic span/trace ids)
    rt2, obs2, _ = traced_scenario(seed, smoke)
    replay_identical = (hashlib.sha256(
        obs2.dump("drill_complete").encode()).hexdigest() == digest)

    events = obs.recorder.events()
    cons = span_conservation(events)
    acct = rt.accounting()
    # root statuses must reconcile with the runtime's own accounting —
    # the span layer cannot lose or invent a request
    by_state = dict(acct["by_state"])
    reconciled = (cons["traces"] == acct["submitted"]
                  and cons["roots_by_status"] == by_state)
    fence_dumps = [d for d in obs.recorder.dumps
                   if d["reason"] == "replica_fenced"]
    fenced = [e for e in events if e.get("kind") == "replica_fenced"]

    checks = {
        "span_conservation_ok": cons["ok"],
        "roots_reconcile_with_accounting": reconciled,
        "zero_unaccounted": acct["unaccounted"] == 0,
        "nothing_dropped_from_ring": obs.recorder.dropped == 0,
        "replay_byte_identical_from_seed": replay_identical,
        "fence_tripped_black_box_dump": (bool(fence_dumps)
                                         if flight_path else bool(fenced)),
    }
    spans = [e for e in events if e.get("kind") == "span"]
    by_name = {}
    for s in spans:
        by_name[s["name"]] = by_name.get(s["name"], 0) + 1
    return {
        "serve_trace": {
            "scripted_requests": n_script,
            "submitted_total": acct["submitted"],
            "accounting": acct,
            "ring_capacity": obs.recorder.capacity,
            "events_recorded": len(events),
            "events_dropped": obs.recorder.dropped,
            "spans": len(spans),
            "spans_by_name": dict(sorted(by_name.items())),
            "conservation": cons,
            "dumps": obs.recorder.dumps,
            "trace_sha256": digest,
            "replay_identical": replay_identical,
            "events_head": events[:3],
            "events_tail": events[-2:],
        },
        "metrics_snapshot": rt.snapshot()["metrics"],
        "prometheus_sample": render_prometheus(
            obs.registry).splitlines()[:8],
        "checks": {"ok": all(checks.values()), **checks},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=f"OBS_{REVISION}.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (~500 requests, seconds of CPU)")
    ap.add_argument("--flight-out", default=None,
                    help="also write the full flight-recorder JSONL here "
                         "(the artifact itself banks counts + sha256)")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from analytics_zoo_tpu.obs import run_metadata

    result = obs_drill(args.seed, args.smoke, flight_path=args.flight_out)
    report = {
        "drill": "obs_drill",
        "revision": REVISION,
        "seed": args.seed,
        "smoke": bool(args.smoke),
        "run_metadata": run_metadata("obs_drill", seed=args.seed,
                                     extra={"smoke": bool(args.smoke)}),
        **result,
        "verdict": "PASS" if result["checks"]["ok"] else "FAIL",
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    st = report["serve_trace"]
    print(f"obs drill: {report['verdict']} — {st['spans']} spans over "
          f"{st['submitted_total']} requests "
          f"({st['conservation']['roots_by_status']}), replay identical: "
          f"{st['replay_identical']}; wrote {args.out}")
    return 0 if report["verdict"] == "PASS" else 1


if __name__ == "__main__":
    sys.exit(main())
