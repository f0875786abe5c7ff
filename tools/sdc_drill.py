"""One-command device-health drill: silent-data-corruption detection,
LKG rollback + elastic eviction, and straggler quarantine (ISSUE 20).

The banked execution for ``resilience.health`` — ``SDC_r01.json`` at
the repo root is its committed output.  Two segments:

1. **sdc_training** — a width-4 data-parallel regression run with the
   parity audit armed (``HealthPolicy(audit_every=4)``) under a chaos
   ``bit_flip`` fault: mid-epoch, one replica's view of the params
   grows a stuck bit.  Survival = the next audit's fingerprint vector
   names that exact replica as the minority (detection within ONE audit
   interval), ``DeviceQuarantine`` carries the suspect out of
   ``optimize()``, the suspect device is evicted
   (:func:`~analytics_zoo_tpu.resilience.health.evict_device`), and
   training resumes CHECKPOINT-FREE from the anomaly ladder's
   last-known-good tier at width 2 — finishing with finals that match a
   fault-free reference run (which also proves the audit's
   false-positive count is zero: same cadence, zero divergences).
2. **straggler_serving** — a 3-replica parallel-mode serving pool under
   a chaos ``slow_device`` window (one replica's service time ×6,
   deliberately invisible to the wedge/fence watchdogs).  Survival =
   the per-replica EWMA hysteresis ladder flags the replica only after
   ``flag_after`` consecutive outlier windows (one-shot noise never
   flags: a fault-free arm banks zero flags), the pool quarantines it
   (drain-then-retire, ``device_budget`` decremented), and tail latency
   recovers on the surviving replicas.

Both segments run TWICE and the artifact records that the replay was
byte-identical (the OBS_r02 discipline).  Everything is seeded and
virtual-/step-time based — no wall-clock, hostnames, or scratch paths
land in the artifact.

Usage::

    python tools/sdc_drill.py --smoke          # CI-sized, ~30 s CPU
    python tools/sdc_drill.py --out SDC_r01.json
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import shutil
import sys

# runnable as `python tools/<name>.py` from a checkout: the package is
# not installed, so the repo root goes on the path here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REVISION = "r01"
AUDIT_EVERY = 4
WIDTH, EVICTED_WIDTH = 4, 2
#: global batch index the stuck bit arms at (mid-epoch 1 of 8-batch
#: epochs — between audit boundaries, so detection latency is exercised)
INJECT_AT = 13
FLIP = {"replica": 2, "element": 0, "bit": 3}
#: cross-width float agreement bound for the finals comparison — the
#: precedent set by bench_scaling's elastic drill (reduction order
#: differs between widths; the trajectory must not)
REL_TOL = 1e-4


# ---------------------------------------------------------------------------
# Segment 1: SDC detection -> quarantine -> elastic LKG recovery
# ---------------------------------------------------------------------------


class LossRecorder:
    """Minimal TrainSummary stand-in (the chaos_drill idiom)."""

    def __init__(self):
        self.loss = {}          # iteration -> float (last write wins)

    def add_scalar(self, tag, value, iteration):
        if tag == "Loss":
            self.loss[int(iteration)] = float(value)


def _final_params_digest(model):
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(model.variables)
    h = hashlib.sha256()
    for leaf in leaves:
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _params_rel_diff(model_a, model_b):
    """(max |a-b|, max |b|) over the two models' variable trees."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves(model_a.variables)
    lb = jax.tree_util.tree_leaves(model_b.variables)
    max_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                   for a, b in zip(la, lb))
    max_ref = max(float(np.max(np.abs(np.asarray(b)))) for b in lb)
    return max_diff, max_ref


def sdc_training_drill(tmpdir: str, seed: int, smoke: bool) -> dict:
    import numpy as np

    from analytics_zoo_tpu.core.criterion import MSECriterion
    from analytics_zoo_tpu.core.module import Model
    from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger
    from analytics_zoo_tpu.parallel import checkpoint as ckpt
    from analytics_zoo_tpu.parallel.specs import SpecSet
    from analytics_zoo_tpu.resilience.anomaly import AnomalyPolicy
    from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec
    from analytics_zoo_tpu.resilience.errors import DeviceQuarantine
    from analytics_zoo_tpu.resilience.health import HealthPolicy, evict_device
    from flax import linen as nn
    import jax
    import jax.numpy as jnp

    if jax.device_count() < WIDTH:
        raise RuntimeError(
            f"the SDC drill needs {WIDTH} devices (virtualize with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={WIDTH}); "
            f"got {jax.device_count()}")

    dim, batch, n_batches = 4, 8, 8
    max_epoch = 4 if smoke else 6
    data_rng = np.random.RandomState(seed * 7 + 1)
    w = data_rng.randn(dim, 1).astype(np.float32)
    data = [{"input": (x := data_rng.randn(batch, dim).astype(np.float32)),
             "target": x @ w} for _ in range(n_batches)]

    def build_model():
        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, dim), jnp.float32))
        return m

    def build_opt(model, dataset, ckpt_path, specs=None):
        # the anomaly ladder is armed in EVERY arm (it owns LKG
        # promotion, and arming it changes the jitted step program —
        # identical programs keep the arms float-comparable)
        return (Optimizer(model, dataset, MSECriterion(), specs=specs)
                .set_optim_method(SGD(0.05))
                .set_checkpoint(ckpt_path, Trigger.several_iteration(2),
                                overwrite=False, keep_last=4)
                .set_anomaly_policy(AnomalyPolicy(rollback_after=3,
                                                  promote_after=2,
                                                  max_rollbacks=2))
                .set_health_policy(HealthPolicy(audit_every=AUDIT_EVERY))
                .set_end_when(Trigger.or_(Trigger.max_epoch(max_epoch),
                                          Trigger.max_wall_time(600))))

    # -- faulted arm: width 4, stuck bit on one replica's param view ------
    ckpt_path = os.path.join(tmpdir, "ckpt")
    monkey = ChaosMonkey([FaultSpec("bit_flip", INJECT_AT, detail=FLIP)],
                         checkpoint_path=ckpt_path)
    recorder = LossRecorder()
    opt1 = build_opt(build_model(), monkey.dataset(data), ckpt_path)
    opt1.set_train_summary(recorder)
    quarantine = None
    with monkey:
        try:
            opt1.optimize()
        except DeviceQuarantine as e:
            quarantine = e
    sent1 = opt1._health
    divergence = next((e for e in sent1.events
                       if e["kind"] == "audit_divergence"), None)
    detect_step = divergence["step"] if divergence else None

    # -- quarantine + eviction: rebuild on survivors, resume from LKG -----
    lkg = ckpt.lkg_snapshot(ckpt_path)
    resumed, mesh2 = None, None
    if quarantine is not None and quarantine.device is not None \
            and lkg is not None:
        suspect = int(quarantine.device)
        mesh2 = evict_device(opt1.mesh, suspect, new_width=EVICTED_WIDTH)
        # checkpoint-free recovery: the LKG tier slot is deliberately NOT
        # a normal resume candidate, so publish its exact bytes as the
        # fresh post-eviction root's "latest" — the rebuilt Optimizer's
        # ordinary set_resume path restores it and _apply_resume_meta
        # performs the elastic sample-coordinate re-seek (the snapshot's
        # meta carries world_width=4 + samples_in_epoch)
        root2 = os.path.join(tmpdir, "ckpt_evicted")
        os.makedirs(root2)
        shutil.copytree(lkg[0], os.path.join(root2, "latest"))
        resumed = {
            "from_tier": "lkg",
            "iteration": int(lkg[1]["meta"].get("iteration", 0)),
            "epoch": int(lkg[1]["meta"].get("epoch", 0)),
            "samples_in_epoch": int(
                lkg[1]["meta"].get("samples_in_epoch", 0)),
            "saved_world_width": int(lkg[1]["meta"].get("world_width", 0)),
            "resumed_world_width": EVICTED_WIDTH,
        }
        opt2 = build_opt(build_model(), data, root2,
                         specs=SpecSet(mesh2))
        opt2.set_train_summary(recorder).set_resume()
        model_faulted = opt2.optimize()
        sent2 = opt2._health

    # -- fault-free reference arm: width 4, audit armed, no chaos ---------
    ref_recorder = LossRecorder()
    opt_ref = build_opt(build_model(), data,
                        os.path.join(tmpdir, "ckpt_ref"))
    opt_ref.set_train_summary(ref_recorder)
    model_ref = opt_ref.optimize()
    sent_ref = opt_ref._health

    iters = sorted(recorder.loss)
    ref_iters = sorted(ref_recorder.loss)
    max_diff, max_ref = ((_params_rel_diff(model_faulted, model_ref))
                         if resumed is not None else (float("inf"), 1.0))
    latency = (detect_step - INJECT_AT) if detect_step is not None else None
    checks = {
        "quarantine_raised": isinstance(quarantine, DeviceQuarantine),
        "suspect_is_injected_replica": (
            quarantine is not None
            and int(quarantine.device) == FLIP["replica"]),
        "audit_named_minority_device": (
            divergence is not None
            and divergence["minority"] == [FLIP["replica"]]
            and len(set(divergence["fingerprints"])) == 2),
        "detected_within_one_audit_interval": (
            latency is not None and 0 < latency <= AUDIT_EVERY),
        "resumed_from_lkg_tier_checkpoint_free": (
            resumed is not None and resumed["iteration"] > 0),
        "elastic_width_change": (
            resumed is not None
            and resumed["saved_world_width"] == WIDTH
            and resumed["resumed_world_width"] == EVICTED_WIDTH),
        "training_completed_at_reduced_width": (
            resumed is not None and iters
            and iters[-1] == max_epoch * n_batches),
        "finals_match_fault_free_reference": max_diff <= REL_TOL * max(
            max_ref, 1e-6),
        "fault_free_false_positives_zero": (
            sent_ref.stats()["audit_divergences"] == 0
            and sent_ref.stats()["quarantines"] == 0
            and sent_ref.stats()["audits"] > 0),
        "post_eviction_audits_clean": (
            resumed is not None
            and sent2.stats()["audit_divergences"] == 0
            and sent2.stats()["audits"] > 0),
    }
    return {
        "config": {"dim": dim, "batch": batch, "n_batches": n_batches,
                   "max_epoch": max_epoch, "world_width": WIDTH,
                   "audit_every": AUDIT_EVERY,
                   "checkpoint_every_iters": 2, "rel_tol": REL_TOL},
        "fault": {"kind": "bit_flip", "at_batch": INJECT_AT, **FLIP},
        "chaos_events": monkey.events,
        "detection": {
            "step": detect_step,
            "latency_steps": latency,
            "suspect": (int(quarantine.device)
                        if quarantine is not None else None),
            "minority": (divergence or {}).get("minority"),
            "fingerprints": (divergence or {}).get("fingerprints"),
        },
        "eviction": {
            "evicted_device": (int(quarantine.device)
                               if quarantine is not None else None),
            "new_width": (EVICTED_WIDTH if mesh2 is not None else None),
            "survivors": (len(list(mesh2.devices.flat))
                          if mesh2 is not None else None),
        },
        "resume": resumed,
        "sentinel_faulted": sent1.stats(),
        "sentinel_post_eviction": (sent2.stats()
                                   if resumed is not None else None),
        "sentinel_fault_free": sent_ref.stats(),
        "finals": {
            "iterations_faulted": iters[-1] if iters else 0,
            "iterations_reference": ref_iters[-1] if ref_iters else 0,
            "loss_final_faulted": (round(recorder.loss[iters[-1]], 8)
                                   if iters else None),
            "loss_final_reference": (
                round(ref_recorder.loss[ref_iters[-1]], 8)
                if ref_iters else None),
            "params_max_abs_diff": max_diff,
            "params_ref_max_abs": max_ref,
            "params_digest_faulted": (_final_params_digest(model_faulted)
                                      if resumed is not None else None),
            "params_digest_reference": _final_params_digest(model_ref),
        },
        "checks": {"ok": all(checks.values()), **checks},
    }


# ---------------------------------------------------------------------------
# Segment 2: straggler detection -> serving quarantine -> goodput recovery
# ---------------------------------------------------------------------------


def straggler_serving_drill(seed: int, smoke: bool) -> dict:
    import numpy as np

    from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec
    from analytics_zoo_tpu.resilience.health import (HealthPolicy,
                                                     HealthSentinel)
    from analytics_zoo_tpu.serving import ServingRuntime, VirtualClock
    from analytics_zoo_tpu.serving.ladder import ServingTier

    n = 240 if smoke else 480
    service_s = 0.05            # per-dispatch service at every replica
    mean_gap_s = 0.045          # offered ~22 req/s vs 60 (40 post-evict:
                                # utilization 0.55, so queueing noise
                                # cannot mask the recovery signal)
    slow_from = n // 4          # dispatch index the slow window opens at
    slow_x = 6.0
    policy = HealthPolicy(straggler_factor=2.0, straggler_alpha=0.25,
                          flag_after=3, clear_after=2, warmup_obs=2,
                          evict=True, max_evictions=1)

    def fwd(batch):
        return np.zeros((np.asarray(batch["input"]).shape[0], 1),
                        np.float32)

    def run_once(with_fault: bool):
        clock = VirtualClock()
        faults = ([FaultSpec("slow_device", slow_from, batches=10**6,
                             detail={"replica": 2, "slow_x": slow_x})]
                  if with_fault else [])
        monkey = ChaosMonkey(faults)
        sentinel = HealthSentinel(policy)
        rt = ServingRuntime(
            [ServingTier("fp", fwd, speed=1.0)], n_replicas=3,
            clock=clock, queue_capacity=n, max_batch=1,
            default_deadline_s=5.0,
            service_time=lambda edge, n_, tier: service_s,
            decision_every=10**9, shed_expired=False, chaos=monkey,
            health=sentinel, parallel_replicas=True, device_budget=3)
        rng = random.Random(seed)
        arrivals, t = [], 0.0
        for _ in range(n):
            t += rng.expovariate(1.0 / mean_gap_s)
            arrivals.append(t)
        i = 0
        while i < n:
            now = clock.now()
            if now < arrivals[i]:
                if rt.pump() == 0:
                    ev = rt.next_event_t()
                    target = (arrivals[i] if ev is None
                              else min(ev, arrivals[i]))
                    clock.advance(max(target - now, 1e-9))
                continue
            while i < n and clock.now() >= arrivals[i]:
                rt.submit({"input": np.zeros((1, 4), np.float32)},
                          deadline_s=5.0)
                i += 1
            rt.pump()
        for _ in range(100_000):
            if len(rt.queue) == 0:
                break
            if rt.pump() == 0:
                ev = rt.next_event_t()
                clock.advance(max((ev - clock.now()) if ev is not None
                                  else 0.05, 1e-9))
        rt.drain()
        return rt, monkey, sentinel

    rt, monkey, sentinel = run_once(with_fault=True)
    acct = rt.accounting()
    pool_events = rt.pool.events
    quarantined = [e for e in pool_events
                   if e["kind"] == "replica_quarantined"]
    retired = [e for e in pool_events if e["kind"] == "replica_retired"]
    flagged = [e for e in sentinel.events
               if e["kind"] == "straggler_flagged"]
    slow_hits = [e for e in monkey.events if e["kind"] == "slow_device"]

    done = sorted((r for r in rt.requests if r.state == "done"),
                  key=lambda r: r.completed_t)
    latencies = [r.completed_t - r.arrival_t for r in done]
    tail = latencies[-50:]
    t_q = quarantined[0]["t"] if quarantined else None
    degraded = ([r.completed_t - r.arrival_t for r in done
                 if r.completed_t <= t_q] if t_q is not None else [])

    # fault-free arm: the hysteresis ladder must stay silent (the
    # straggler false-positive count the artifact banks as zero)
    rt0, _, sentinel0 = run_once(with_fault=False)
    acct0 = rt0.accounting()

    checks = {
        "all_requests_accounted": (acct["unaccounted"] == 0
                                   and acct0["unaccounted"] == 0),
        "slow_device_window_fired": bool(slow_hits),
        "slow_service_observed": bool(latencies) and max(
            latencies) >= 0.9 * slow_x * service_s,
        "flagged_only_after_hysteresis": (
            len(flagged) == 1
            and flagged[0]["device"] == 2
            and flagged[0]["streak"] == policy.flag_after),
        "quarantined_replica_drained_and_retired": (
            len(quarantined) == 1
            and quarantined[0]["replica"] == 2
            and quarantined[0]["reason"] == "straggler"
            and any(e["replica"] == 2 for e in retired)),
        "device_budget_decremented": (
            quarantined and quarantined[0]["device_budget"] == 2
            and rt.pool.device_budget == 2),
        "quarantine_within_run": (
            t_q is not None and done
            and t_q < done[-1].completed_t),
        "goodput_recovered_on_survivors": (
            bool(tail) and bool(degraded)
            and sum(tail) / len(tail) <= 2.0 * service_s
            and sum(tail) / len(tail) < max(degraded)),
        "fault_free_no_flags": (sentinel0.stats()["straggler_flags"] == 0
                                and sentinel0.stats()["quarantines"] == 0),
        "single_eviction_budget_respected": (
            sentinel.stats()["quarantines"] == 1
            and sentinel.stats()["straggler_flags"] == 1),
    }
    return {
        "config": {"n_requests": n, "n_replicas": 3, "device_budget": 3,
                   "service_s": service_s, "mean_gap_s": mean_gap_s,
                   "slow_from_dispatch": slow_from, "slow_x": slow_x,
                   "policy": {"straggler_factor": policy.straggler_factor,
                              "straggler_alpha": policy.straggler_alpha,
                              "flag_after": policy.flag_after,
                              "clear_after": policy.clear_after,
                              "warmup_obs": policy.warmup_obs}},
        "accounting": acct,
        "accounting_fault_free": acct0,
        "sentinel": sentinel.stats(),
        "sentinel_fault_free": sentinel0.stats(),
        "flag_events": flagged,
        "quarantine_events": quarantined,
        "retire_events": retired,
        "slow_dispatches_hit": len(slow_hits),
        "latency": {
            "mean_degraded_s": (round(sum(degraded) / len(degraded), 6)
                                if degraded else None),
            "max_s": round(max(latencies), 6) if latencies else None,
            "mean_tail50_s": (round(sum(tail) / len(tail), 6)
                              if tail else None),
        },
        "checks": {"ok": all(checks.values()), **checks},
    }


# ---------------------------------------------------------------------------


def _digest(result: dict) -> str:
    return hashlib.sha256(
        json.dumps(result, sort_keys=True).encode()).hexdigest()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=f"SDC_{REVISION}.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer epochs/requests)")
    args = ap.parse_args(argv)

    # BEFORE jax loads: CPU backend + 4 virtual devices (the same
    # process-level virtualization bench_scaling's elastic drill uses)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={WIDTH}"
        ).strip()

    import tempfile

    # both segments run twice (fresh scratch, same seed): the banked
    # claim is that the whole drill replays byte-identically
    def sdc_once():
        with tempfile.TemporaryDirectory() as td:
            return sdc_training_drill(td, args.seed, args.smoke)

    sdc = sdc_once()
    sdc_replay = _digest(sdc_once()) == _digest(sdc)
    straggler = straggler_serving_drill(args.seed, args.smoke)
    straggler_replay = (_digest(straggler_serving_drill(
        args.seed, args.smoke)) == _digest(straggler))

    from analytics_zoo_tpu.obs import run_metadata

    kinds = sorted({e["kind"] for e in sdc["chaos_events"]}
                   | ({"slow_device"}
                      if straggler["slow_dispatches_hit"] else set()))
    survived = (sdc["checks"]["ok"] and straggler["checks"]["ok"]
                and sdc_replay and straggler_replay)
    report = {
        "drill": "sdc_drill",
        "revision": REVISION,
        "seed": args.seed,
        "smoke": bool(args.smoke),
        "run_metadata": run_metadata("sdc_drill", seed=args.seed,
                                     extra={"smoke": bool(args.smoke)}),
        "sdc_training": sdc,
        "straggler_serving": straggler,
        "fault_kinds_survived": kinds,
        "replay": {"sdc_identical": bool(sdc_replay),
                   "straggler_identical": bool(straggler_replay),
                   "sdc_digest": _digest(sdc),
                   "straggler_digest": _digest(straggler)},
        "verdict": "PASS" if survived else "FAIL",
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    det = sdc["detection"]
    print(f"sdc drill: {report['verdict']} — bit_flip on replica "
          f"{FLIP['replica']} detected at step {det['step']} "
          f"(latency {det['latency_steps']} <= {AUDIT_EVERY}), evicted, "
          f"LKG resume at width {EVICTED_WIDTH} "
          f"(params diff {sdc['finals']['params_max_abs_diff']:.2e}); "
          f"straggler flagged after {straggler['config']['policy']['flag_after']} "
          f"windows, quarantined, tail latency "
          f"{straggler['latency']['mean_tail50_s']}s; "
          f"replay sdc={sdc_replay} straggler={straggler_replay}; "
          f"wrote {args.out}")
    return 0 if report["verdict"] == "PASS" else 1


if __name__ == "__main__":
    raise SystemExit(main())
