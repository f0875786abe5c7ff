"""Device-health sentinel tests (ISSUE 20): fingerprint sensitivity,
minority-vote attribution, straggler hysteresis, chaos ``bit_flip``
arming, detail-key validation, the error classification pins, and the serving
pool's quarantine path.

The full multi-device story (bit-flip detected within one audit
interval → quarantine → eviction → LKG resume at reduced width) needs
4 virtual devices and is banked by ``tools/sdc_drill.py`` →
``SDC_r01.json`` (claims pinned in ``tests/test_tools.py``); here the
pieces are unit-tested host-side and on the single tier-1 device.
"""

import numpy as np
import pytest

from analytics_zoo_tpu.resilience.health import (
    AuditVerdict,
    HealthPolicy,
    HealthSentinel,
    active_bit_flip,
    arm_bit_flip,
    clear_bit_flip,
    evict_device,
    make_audit_fn,
    tree_fingerprint,
)


class TestHealthPolicy:
    def test_defaults_are_off(self):
        p = HealthPolicy()
        assert p.audit_every == 0 and p.shadow_every == 0

    @pytest.mark.parametrize("kw", [
        {"audit_every": -1},
        {"shadow_every": -1},
        {"shadow_device": 0},
        {"straggler_factor": 1.0},
        {"straggler_alpha": 0.0},
        {"straggler_alpha": 1.5},
        {"flag_after": 0},
        {"clear_after": 0},
        {"warmup_obs": -1},
        {"max_evictions": -1},
    ])
    def test_validation(self, kw):
        with pytest.raises(ValueError):
            HealthPolicy(**kw)

    def test_optimizer_default_policy_audits(self):
        from flax import linen as nn
        import jax.numpy as jnp

        from analytics_zoo_tpu.core.criterion import MSECriterion
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import Optimizer

        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        opt = Optimizer(m, [], MSECriterion()).set_health_policy()
        assert opt.health_policy.audit_every == 8
        # an un-armed Optimizer carries no policy at all (default off:
        # every legacy banked drill replays byte-identically)
        opt2 = Optimizer(m, [], MSECriterion())
        assert opt2.health_policy is None


class TestFingerprint:
    def test_deterministic_and_bit_sensitive(self):
        import jax

        tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": np.ones((5,), np.float32)}
        f = jax.jit(tree_fingerprint)
        w1, w2 = int(f(tree)), int(f(tree))
        assert w1 == w2
        # one single-bit change in one element must change the word
        flipped = {"a": tree["a"].copy(), "b": tree["b"]}
        raw = flipped["a"].view(np.uint32)
        raw[0, 0] ^= np.uint32(1 << 3)
        assert int(f(flipped)) != w1

    def test_sign_bit_flip_at_odd_index_changes_word(self):
        # regression pin: the pre-fix weight idx·K + (2k+1) was EVEN at
        # every odd flat index (odd·odd + odd), so 2^31·w ≡ 0 mod 2^32
        # and the fold was blind to float32 sign-bit SDC at half of all
        # positions; odd-forced weights make every bit land
        import jax

        tree = {"a": np.arange(8, dtype=np.float32)}
        f = jax.jit(tree_fingerprint)
        clean = int(f(tree))
        for idx in (1, 3, 5, 7):
            flipped = {"a": tree["a"].copy()}
            flipped["a"].view(np.uint32)[idx] ^= np.uint32(1 << 31)
            assert int(f(flipped)) != clean, f"blind to sign bit @ {idx}"

    def test_every_single_bit_flip_changes_word(self):
        # exhaustive single-bit sensitivity over a small two-leaf tree:
        # all (leaf, element, bit) corruptions must perturb the fold
        import jax

        tree = {"a": np.arange(6, dtype=np.float32),
                "b": np.ones((3,), np.float32)}
        f = jax.jit(tree_fingerprint)
        clean = int(f(tree))
        for leaf in ("a", "b"):
            for idx in range(tree[leaf].size):
                for bit in range(32):
                    t = {k: v.copy() for k, v in tree.items()}
                    t[leaf].view(np.uint32)[idx] ^= np.uint32(1 << bit)
                    assert int(f(t)) != clean, (leaf, idx, bit)

    def test_traced_flip_matches_manual_flip(self):
        import jax
        import jax.numpy as jnp

        tree = {"a": np.arange(8, dtype=np.float32)}
        manual = {"a": tree["a"].copy()}
        manual["a"].view(np.uint32)[2] ^= np.uint32(1 << 7)

        def with_flip(t, on):
            return tree_fingerprint(
                t, flip=(jnp.uint32(2), jnp.uint32(7), on))

        f = jax.jit(with_flip)
        assert int(f(tree, jnp.bool_(True))) == int(
            jax.jit(tree_fingerprint)(manual))
        assert int(f(tree, jnp.bool_(False))) == int(
            jax.jit(tree_fingerprint)(tree))

    def test_audit_fn_names_minority_device(self):
        from analytics_zoo_tpu.parallel import mesh as mesh_lib
        import jax.numpy as jnp

        mesh = mesh_lib.create_mesh()
        audit = make_audit_fn(mesh)
        params = {"w": np.arange(6, dtype=np.float32)}
        width = mesh.devices.size
        clean = np.asarray(audit(params, jnp.int32(-1), jnp.int32(0),
                                 jnp.int32(0)))
        assert clean.shape == (width,)
        assert len(set(int(v) for v in clean)) == 1
        if width < 3:
            return   # no strict majority possible below width 3
        # flipping replica 2's view diverges only its fingerprint, and
        # the sentinel's majority vote names it
        flipped = np.asarray(audit(params, jnp.int32(2), jnp.int32(0),
                                   jnp.int32(3)))
        assert int(flipped[2]) != int(clean[2])
        assert all(int(flipped[i]) == int(clean[i])
                   for i in range(width) if i != 2)
        v = HealthSentinel().observe_audit(0, [int(x) for x in flipped])
        assert not v.ok and v.suspect == 2
        # sign-bit SDC at an ODD element — the pre-fix even-weight
        # blind spot — must diverge the target replica just the same
        sign = np.asarray(audit(params, jnp.int32(1), jnp.int32(3),
                                jnp.int32(31)))
        assert int(sign[1]) != int(clean[1])
        assert all(int(sign[i]) == int(clean[i])
                   for i in range(width) if i != 1)

    def test_audit_fn_rejects_hybrid_mesh(self):
        from analytics_zoo_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh(mesh_shape=(-1, 1),
                                    axis_names=("data", "model"))
        with pytest.raises(ValueError, match="pure data-parallel"):
            make_audit_fn(mesh)

    def test_evict_only_device_rejected(self):
        from analytics_zoo_tpu.parallel import mesh as mesh_lib
        import jax

        mesh = mesh_lib.create_mesh(
            mesh_shape=(1,), devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="only device"):
            evict_device(mesh, 0)


class TestAuditVoting:
    def test_all_equal_is_ok(self):
        s = HealthSentinel()
        v = s.observe_audit(8, [7, 7, 7, 7])
        assert v.ok and v.suspect is None
        assert s.stats()["audits"] == 1
        assert s.stats()["audit_divergences"] == 0

    def test_single_minority_named(self):
        s = HealthSentinel()
        v = s.observe_audit(8, [7, 7, 9, 7])
        assert not v.ok and not v.ambiguous
        assert v.suspect == 2
        assert s.events[0]["kind"] == "audit_divergence"
        assert s.events[0]["minority"] == [2]

    def test_two_way_tie_is_ambiguous(self):
        s = HealthSentinel()
        v = s.observe_audit(8, [7, 9, 7, 9])
        assert not v.ok and v.ambiguous and v.suspect is None

    def test_multiple_divergers_are_ambiguous(self):
        s = HealthSentinel()
        v = s.observe_audit(8, [7, 9, 8, 7])
        assert not v.ok and v.ambiguous and v.suspect is None

    def test_two_replica_disagreement_is_ambiguous(self):
        # width 2: no strict majority — eviction cannot be attributed
        s = HealthSentinel()
        v = s.observe_audit(8, [7, 9])
        assert not v.ok and v.ambiguous and v.suspect is None


class TestShadowVoting:
    def test_match_is_ok(self):
        s = HealthSentinel()
        assert s.observe_shadow(4, 11, 11, device=1).ok
        assert s.stats()["shadow_checks"] == 1

    def test_tiebreak_blames_shadow(self):
        s = HealthSentinel()
        v = s.observe_shadow(4, 11, 13, device=2, tiebreak_fp=11)
        assert not v.ok and v.suspect == 2

    def test_tiebreak_blames_primary(self):
        s = HealthSentinel()
        v = s.observe_shadow(4, 11, 13, device=2, tiebreak_fp=13)
        assert not v.ok and v.suspect == 0

    def test_no_tiebreak_is_ambiguous(self):
        s = HealthSentinel()
        v = s.observe_shadow(4, 11, 13, device=1)
        assert not v.ok and v.ambiguous and v.suspect is None


class TestStragglerHysteresis:
    def _warm(self, s, devices=(0, 1, 2), t=0.05, rounds=3):
        for _ in range(rounds):
            for d in devices:
                assert s.observe_step_time(d, t) is None

    def test_flags_only_after_consecutive_outliers(self):
        pol = HealthPolicy(straggler_factor=2.0, flag_after=3,
                           warmup_obs=2, straggler_alpha=1.0)
        s = HealthSentinel(pol)
        self._warm(s)
        # two outlier windows: under flag_after, no flag yet
        assert s.observe_step_time(2, 0.5) is None
        assert s.observe_step_time(2, 0.5) is None
        # third consecutive: flagged, exactly once
        assert s.observe_step_time(2, 0.5) == 2
        assert s.observe_step_time(2, 0.5) is None   # no re-return
        assert s.flagged() == [2]
        assert s.stats()["straggler_flags"] == 1
        ev = [e for e in s.events if e["kind"] == "straggler_flagged"]
        assert len(ev) == 1 and ev[0]["streak"] == pol.flag_after

    def test_one_shot_noise_never_flags(self):
        pol = HealthPolicy(straggler_factor=2.0, flag_after=3,
                           clear_after=2, warmup_obs=2,
                           straggler_alpha=1.0)
        s = HealthSentinel(pol)
        self._warm(s)
        for _ in range(5):   # isolated spikes separated by clean windows
            assert s.observe_step_time(1, 0.5) is None
            assert s.observe_step_time(1, 0.05) is None
            assert s.observe_step_time(1, 0.05) is None
        assert s.flagged() == [] and s.stats()["straggler_flags"] == 0

    def test_clear_after_clean_windows_unflags(self):
        pol = HealthPolicy(straggler_factor=2.0, flag_after=2,
                           clear_after=2, warmup_obs=1,
                           straggler_alpha=1.0)
        s = HealthSentinel(pol)
        self._warm(s, rounds=2)
        assert s.observe_step_time(2, 0.5) is None
        assert s.observe_step_time(2, 0.5) == 2
        assert s.observe_step_time(2, 0.05) is None
        assert s.observe_step_time(2, 0.05) is None
        assert s.flagged() == []
        assert any(e["kind"] == "straggler_cleared" for e in s.events)

    def test_warmup_observations_ignored(self):
        pol = HealthPolicy(straggler_factor=2.0, flag_after=1,
                           warmup_obs=3, straggler_alpha=1.0)
        s = HealthSentinel(pol)
        for _ in range(4):   # peers must be past their own warm-up
            for d in (0, 1):
                assert s.observe_step_time(d, 0.05) is None
        # device 2's first 3 observations are warm-up even though they
        # are huge outliers vs the warmed peers
        for _ in range(3):
            assert s.observe_step_time(2, 1.0) is None
        assert s.observe_step_time(2, 1.0) == 2

    def test_eviction_budget(self):
        s = HealthSentinel(HealthPolicy(max_evictions=1))
        assert s.eviction_budget_left
        s.note_quarantine(2, "parity_audit")
        assert not s.eviction_budget_left
        assert s.stats()["quarantines"] == 1

    def test_quarantine_drops_device_from_fleet_median(self):
        # regression pin: a retired device's inflated EWMA must not
        # keep counting as a peer — with device 2's 1.0s EWMA still in
        # the pool, device 0's 0.12s would sit under the skewed median
        # (0.525s × factor) and the outlier would be masked
        pol = HealthPolicy(straggler_factor=2.0, flag_after=1,
                           warmup_obs=1, straggler_alpha=1.0)
        s = HealthSentinel(pol)
        for _ in range(2):
            assert s.observe_step_time(0, 0.05) is None
            assert s.observe_step_time(1, 0.05) is None
            s.observe_step_time(2, 1.0)
        assert s.flagged() == [2]
        s.note_quarantine(2, "straggler")
        assert 2 not in s._ewma and 2 not in s._obs
        assert s.observe_step_time(0, 0.12) == 0


class TestFaultSpecDetailValidation:
    def test_typod_key_rejected_with_accepted_set(self):
        from analytics_zoo_tpu.resilience.chaos import FaultSpec

        with pytest.raises(ValueError) as ei:
            FaultSpec("slow_forward", 3, detail={"replica": 1,
                                                 "dealy_s": 5.0})
        assert "dealy_s" in str(ei.value)
        assert "delay_s" in str(ei.value)   # the accepted set is named

    def test_detail_on_detail_free_kind_rejected(self):
        from analytics_zoo_tpu.resilience.chaos import FaultSpec

        with pytest.raises(ValueError, match="(none)"):
            FaultSpec("crash", 3, detail={"replica": 1})

    def test_valid_details_accepted(self):
        from analytics_zoo_tpu.resilience.chaos import FaultSpec

        FaultSpec("slow_forward", 1, detail={"replica": 0, "delay_s": 2.0})
        FaultSpec("bit_flip", 1, detail={"replica": 2, "element": 0,
                                         "bit": 3})
        FaultSpec("slow_device", 1, batches=9,
                  detail={"replica": 1, "slow_x": 6.0})
        FaultSpec("burst_load", 1, batches=9, detail={"rate_x": 4.0})


class TestErrorClasses:
    def test_device_quarantine_retryable_with_suspect(self):
        from analytics_zoo_tpu.resilience.errors import (
            _RETRYABLE_CLASSES, DeviceQuarantine, is_retryable)

        e = DeviceQuarantine("replica 2 corrupt", device=2)
        assert DeviceQuarantine in _RETRYABLE_CLASSES
        assert is_retryable(e)
        assert e.device == 2

    def test_sdc_detected_is_fatal(self):
        from analytics_zoo_tpu.resilience.errors import (
            FATAL_ERRORS, SdcDetected, is_retryable)

        assert SdcDetected in FATAL_ERRORS
        assert not is_retryable(SdcDetected("unattributable divergence"))


class TestBitFlipChaos:
    def test_wrapper_arms_and_disarm_clears(self):
        from analytics_zoo_tpu.resilience.chaos import (ChaosMonkey,
                                                        FaultSpec)

        monkey = ChaosMonkey([FaultSpec("bit_flip", 1,
                                        detail={"replica": 2,
                                                "element": 5,
                                                "bit": 3})])
        data = [{"x": np.zeros(2)} for _ in range(3)]
        with monkey:
            out = list(monkey.dataset(data))
            assert len(out) == 3
            assert active_bit_flip() == (2, 5, 3)
            assert monkey.events[0]["kind"] == "bit_flip"
            assert monkey.events[0]["replica"] == 2
        # context exit disarms the module-global hook
        assert active_bit_flip() is None

    def test_arm_returns_previous_and_clear(self):
        try:
            assert arm_bit_flip(1) is None
            assert arm_bit_flip(3, element=2, bit=7) == (1, 0, 0)
            assert active_bit_flip() == (3, 2, 7)
        finally:
            clear_bit_flip()
        assert active_bit_flip() is None


class TestReplicaPoolQuarantine:
    def _pool(self, n=3, budget=3):
        from analytics_zoo_tpu.serving import VirtualClock
        from analytics_zoo_tpu.serving.replica import Replica, ReplicaPool

        clock = VirtualClock()
        reps = [Replica(i, [lambda b: np.zeros((1, 1))], clock,
                        wedge_timeout_s=1.0) for i in range(n)]
        return ReplicaPool(reps, clock, device_budget=budget), clock

    def test_quarantine_drains_decrements_and_retires(self):
        pool, clock = self._pool()
        assert pool.quarantine(1, reason="straggler") is True
        assert pool.device_budget == 2
        ev = [e for e in pool.events
              if e["kind"] == "replica_quarantined"]
        assert ev and ev[0]["replica"] == 1
        assert ev[0]["reason"] == "straggler"
        assert ev[0]["device_budget"] == 2
        # idle drained replica retires on the next pool sweep
        clock.advance(0.01)
        assert [r.rid for r in pool.healthy()] == [0, 2]
        assert any(e["kind"] == "replica_retired" and e["replica"] == 1
                   for e in pool.events)

    def test_quarantine_is_idempotent(self):
        pool, _ = self._pool()
        assert pool.quarantine(1) is True
        assert pool.quarantine(1) is False    # already draining
        assert pool.quarantine(99) is False   # unknown rid
        assert pool.device_budget == 2        # decremented exactly once


class TestServingHealthFeed:
    def test_injected_delay_and_warm_tax_do_not_flag(self):
        # regression pin: the straggler EWMA must see only the SERVICE
        # component — a replica paying chaos slow_forward delays (and
        # cold-start warm taxes) is healthy silicon, and eviction is
        # irreversible.  Pre-fix, elapsed = delay + tax + service fed
        # the ladder and replica 2 here was falsely quarantined.
        import random

        from analytics_zoo_tpu.resilience.chaos import (ChaosMonkey,
                                                        FaultSpec)
        from analytics_zoo_tpu.serving import ServingRuntime, VirtualClock
        from analytics_zoo_tpu.serving.ladder import ServingTier

        n, service_s = 90, 0.05

        def fwd(batch):
            return np.zeros((np.asarray(batch["input"]).shape[0], 1),
                            np.float32)

        clock = VirtualClock()
        monkey = ChaosMonkey([FaultSpec(
            "slow_forward", 0, batches=10**6,
            detail={"replica": 2, "delay_s": 0.2})])
        sentinel = HealthSentinel(HealthPolicy(
            straggler_factor=2.0, straggler_alpha=0.25, flag_after=2,
            warmup_obs=1, evict=True, max_evictions=1))
        rt = ServingRuntime(
            [ServingTier("fp", fwd, speed=1.0)], n_replicas=3,
            clock=clock, queue_capacity=n, max_batch=1,
            default_deadline_s=30.0,
            service_time=lambda edge, n_, tier: service_s,
            decision_every=10**9, shed_expired=False, chaos=monkey,
            health=sentinel, parallel_replicas=True, device_budget=3)
        rng = random.Random(0)
        t = 0.0
        arrivals = []
        for _ in range(n):
            t += rng.expovariate(1.0 / 0.045)
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
                          deadline_s=30.0)
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
        acct = rt.accounting()
        assert acct["unaccounted"] == 0
        assert sentinel.stats()["straggler_flags"] == 0
        assert sentinel.stats()["quarantines"] == 0
        assert not any(e["kind"] == "replica_quarantined"
                       for e in rt.pool.events)


class TestOptimizerHealthProgramCache:
    def test_stale_audit_programs_invalidated_per_optimize(self):
        # regression pin: _audit_fn/_shadow_fn close over the mesh and
        # forward fn — a reused Optimizer whose mesh was swapped (the
        # elastic replace_mesh path) must not audit against the stale
        # one, so optimize() drops the cache alongside the sentinel
        from flax import linen as nn
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.core.criterion import MSECriterion
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger

        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        b = jax.device_count()
        data = [{"input": np.zeros((b, 4), np.float32),
                 "target": np.zeros((b, 1), np.float32)}]
        opt = (Optimizer(m, data, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_end_when(Trigger.max_epoch(1)))
        stale = object()
        opt._audit_fn = opt._shadow_fn = stale
        opt.optimize()
        assert opt._audit_fn is None and opt._shadow_fn is None


class TestHealthMetricNames:
    def test_health_family_is_cataloged(self):
        from analytics_zoo_tpu.obs.names import lookup

        for name in ("health/audits", "health/audit_divergences",
                     "health/shadow_checks", "health/shadow_mismatches",
                     "health/straggler_flags", "health/quarantines"):
            assert lookup(name), name

    def test_sentinel_publishes_to_registry(self):
        from analytics_zoo_tpu.obs import MetricRegistry

        reg = MetricRegistry()
        s = HealthSentinel(HealthPolicy(), registry=reg)
        s.observe_audit(0, [1, 1])
        s.observe_audit(4, [1, 2, 1])
        s.observe_shadow(8, 5, 5, device=1)
        s.note_quarantine(1, "parity_audit")
        snap = reg.snapshot()
        assert snap["counters"]["health/audits"] == 2
        assert snap["counters"]["health/audit_divergences"] == 1
        assert snap["counters"]["health/shadow_checks"] == 1
        assert snap["counters"]["health/quarantines"] == 1
