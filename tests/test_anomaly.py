"""Training anomaly sentinel: in-graph health word, skip → rollback →
diverge ladder, forensics replay, and classification completeness.

The reference's only numerical guard is the MultiBoxLoss loss>50 skip
(``MultiBoxLoss.scala:546``); everything here is new surface (see
docs/RESILIENCE.md "Numerical anomalies").  All CPU, all fast — the
ladder smoke (`TestLadderSmoke`) runs the full skip→rollback chain on a
tiny MLP in a few seconds so it is exercised on EVERY tier-1 run, not
only in the committed drill artifact (RESILIENCE_r02.json).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

from analytics_zoo_tpu.core.criterion import MSECriterion
from analytics_zoo_tpu.core.module import Model
from analytics_zoo_tpu.data.dataset import DataSet
from analytics_zoo_tpu.parallel import (
    SGD,
    Optimizer,
    Trigger,
    create_train_state,
    make_train_step,
    run_resilient,
)
from analytics_zoo_tpu.parallel import checkpoint as cp
from analytics_zoo_tpu.resilience import anomaly as anomaly_lib
from analytics_zoo_tpu.resilience.anomaly import (
    AnomalyPolicy,
    AnomalySentinel,
    batch_fingerprint,
    decode_health,
    health_sections,
)
from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec, \
    mutate_batch
from analytics_zoo_tpu.resilience.errors import TrainingDiverged

DIM, BS = 4, 8


def _model():
    m = Model(nn.Dense(1))
    m.build(0, jnp.zeros((1, DIM), jnp.float32))
    return m


def _batch(seed=0, n=BS):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, DIM).astype(np.float32)
    return {"input": x, "target": (x @ np.ones((DIM, 1))).astype(np.float32)}


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


class TestHealthWord:
    def _step(self, **kw):
        m = _model()
        optim = SGD(0.05)
        state = create_train_state(m, optim)
        step = make_train_step(m.module, MSECriterion(), optim,
                               health_check=True, **kw)
        return m, state, step

    def test_clean_step_is_zero(self):
        _, state, step = self._step()
        _, met = step(state, _batch(), 1.0)
        assert int(met["health"]) == 0

    def test_nan_input_sets_all_bits_and_sections(self):
        m, state, step = self._step(skip_unhealthy=True)
        bad = _batch()
        bad["input"][0, 0] = np.nan
        _, met = step(state, bad, 1.0)
        rep = decode_health(int(met["health"]), health_sections(m.params))
        assert not rep["healthy"]
        assert rep["loss_nonfinite"] and rep["grads_nonfinite"] \
            and rep["params_nonfinite"]
        # per-section flags name the poisoned subtrees
        assert set(rep["bad_sections"]) == {"bias", "kernel"}

    def test_spike_bit_from_threshold(self):
        _, state, step = self._step(skip_loss_above=50.0,
                                    skip_unhealthy=True)
        spiky = _batch()
        spiky["target"] += 1e3     # huge but finite loss
        _, met = step(state, spiky, 1.0)
        rep = decode_health(int(met["health"]), ["bias", "kernel"])
        assert rep["loss_spike"] and not rep["loss_nonfinite"]
        assert not rep["grads_nonfinite"]

    def test_skip_unhealthy_keeps_state_bit_identical(self):
        """A poison batch must leave params, optimizer slots and the rng
        untouched — bit for bit."""
        _, state, step = self._step(skip_unhealthy=True)
        state, _ = step(state, _batch(), 1.0)
        before_p = _leaves(state.params)
        before_o = _leaves(state.opt_state)
        bad = _batch(1)
        bad["input"][:] = np.inf
        state, met = step(state, bad, 1.0)
        assert int(met["health"]) != 0
        assert all(np.array_equal(a, b)
                   for a, b in zip(before_p, _leaves(state.params)))
        assert all(np.array_equal(a, b)
                   for a, b in zip(before_o, _leaves(state.opt_state)))
        # and the step still advances + recovers on the next clean batch
        state, met = step(state, _batch(2), 1.0)
        assert int(met["health"]) == 0
        assert np.isfinite(float(met["loss"]))

    def test_health_sections_fallback(self):
        assert health_sections({"a": 1, "b": 2}) == ["a", "b"]
        assert health_sections(np.zeros(3)) == ["params"]

    def test_fingerprint_is_content_hash(self):
        b1, b2 = _batch(3), _batch(3)
        assert batch_fingerprint(b1) == batch_fingerprint(b2)
        b2["input"][0, 0] += 1
        assert batch_fingerprint(b1) != batch_fingerprint(b2)


class TestSentinel:
    def test_skip_then_rollback_then_diverged(self):
        s = AnomalySentinel(AnomalyPolicy(rollback_after=2,
                                          max_rollbacks=1), ["w"])
        assert s.observe(0) == ("ok", False)
        assert s.observe(1) == ("skipped", True)     # first detection
        assert s.observe(1) == ("rollback", False)   # K=2 consecutive
        s.note_rollback()
        assert s.observe(0) == ("ok", False)         # recovered
        assert s.observe(1) == ("skipped", True)     # new episode
        assert s.observe(1) == ("diverged", False)   # budget spent
        assert s.stats()["rollbacks"] == 1

    def test_spike_only_skips_but_never_escalates(self):
        """Reference semantics: a finite loss spike (routine in early
        training) skips the update and nothing more — it must not feed
        the rollback/diverge ladder."""
        spike_word = 1 << anomaly_lib.BIT_LOSS_SPIKE
        s = AnomalySentinel(AnomalyPolicy(rollback_after=2,
                                          max_rollbacks=0), ["w"])
        for _ in range(10):
            assert s.observe(spike_word) == ("skipped", False)
        assert s.consecutive_bad == 0 and s.rollbacks == 0
        assert s.stats()["spike_skips"] == 10
        # but a spike COMBINED with non-finite bits does escalate
        assert s.observe(spike_word | 1)[0] == "skipped"
        assert s.observe(spike_word | 1)[0] == "diverged"

    def test_clean_step_resets_streak(self):
        s = AnomalySentinel(AnomalyPolicy(rollback_after=3), ["w"])
        for _ in range(5):
            s.observe(1)
            s.observe(0)
        assert s.rollbacks == 0 and s.bad_steps == 5

    def test_promotion_throttled(self):
        s = AnomalySentinel(AnomalyPolicy(promote_after=3), ["w"])
        for _ in range(2):
            s.observe(0)
        assert not s.should_promote()
        s.observe(0)
        assert s.should_promote()
        s.note_promoted(step=3, snapshot="lkg")
        s.observe(0)
        assert not s.should_promote()      # throttle window
        for _ in range(2):
            s.observe(0)
        assert s.should_promote()

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            AnomalyPolicy(rollback_after=0)
        assert AnomalyPolicy(rollback_after=4).reseek == 4
        assert AnomalyPolicy(reseek_batches=9).reseek == 9


class TestErrorClassCompleteness:
    def test_every_error_class_is_classified(self):
        """Every exception class defined in resilience.errors must be
        EXPLICITLY retryable or fatal — a new class can't silently fall
        through run_resilient's filter."""
        from analytics_zoo_tpu.resilience import errors as E

        declared = {
            obj for name, obj in vars(E).items()
            if isinstance(obj, type)
            and issubclass(obj, BaseException)
            and obj.__module__ == E.__name__
        }
        assert declared, "errors module defines no error classes?"
        classified = set(E._RETRYABLE_CLASSES) | set(E.FATAL_ERRORS)
        missing = {c.__name__ for c in declared - classified}
        assert not missing, f"unclassified error classes: {missing}"
        both = set(E._RETRYABLE_CLASSES) & set(E.FATAL_ERRORS)
        assert not both, f"classes classified both ways: {both}"

    def test_training_diverged_is_fatal_not_retryable(self):
        from analytics_zoo_tpu.parallel import RETRYABLE_ERRORS
        from analytics_zoo_tpu.resilience.errors import is_retryable

        exc = TrainingDiverged("x")
        assert not isinstance(exc, RETRYABLE_ERRORS)
        assert not is_retryable(exc)
        # ... even though it subclasses RuntimeError like the retryables
        assert isinstance(exc, RuntimeError)

    def test_is_retryable_spot_checks(self):
        from analytics_zoo_tpu.resilience.errors import (
            CheckpointCorrupt, InjectedFault, Preempted, is_retryable)

        assert is_retryable(Preempted("p"))
        assert is_retryable(InjectedFault("i"))
        assert not is_retryable(CheckpointCorrupt("c"))
        assert not is_retryable(ValueError("v"))

    def test_serving_classes_pinned_retryable(self):
        """The serving-side classification (PR 5): ServerOverloaded is the
        explicit bounded-queue rejection (retry WITH backoff — a blind
        immediate retry re-creates the overload), RequestTimeout is a
        shed-before-dispatch (resubmit with a fresh deadline), and
        ReplicaWedged is fatal for the REPLICA (the pool fences it) but
        retryable for the REQUEST — the error object only ever escapes
        to request scope, so the registry pins it retryable."""
        from analytics_zoo_tpu.resilience.errors import (
            _RETRYABLE_CLASSES, ReplicaWedged, RequestTimeout,
            ServerOverloaded, is_retryable)

        for cls in (ServerOverloaded, RequestTimeout, ReplicaWedged):
            assert cls in _RETRYABLE_CLASSES
            assert is_retryable(cls("x"))
        # backoff guidance is part of the overload contract the clients
        # read — keep it in the message
        assert "backoff" in str(ServerOverloaded.__doc__).lower()

    def test_run_resilient_does_not_retry_divergence(self, tmp_path):
        attempts = []

        def build():
            attempts.append(1)
            raise TrainingDiverged("persistent divergence")

        with pytest.raises(TrainingDiverged):
            run_resilient(build, str(tmp_path / "c"), max_restarts=5)
        assert len(attempts) == 1


def _pipeline(X, Y, base_seed=5):
    return (DataSet.from_arrays(input=X, target=Y)
            .batch(BS).parallel(0, base_seed=base_seed))


def _ladder_data(n_batches=6, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(DIM, 1).astype(np.float32)
    X = rng.randn(BS * n_batches, DIM).astype(np.float32)
    return X, (X @ w).astype(np.float32)


class TestLadderSmoke:
    """Tier-1 fast path of the anomaly ladder (the full drill is the
    committed RESILIENCE_r02.json): nan_grads injection → in-graph skip
    → rollback to the promoted last-known-good snapshot."""

    def test_nan_grads_skip_then_rollback(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        X, Y = _ladder_data()
        monkey = ChaosMonkey([FaultSpec("nan_grads", 2),
                              FaultSpec("nan_grads", 8, batches=2)],
                             checkpoint_path=ckpt)
        chaos = monkey.dataset(_pipeline(X, Y))
        policy = AnomalyPolicy(rollback_after=2, promote_after=2)
        opt = (Optimizer(_model(), chaos, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_checkpoint(ckpt, Trigger.several_iteration(2),
                               overwrite=False, keep_last=3)
               .set_anomaly_policy(policy)
               .set_end_when(Trigger.max_epoch(4)))
        opt.optimize()
        sent = opt._anomaly
        stats = sent.stats()
        # single fault skipped; burst of K=2 rolled back; all updates
        # from bad steps discarded
        assert stats["bad_steps"] == 3 and stats["skipped"] == 3
        rollbacks = [e for e in sent.events if e["kind"] == "rollback"]
        assert len(rollbacks) == 1
        assert rollbacks[0]["tier"] == "lkg"
        assert rollbacks[0]["params_match_snapshot"] is True
        # forensics bundle written on each episode's FIRST bad step
        assert len(sent.forensics_paths) == 2
        bundle = json.load(open(sent.forensics_paths[0]))
        assert bundle["health_word"] != 0
        assert bundle["rng"]["base_seed"] == 5
        assert "kernel" in bundle["health"]["bad_sections"] \
            or "bias" in bundle["health"]["bad_sections"]
        # final params are finite — no NaN ever reached the state
        assert all(np.all(np.isfinite(l))
                   for l in _leaves(opt.model.variables["params"]))
        # with in-graph skip armed the state after a bad step is clean,
        # so the loop-level guards were cleared and checkpoints kept
        # flowing (snapshots exist past the last fault's iteration)
        found = cp.newest_intact(ckpt)
        assert found is not None
        assert int(found[1]["meta"]["iteration"]) > 9

    def test_failure_detector_ignored_while_sentinel_armed(self, tmp_path):
        """The legacy DivergenceDetector must not read a discarded bad
        step's NaN loss and raise fatal TrainingDiverged before the
        ladder has a chance to skip/roll back."""
        from analytics_zoo_tpu.parallel import DivergenceDetector

        ckpt = str(tmp_path / "ckpt")
        X, Y = _ladder_data()
        monkey = ChaosMonkey([FaultSpec("nan_grads", 2)],
                             checkpoint_path=ckpt)
        opt = (Optimizer(_model(), monkey.dataset(_pipeline(X, Y)),
                         MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_checkpoint(ckpt, Trigger.several_iteration(2),
                               overwrite=False, keep_last=3)
               .set_failure_detector(DivergenceDetector(check_every=1,
                                                        max_bad_checks=1))
               .set_anomaly_policy(AnomalyPolicy(rollback_after=3,
                                                 promote_after=2))
               .set_end_when(Trigger.max_epoch(2)))
        opt.optimize()                       # no TrainingDiverged raised
        assert opt._anomaly.stats()["skipped"] == 1

    def test_persistent_divergence_raises_not_retries(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        X, Y = _ladder_data()
        monkey = ChaosMonkey([FaultSpec("inf_loss", 2, batches=100)],
                             checkpoint_path=ckpt)
        chaos = monkey.dataset(_pipeline(X, Y))
        attempts = []

        def build():
            attempts.append(1)
            return (Optimizer(_model(), chaos, MSECriterion())
                    .set_optim_method(SGD(0.05))
                    .set_checkpoint(ckpt, Trigger.several_iteration(2),
                                    overwrite=False, keep_last=3)
                    .set_anomaly_policy(AnomalyPolicy(rollback_after=2,
                                                      promote_after=2,
                                                      max_rollbacks=1))
                    .set_end_when(Trigger.max_epoch(10)))

        with pytest.raises(TrainingDiverged, match="ladder exhausted"):
            run_resilient(build, ckpt, max_restarts=5)
        assert len(attempts) == 1      # fatal: never retried

    def test_rollback_without_any_snapshot_diverges(self, tmp_path):
        """No checkpoint path configured -> the ladder has no rollback
        target and must escalate instead of looping."""
        X, Y = _ladder_data()
        monkey = ChaosMonkey([FaultSpec("nan_grads", 1, batches=50)])
        chaos = monkey.dataset(_pipeline(X, Y))
        opt = (Optimizer(_model(), chaos, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_anomaly_policy(AnomalyPolicy(
                   rollback_after=2, forensics_dir=str(tmp_path)))
               .set_end_when(Trigger.max_epoch(4)))
        with pytest.raises(TrainingDiverged, match="no last-known-good"):
            opt.optimize()


class TestForensicsReplay:
    def test_replay_rematerializes_byte_identical(self, tmp_path):
        from tools.replay_batch import replay

        ckpt = str(tmp_path / "ckpt")
        X, Y = _ladder_data(seed=3)
        monkey = ChaosMonkey([FaultSpec("corrupt_batch", 3)],
                             checkpoint_path=ckpt)
        chaos = monkey.dataset(_pipeline(X, Y, base_seed=11))
        opt = (Optimizer(_model(), chaos, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_checkpoint(ckpt, Trigger.several_iteration(2),
                               overwrite=False, keep_last=3)
               .set_anomaly_policy(AnomalyPolicy(rollback_after=3,
                                                 promote_after=2))
               .set_end_when(Trigger.max_epoch(1)))
        opt.optimize()
        bundle = json.load(open(opt._anomaly.forensics_paths[0]))
        gidx = bundle["epoch"] * 6 + bundle["batch_in_epoch"]
        assert gidx == 3
        report = replay(
            bundle, _pipeline(X, Y, base_seed=11), _model(),
            MSECriterion(), optim=SGD(0.05),
            batch_transform=lambda b, i: mutate_batch(
                "corrupt_batch", b, seed=gidx),
            checkpoint_path=ckpt)
        assert report["byte_identical"] is True
        assert report["cause"] == "data"
        assert report["f32_restored_from"] == "lkg"
        # without re-applying the corruption the clean batch differs
        clean = replay(bundle, _pipeline(X, Y, base_seed=11), _model(),
                       MSECriterion(), optim=SGD(0.05))
        assert clean["byte_identical"] is False
        assert clean["batch_finite"] is True

    def test_mutations_deterministic(self):
        b = _batch(7)
        a1 = mutate_batch("corrupt_batch", b, seed=42)
        a2 = mutate_batch("corrupt_batch", _batch(7), seed=42)
        assert np.array_equal(a1["input"], a2["input"])
        a3 = mutate_batch("corrupt_batch", _batch(7), seed=43)
        assert not np.array_equal(a1["input"], a3["input"])
        # original batch never mutated in place
        assert np.array_equal(b["input"], _batch(7)["input"])
        nan = mutate_batch("nan_grads", _batch(7), seed=0)
        assert np.isnan(nan["input"]).any()
        inf = mutate_batch("inf_loss", _batch(7), seed=0)
        assert np.abs(inf["target"]).max() >= 1e30


class TestCheckpointHealthGuard:
    def test_unhealthy_word_refuses_snapshot(self, tmp_path):
        """Satellite: the checkpoint NaN-skip is routed through the
        health word — non-finite PARAMS with a finite loss this step
        must also refuse the snapshot."""
        from analytics_zoo_tpu.parallel.optim import TrainingState

        ckpt = str(tmp_path / "ckpt")
        m = _model()
        opt = (Optimizer(m, [], MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_checkpoint(ckpt, Trigger.always()))
        state = create_train_state(m, opt.optim)
        loop = TrainingState(loss=1.0)        # finite loss ...
        loop.health = 1 << 3                  # ... but params non-finite
        assert opt._maybe_checkpoint(loop, state) is False
        assert not os.path.exists(os.path.join(ckpt, "latest"))
        loop.health = 0
        assert opt._maybe_checkpoint(loop, state) is True
        assert os.path.exists(os.path.join(ckpt, "latest"))
