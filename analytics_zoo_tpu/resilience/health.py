"""Device-health sentinel: silent-data-corruption detection, straggler
quarantine, and elastic eviction (ISSUE 20).

The PR-3 anomaly ladder catches *non-finite math* and the serving pool
catches *crashed or wedged replicas* — but both trust the silicon: a
chip that computes wrong answers (silent data corruption, SDC) or runs
persistently slow degrades the fleet undetected.  This module treats
unpredictable devices the way Clockwork treats unpredictable components
— as failed — and gives training and serving the detectors plus the
decision machinery to *evict* them:

- **Cross-replica parity audit** (:func:`make_audit_fn`): data-parallel
  replicas must hold bit-identical params post-all-reduce, so every
  ``audit_every`` steps an in-graph per-replica param-tree fingerprint
  (a folded uint32 reduction inside ``shard_map``, no host sync on the
  hot path) is compared at the decision boundary; a divergence names
  the minority device (:meth:`HealthSentinel.observe_audit`).
- **Shadow recompute spot-check**: a sampled microbatch's forward is
  re-executed on a second device and the output fingerprints compared
  (:meth:`HealthSentinel.observe_shadow`) — catching SDC that the
  gradient all-reduce would otherwise average into the fleet.
- **Straggler detector** (:meth:`HealthSentinel.observe_step_time`):
  per-device step-time EWMAs vs the fleet median with hysteresis (the
  PR-5 ladder idiom — ``flag_after`` consecutive over-threshold
  windows flag, ``clear_after`` clean ones clear), so persistent
  outliers are flagged and one-shot noise never is.
- **Quarantine + eviction**: a confirmed suspect raises
  :class:`~analytics_zoo_tpu.resilience.errors.DeviceQuarantine`
  (retryable — the supervisor rebuilds on the surviving devices via
  :func:`evict_device` + ``SpecSet.replace_mesh`` + the LKG tier +
  ``elastic_resume_coordinates``); an *ambiguous* divergence (no
  strict minority) raises
  :class:`~analytics_zoo_tpu.resilience.errors.SdcDetected` (fatal —
  restarting onto the same unattributed silicon re-creates it).
  Serving retires a flagged device's slice through
  ``ReplicaPool.quarantine`` (drain-then-retire, ``device_budget``
  decremented).

Every knob defaults **off** (``HealthPolicy(audit_every=0,
shadow_every=0)`` and no sentinel armed anywhere by default), so legacy
runs and every banked drill replay byte-identically.

Chaos composition: the ``bit_flip`` fault kind
(:mod:`analytics_zoo_tpu.resilience.chaos`) arms a module-global flip
spec here (:func:`arm_bit_flip`, the ``set_fault_hook`` precedent) that
the audit/shadow programs consume as *traced* scalars — a deterministic
single-element single-bit corruption of the named replica's view of the
params/output, modeling a stuck bit in that device's read path.  Banked
drill: ``tools/sdc_drill.py`` → ``SDC_r01.json``.
"""

from __future__ import annotations

import dataclasses
import logging
import statistics
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger("analytics_zoo_tpu")


# ---------------------------------------------------------------------------
# Chaos hook: deterministic bit-flip injection (the SDC fault model)
# ---------------------------------------------------------------------------

#: armed flip spec ``(replica, element, bit)`` or None — module-global on
#: purpose (the ``checkpoint.set_fault_hook`` precedent): the chaos
#: schedule fires from the dataset wrapper while the audit runs deep in
#: the train loop, and neither holds a reference to the other.
_FLIP: Optional[Tuple[int, int, int]] = None


def arm_bit_flip(replica: int, element: int = 0,
                 bit: int = 0) -> Optional[Tuple[int, int, int]]:
    """Arm a persistent single-bit corruption of device ``replica``'s
    view of the audited tree (flat ``element`` of the first leaf, bit
    ``bit``).  Persistent — a stuck bit, not a transient — until
    :func:`clear_bit_flip` (``ChaosMonkey.disarm`` calls it).  Returns
    the previously armed spec."""
    global _FLIP
    prev = _FLIP
    _FLIP = (int(replica), int(element), int(bit))
    logger.warning("health: bit_flip armed on replica %d (element %d, "
                   "bit %d)", *_FLIP)
    return prev


def clear_bit_flip() -> None:
    global _FLIP
    _FLIP = None


def active_bit_flip() -> Optional[Tuple[int, int, int]]:
    """The armed flip spec, or None.  The trainer passes it into the
    audit program as traced scalars (no retrace per arm/clear)."""
    return _FLIP


# ---------------------------------------------------------------------------
# In-graph fingerprints (traced; no host sync)
# ---------------------------------------------------------------------------


def _as_u32(x):
    """Flat uint32 view of one leaf: 4-byte dtypes are bitcast (exact —
    two values differing in ONE bit fold to different words), others are
    value-cast through a 32-bit carrier."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.uint32).reshape(-1)
    return x.astype(jnp.uint32).reshape(-1)


def tree_fingerprint(tree, flip=None):
    """Traced uint32 fold over every leaf of ``tree`` — an FNV-style
    position-weighted reduction (uint32 arithmetic wraps mod 2^32 in
    XLA, so the fold is exact and deterministic; leaf order is jax's
    canonical tree order).  Any single-element change anywhere in the
    tree changes the word with overwhelming probability, and a one-BIT
    change ALWAYS changes the folded leaf's term (bitcast + per-position
    odd weight: flipping bit ``b`` of a word perturbs the fold by
    ``±2^b·w mod 2^32``, which is non-zero for every ``b < 32`` exactly
    because ``w`` is odd).

    ``flip`` (optional) = ``(element, bit, on)`` traced scalars: when
    ``on`` is true, flat ``element`` of the FIRST leaf has ``bit``
    XOR-flipped *in this device's view* before folding — the chaos
    ``bit_flip`` injection point."""
    import jax
    import jax.numpy as jnp

    word = jnp.uint32(2166136261)           # FNV-1a offset basis
    for k, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
        u = _as_u32(leaf)
        if flip is not None and k == 0:
            element, bit, on = flip
            idx = jnp.clip(jnp.uint32(element), 0, u.size - 1)
            flipped = u.at[idx].set(
                u[idx] ^ (jnp.uint32(1) << jnp.uint32(bit)))
            u = jnp.where(on, flipped, u)
        # per-position Knuth-hash weights FORCED odd (|1): an even
        # weight is blind to high bits (2^b·w ≡ 0 mod 2^32 once
        # w ≡ 0 mod 2^(32-b)) — the old idx·K + (2k+1) scheme was even
        # at every odd idx and so missed sign-bit flips there.  The
        # leaf index mixes into the fold as its own odd term instead,
        # keeping leaf reorders visible.
        w = ((jnp.arange(u.size, dtype=jnp.uint32)
              * jnp.uint32(2654435761)) | jnp.uint32(1))
        word = (word * jnp.uint32(16777619) + jnp.uint32(2 * k + 1)
                + jnp.sum(u * w, dtype=jnp.uint32))
    return word


def make_audit_fn(mesh):
    """Build the jitted cross-replica parity audit for a pure
    data-parallel mesh: ``audit_fn(params, target, element, bit) →
    uint32[W]`` — each device folds ITS OWN local copy of the
    (logically replicated) params inside ``shard_map``, so the output
    vector holds one fingerprint per replica and the comparison happens
    at the host decision boundary, not in the hot path.

    ``target`` (traced int32, -1 = none) is the chaos ``bit_flip``
    replica: that device's view has ``(element, bit)`` flipped before
    folding — on healthy silicon this is the only way replicas can
    diverge, which is exactly what the fault drill banks."""
    import jax
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.parallel.sequence import _shard_map

    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"parity audit needs a pure data-parallel mesh (params "
            f"replicated over one axis); got axes {mesh.axis_names} — "
            f"hybrid meshes shard params, so per-replica bit-identity "
            f"does not hold")
    axis = mesh_lib.data_axis(mesh)

    def per_device(params, target, element, bit):
        me = jax.lax.axis_index(axis)
        on = (target >= 0) & (me == target)
        word = tree_fingerprint(params, flip=(element, bit, on))
        return word[None]                   # (1,) per device → (W,)

    return jax.jit(_shard_map(per_device, mesh,
                              in_specs=(P(), P(), P(), P()),
                              out_specs=P(axis)))


def make_shadow_fn(module, forward_fn=None):
    """Build the jitted shadow-recompute program: ``shadow(variables,
    batch, element, bit, on) → uint32`` — a deterministic (train=False)
    forward of the microbatch folded to one fingerprint word.  The
    caller executes it under ``jax.default_device(d)`` for each device
    being cross-checked; ``on`` keys in the armed ``bit_flip`` when the
    executing device is the chaos target (corrupting that device's view
    of the OUTPUT — SDC in the compute path, which a gradient
    all-reduce would have averaged into the fleet)."""
    import jax

    from analytics_zoo_tpu.parallel.train import _forward

    def shadow(variables, batch, element, bit, on):
        if forward_fn is not None:
            output, _ = forward_fn(variables, batch["input"],
                                   train=False, rngs=None)
        else:
            output, _ = _forward(module, variables, batch["input"],
                                 train=False)
        return tree_fingerprint({"output": output},
                                flip=(element, bit, on))

    return jax.jit(shadow)


def evict_device(mesh, device_index: int, new_width: Optional[int] = None):
    """The eviction actuator's mesh half: a fresh pure-data mesh over
    the surviving devices of ``mesh`` with flat index ``device_index``
    removed (``new_width`` optionally narrows further, e.g. so the
    width keeps dividing the global batch).  Compose with
    ``SpecSet.replace_mesh`` + the LKG tier + ``restore_elastic`` +
    ``elastic_resume_coordinates`` for checkpoint-free recovery at the
    smaller width (the PR-19 elastic path)."""
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    devices = [d for i, d in enumerate(mesh.devices.flat)
               if i != int(device_index)]
    if not devices:
        raise ValueError("cannot evict the only device in the mesh")
    if new_width is not None:
        if not 1 <= new_width <= len(devices):
            raise ValueError(f"new_width {new_width} not in "
                             f"[1, {len(devices)}]")
        devices = devices[:new_width]
    return mesh_lib.create_mesh(devices=devices,
                                axis_names=mesh.axis_names)


# ---------------------------------------------------------------------------
# Policy + sentinel (host-side decision machinery)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HealthPolicy:
    """Knobs for the device-health sentinel.  Both detector cadences
    default to 0 = **off**, so an un-armed job (and every legacy banked
    drill) runs byte-identically."""

    #: parity-audit cadence in steps (0 = off)
    audit_every: int = 0
    #: shadow-recompute cadence in steps (0 = off)
    shadow_every: int = 0
    #: device index the shadow forward is re-executed on
    shadow_device: int = 1
    #: a device is an outlier when its EWMA > factor × fleet median
    straggler_factor: float = 1.75
    #: EWMA smoothing for per-device step times
    straggler_alpha: float = 0.25
    #: hysteresis: consecutive outlier observations before flagging —
    #: one-shot noise (a GC pause, one slow batch) never flags
    flag_after: int = 3
    #: consecutive clean observations before an outlier streak resets
    clear_after: int = 2
    #: per-device observations ignored before the EWMA is trusted
    #: (compile / warm-up noise)
    warmup_obs: int = 2
    #: raise ``DeviceQuarantine`` on a confirmed suspect (False =
    #: detect-and-log only)
    evict: bool = True
    #: quarantine budget — evictions beyond it degrade to log-only
    #: (each eviction shrinks the fleet; past the budget an operator
    #: should be looking at the hardware, not the supervisor)
    max_evictions: int = 1

    def __post_init__(self):
        if self.audit_every < 0 or self.shadow_every < 0:
            raise ValueError("audit_every/shadow_every must be >= 0 "
                             "(0 = off)")
        if self.shadow_device < 1:
            raise ValueError("shadow_device must be >= 1 (device 0 is "
                             "the primary)")
        if self.straggler_factor <= 1.0:
            raise ValueError("straggler_factor must be > 1 (an EWMA at "
                             "the median is not an outlier)")
        if not 0.0 < self.straggler_alpha <= 1.0:
            raise ValueError("straggler_alpha must be in (0, 1]")
        if self.flag_after < 1 or self.clear_after < 1:
            raise ValueError("flag_after/clear_after must be >= 1")
        if self.warmup_obs < 0:
            raise ValueError("warmup_obs must be >= 0")
        if self.max_evictions < 0:
            raise ValueError("max_evictions must be >= 0")


@dataclasses.dataclass
class AuditVerdict:
    """One parity-audit comparison: ``ok`` when all replicas agree;
    otherwise ``suspect`` names the single minority device (strict
    majority agrees) or stays None with ``ambiguous=True`` (a 2-way
    split / multiple divergers — eviction cannot be attributed)."""

    ok: bool
    suspect: Optional[int] = None
    ambiguous: bool = False
    fingerprints: Tuple[int, ...] = ()


class HealthSentinel:
    """Host-side state machine for the three detectors.  Pure decision
    logic: callers hand it HOST values (fingerprint vectors fetched at
    the decision boundary, per-device step seconds) and act on the
    returned verdicts — raising/evicting stays with the trainer or the
    serving runtime, so the sentinel is trivially unit-testable."""

    def __init__(self, policy: Optional[HealthPolicy] = None,
                 registry=None):
        self.policy = policy or HealthPolicy()
        self.registry = registry
        self.events: List[Dict[str, Any]] = []
        self._ewma: Dict[int, float] = {}
        self._obs: Dict[int, int] = {}
        self._streak: Dict[int, int] = {}
        self._clean: Dict[int, int] = {}
        self._flagged: set = set()
        self.audits = 0
        self.divergences = 0
        self.shadow_checks = 0
        self.shadow_mismatches = 0
        self.straggler_flags = 0
        self.quarantines = 0

    def _count(self, name: str) -> None:
        if self.registry is not None:
            # az-allow: registered-metric-names — sentinel-internal helper; every caller passes a literal from the health/* family declared in obs/names.py
            self.registry.counter(name).inc()

    # -- parity audit ------------------------------------------------------
    def observe_audit(self, step: int,
                      fingerprints: Sequence[int]) -> AuditVerdict:
        """Compare one audit's per-replica fingerprint vector (host
        ints).  All-equal → ok.  A single device against a strict
        majority → that device is the suspect.  Anything else (2-way
        tie, multiple divergers) → ambiguous: corruption is proven but
        unattributable, the ``SdcDetected`` path."""
        fps = tuple(int(v) for v in fingerprints)
        self.audits += 1
        self._count("health/audits")
        if len(set(fps)) <= 1:
            return AuditVerdict(ok=True, fingerprints=fps)
        self.divergences += 1
        self._count("health/audit_divergences")
        maj_val, maj_n = Counter(fps).most_common(1)[0]
        minority = [i for i, v in enumerate(fps) if v != maj_val]
        suspect = (minority[0] if len(minority) == 1
                   and 2 * maj_n > len(fps) else None)
        self.events.append({"kind": "audit_divergence", "step": int(step),
                            "suspect": suspect,
                            "minority": [int(i) for i in minority],
                            "fingerprints": [int(v) for v in fps]})
        logger.error("health: parity audit diverged at step %d — "
                     "suspect=%s fingerprints=%s", step, suspect,
                     list(fps))
        return AuditVerdict(ok=False, suspect=suspect,
                            ambiguous=suspect is None, fingerprints=fps)

    # -- shadow recompute --------------------------------------------------
    def observe_shadow(self, step: int, primary_fp: int, shadow_fp: int,
                       device: int,
                       tiebreak_fp: Optional[int] = None) -> AuditVerdict:
        """Compare a shadow recompute against the primary.  A mismatch
        with a third vote (``tiebreak_fp``) names the odd one out; a
        bare two-way mismatch is ambiguous (proven SDC, unknown
        culprit)."""
        p, s = int(primary_fp), int(shadow_fp)
        self.shadow_checks += 1
        self._count("health/shadow_checks")
        if p == s:
            return AuditVerdict(ok=True, fingerprints=(p, s))
        self.shadow_mismatches += 1
        self._count("health/shadow_mismatches")
        suspect = None
        if tiebreak_fp is not None:
            t = int(tiebreak_fp)
            if p == t:
                suspect = int(device)       # shadow is the odd one out
            elif s == t:
                suspect = 0                 # primary is the odd one out
        self.events.append({"kind": "shadow_mismatch", "step": int(step),
                            "device": int(device), "suspect": suspect,
                            "primary_fp": p, "shadow_fp": s,
                            "tiebreak_fp": (int(tiebreak_fp)
                                            if tiebreak_fp is not None
                                            else None)})
        logger.error("health: shadow recompute mismatch at step %d "
                     "(device %d vs primary) — suspect=%s", step, device,
                     suspect)
        return AuditVerdict(ok=False, suspect=suspect,
                            ambiguous=suspect is None,
                            fingerprints=(p, s))

    # -- straggler detector ------------------------------------------------
    def observe_step_time(self, device: int,
                          seconds: float) -> Optional[int]:
        """Feed one per-device step/service time.  Returns the device id
        when its EWMA has now been over ``straggler_factor`` × the fleet
        median for ``flag_after`` consecutive observations (the
        hysteresis ladder), else None.  A flagged device stays flagged
        (no re-return) until ``clear_after`` clean observations."""
        p = self.policy
        device = int(device)
        n = self._obs.get(device, 0) + 1
        self._obs[device] = n
        prev = self._ewma.get(device)
        self._ewma[device] = (float(seconds) if prev is None else
                              (1.0 - p.straggler_alpha) * prev
                              + p.straggler_alpha * float(seconds))
        if n <= p.warmup_obs:
            return None
        peers = [e for d, e in self._ewma.items()
                 if d != device and self._obs.get(d, 0) > p.warmup_obs]
        if not peers:
            return None
        median = statistics.median(peers)
        if self._ewma[device] > p.straggler_factor * median:
            self._clean[device] = 0
            streak = self._streak.get(device, 0) + 1
            self._streak[device] = streak
            if streak >= p.flag_after and device not in self._flagged:
                self._flagged.add(device)
                self.straggler_flags += 1
                self._count("health/straggler_flags")
                self.events.append({
                    "kind": "straggler_flagged", "device": device,
                    "ewma_s": round(self._ewma[device], 6),
                    "fleet_median_s": round(median, 6),
                    "streak": streak})
                logger.warning("health: device %d flagged as straggler "
                               "(ewma %.4fs vs median %.4fs, streak %d)",
                               device, self._ewma[device], median, streak)
                return device
        else:
            clean = self._clean.get(device, 0) + 1
            self._clean[device] = clean
            if clean >= p.clear_after:
                self._streak[device] = 0
                if device in self._flagged:
                    self._flagged.discard(device)
                    self.events.append({"kind": "straggler_cleared",
                                        "device": device})
        return None

    # -- bookkeeping -------------------------------------------------------
    def note_quarantine(self, device: int, reason: str) -> None:
        """Record an actuated eviction (the caller raises/retires) and
        drop the device's straggler state — a retired device's inflated
        EWMA must not keep counting as a peer in the fleet median, where
        it would skew every later outlier decision."""
        device = int(device)
        self.quarantines += 1
        self._count("health/quarantines")
        for m in (self._ewma, self._obs, self._streak, self._clean):
            m.pop(device, None)
        self.events.append({"kind": "quarantine", "device": device,
                            "reason": reason})

    @property
    def eviction_budget_left(self) -> bool:
        return self.quarantines < self.policy.max_evictions

    def flagged(self) -> List[int]:
        return sorted(self._flagged)

    def stats(self) -> Dict[str, int]:
        return {"audits": self.audits,
                "audit_divergences": self.divergences,
                "shadow_checks": self.shadow_checks,
                "shadow_mismatches": self.shadow_mismatches,
                "straggler_flags": self.straggler_flags,
                "quarantines": self.quarantines}
