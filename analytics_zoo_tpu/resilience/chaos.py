"""Chaos fault-injection matrix — grow-up of ``parallel.elastic.FaultInjector``.

Where :class:`~analytics_zoo_tpu.parallel.elastic.FaultInjector` raises a
single exception once, :class:`ChaosMonkey` drives a whole *schedule* of
heterogeneous faults against a running training job, each at a chosen
global batch index:

===================  ======================================================
kind                 effect
===================  ======================================================
``crash``            raise :class:`InjectedFault` (generic lost task)
``xla_transient``    raise ``jax.errors.JaxRuntimeError`` (device/runtime
                     error — what a lost TPU surfaces as)
``sigterm``          deliver SIGTERM to this process (graceful-preemption
                     path: checkpoint at the boundary, ``Preempted``)
``mid_save_kill``    arm a one-shot hook that crashes the NEXT checkpoint
                     save after the snapshot is written but BEFORE the
                     atomic publish rename (crash mid-save)
``corrupt_latest``   truncate a manifest-listed file of the newest intact
                     snapshot on disk (restore must fall back)
``stall``            sleep past the StallWatchdog deadline (hung step)
``nan_grads``        poison the batch input with a NaN — loss/grads go
                     non-finite (the anomaly sentinel must skip)
``inf_loss``         blow the batch target up so the loss overflows to
                     inf (spike/overflow path of the health word)
``corrupt_batch``    deterministically scramble the input payload's raw
                     bytes (a corrupt record surviving decode)
``bit_flip``         arm a persistent single-bit corruption of ONE named
                     replica's view of the params/output (silent data
                     corruption — the device-health parity audit must
                     name the minority device)
``slow_device``      persistent per-device slowdown (service-time
                     multiplier) — unlike the one-shot ``slow_forward``
                     it never wedges, so only the straggler EWMA
                     detector catches it
===================  ======================================================

The last three are *numerical* faults: instead of raising, they MUTATE
the yielded batch (deterministically — the scramble RNG is seeded from
the global batch index, so ``tools/replay_batch.py`` can re-apply the
exact corruption during forensics replay).  ``FaultSpec(batches=N)``
stretches a numerical fault over N consecutive batches — one batch
exercises the sentinel's skip, ``rollback_after`` consecutive force a
rollback, and a persistent window drives the ladder to
``TrainingDiverged``.

The schedule is plain data (:class:`FaultSpec` list), so drills can build
it from a seeded RNG and stay deterministic.  The monkey's batch counter
is *global across epochs and restart attempts* — wrap the dataset once,
reuse the wrapper in every rebuilt Optimizer, and each fault fires
exactly once per schedule entry.

Used by ``tools/chaos_drill.py`` (committed artifact RESILIENCE_r01.json)
and the tier-1 chaos-matrix tests in ``tests/test_elastic.py``.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import signal as _signal
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.resilience.errors import InjectedFault

logger = logging.getLogger("analytics_zoo_tpu")

#: kinds that MUTATE the yielded batch instead of raising/killing
NUMERICAL_KINDS = ("nan_grads", "inf_loss", "corrupt_batch")

#: kinds the SERVING runtime consumes (``serving.runtime`` /
#: ``tools/serve_drill.py``) via :meth:`ChaosMonkey.serving_active` —
#: they never fire from a wrapped training dataset:
#:
#: ``slow_forward``   injected latency on ONE replica's forward
#:                    (``detail={"replica": r, "delay_s": d}``) — drives
#:                    the StallWatchdog-wedged → fence → failover path
#: ``replica_crash``  the targeted replica's forward raises mid-batch
#:                    (``detail={"replica": r}``)
#: ``burst_load``     arrival-rate spike: the drill's workload generator
#:                    multiplies its arrival rate by
#:                    ``detail={"rate_x": k}`` inside the window
SERVING_KINDS = ("slow_forward", "replica_crash", "burst_load")

#: kinds modeling UNHEALTHY SILICON (``resilience.health``):
#:
#: ``bit_flip``     fires from the dataset wrapper like a raising kind,
#:                  but instead of raising it ARMS ``health.arm_bit_flip``
#:                  (``detail={"replica": r, "element": e, "bit": b}``) —
#:                  a persistent stuck bit in that device's read path,
#:                  visible only to the parity audit / shadow recompute
#: ``slow_device``  consumed by the serving runtime via
#:                  :meth:`ChaosMonkey.serving_active` (dispatch index,
#:                  like ``slow_forward``) — ``detail={"replica": r,
#:                  "slow_x": k}`` multiplies the replica's service time
#:                  over the window WITHOUT tripping wedge detection
DEVICE_KINDS = ("bit_flip", "slow_device")

KINDS = ("crash", "xla_transient", "sigterm", "mid_save_kill",
         "corrupt_latest", "stall") + NUMERICAL_KINDS + SERVING_KINDS \
    + DEVICE_KINDS

#: accepted ``FaultSpec.detail`` keys per kind — kinds absent here take
#: no detail at all.  ``__post_init__`` REJECTS unknown keys: a typo'd
#: knob (``dealy_s``) used to be silently ignored, turning a drill's
#: fault into a no-op that still "passed".
_DETAIL_KEYS: Dict[str, frozenset] = {
    "slow_forward": frozenset({"replica", "delay_s"}),
    "replica_crash": frozenset({"replica"}),
    "burst_load": frozenset({"rate_x"}),
    "bit_flip": frozenset({"replica", "element", "bit"}),
    "slow_device": frozenset({"replica", "slow_x"}),
}


def _poison_leaf(batch: Dict[str, Any], key: str) -> np.ndarray:
    """Copy-on-write float leaf under ``batch[key]`` (first element of a
    tuple/list input).  The caller's batch is never mutated in place —
    the same host arrays may be re-yielded on a later epoch."""
    val = batch[key]
    if isinstance(val, (tuple, list)):
        arr = np.array(np.asarray(val[0]), copy=True)
        rest = list(val)[1:]
        batch[key] = type(val)([arr] + rest) if isinstance(val, list) \
            else (arr,) + tuple(rest)
    else:
        arr = np.array(np.asarray(val), copy=True)
        batch[key] = arr
    if not np.issubdtype(arr.dtype, np.floating):
        raise TypeError(f"numerical chaos needs a float leaf at "
                        f"batch[{key!r}], got {arr.dtype}")
    return arr


def mutate_batch(kind: str, batch: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Apply one numerical fault to a batch, deterministically.

    ``seed`` is the batch's GLOBAL stream index by convention: replaying
    the same (kind, seed) on the same clean batch reproduces the
    corrupted payload byte for byte (the forensics replay contract).
    Returns a shallow copy; poisoned leaves are fresh arrays."""
    if kind not in NUMERICAL_KINDS:
        raise ValueError(f"not a numerical fault kind: {kind!r}")
    if not isinstance(batch, dict):
        raise TypeError("numerical chaos kinds need dict batches")
    out = dict(batch)
    if kind == "nan_grads":
        arr = _poison_leaf(out, "input")
        arr.reshape(-1)[0] = np.nan
    elif kind == "inf_loss":
        key = "target" if "target" in out else "input"
        arr = _poison_leaf(out, key)
        # large-but-representable: the squared error overflows f32 → inf
        arr.reshape(-1)[0] = np.asarray(1e30, arr.dtype)
    else:  # corrupt_batch: scramble the payload's raw bytes
        arr = _poison_leaf(out, "input")
        rng = np.random.Generator(np.random.PCG64(seed & 0xFFFFFFFFFFFFFFFF))
        flat = arr.view(np.uint8).reshape(-1)
        flat[:] = flat[rng.permutation(flat.size)]
    return out


def transient_xla_error(msg: str = "injected transient device error"):
    """An exception of the real JAX runtime-error type, so the retry
    filter is exercised against the genuine class."""
    from jax.errors import JaxRuntimeError

    return JaxRuntimeError(msg)


def corrupt_snapshot(checkpoint_path: str) -> Tuple[str, str]:
    """Truncate the largest manifest-listed file of the newest intact
    snapshot under ``checkpoint_path`` to half its size.  Returns
    ``(snapshot_dir, relative_file)``.  Raises ``FileNotFoundError``
    when no intact snapshot exists to corrupt."""
    from analytics_zoo_tpu.parallel import checkpoint as ckpt

    found = ckpt.newest_intact(checkpoint_path)
    if found is None:
        raise FileNotFoundError(
            f"no intact snapshot under {checkpoint_path} to corrupt")
    snap_dir, man = found
    files = man.get("files", {})
    if not files:
        raise FileNotFoundError(f"{snap_dir}: manifest lists no files")
    rel = max(files, key=lambda r: files[r]["size"])
    full = os.path.join(snap_dir, rel)
    size = os.path.getsize(full)
    with open(full, "r+b") as f:
        f.truncate(max(size // 2, 1))
    logger.warning("chaos: truncated %s (%d -> %d bytes)", full, size,
                   os.path.getsize(full))
    return snap_dir, rel


@dataclasses.dataclass
class FaultSpec:
    """One scheduled fault: ``kind`` fires just before the wrapped
    dataset yields global batch index ``at_batch`` (counted across epochs
    AND restart attempts).  Numerical kinds may stretch over ``batches``
    consecutive batches (``[at_batch, at_batch + batches)``) — the knob
    that distinguishes a one-off bad record (skip), a bad burst
    (rollback) and persistent divergence (``TrainingDiverged``)."""

    kind: str
    at_batch: int
    batches: int = 1
    #: kind-specific knobs (serving kinds: target replica, delay, rate
    #: multiplier).  Plain data so drill schedules stay seedable.
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"one of {KINDS}")
        if self.batches < 1:
            raise ValueError("batches must be >= 1")
        windowed = NUMERICAL_KINDS + SERVING_KINDS + ("slow_device",)
        if self.batches > 1 and self.kind not in windowed:
            raise ValueError(f"batches>1 only applies to windowed kinds "
                             f"{windowed}, not {self.kind!r}")
        accepted = _DETAIL_KEYS.get(self.kind, frozenset())
        unknown = set(self.detail) - accepted
        if unknown:
            raise ValueError(
                f"unknown detail key(s) {sorted(unknown)} for kind "
                f"{self.kind!r}; accepted: "
                f"{sorted(accepted) if accepted else '(none)'}")


class ChaosMonkey:
    """Executes a :class:`FaultSpec` schedule against a training job.

    ``checkpoint_path`` is required for the ``mid_save_kill`` and
    ``corrupt_latest`` kinds.  ``stall_s`` sizes the injected hang (must
    exceed the job's StallWatchdog deadline to trigger it).  Every fired
    fault is appended to :attr:`events` (plain dicts, no wall-clock — so
    drill artifacts stay deterministic).
    """

    def __init__(self, faults: Sequence[FaultSpec],
                 checkpoint_path: Optional[str] = None,
                 stall_s: float = 1.0):
        self.faults = sorted(faults, key=lambda f: f.at_batch)
        self.checkpoint_path = checkpoint_path
        self.stall_s = stall_s
        self.events: List[Dict[str, Any]] = []
        self.consumed = 0          # global batch counter
        self._fired = [False] * len(self.faults)
        self._armed_hook = None    # mid_save_kill hook awaiting a save
        self._armed_flip = False   # bit_flip armed on the health module

    def arm(self, fault: FaultSpec) -> None:
        """Schedule an additional fault mid-run — how a drill targets a
        fault at a condition only known at runtime (e.g. "crash a
        replica while THIS rollout is draining"): observe the state,
        then arm a spec at a near-future index.  Deterministic as long
        as the observed state and the chosen index are."""
        self.faults.append(fault)
        self._fired.append(False)

    # -- dataset hook ------------------------------------------------------
    def dataset(self, ds) -> "ChaosDataset":
        """Wrap ``ds`` so faults fire at their scheduled batch indices.
        The wrapper is re-iterable (one fresh pass over ``ds`` per epoch)
        while the fault schedule and counter stay with the monkey."""
        return ChaosDataset(self, ds)

    def _due(self) -> List[int]:
        # slow_device is serving-consumed (dispatch index) like the
        # SERVING_KINDS; bit_flip DOES fire from the dataset wrapper
        # (it arms the health hook instead of raising)
        return [i for i, f in enumerate(self.faults)
                if not self._fired[i] and f.at_batch <= self.consumed
                and f.kind not in NUMERICAL_KINDS
                and f.kind not in SERVING_KINDS
                and f.kind != "slow_device"]

    def on_batch(self, batch=None):
        """Fire every due fault (called by the wrapper before each yield)
        and apply any numerical fault whose window covers this batch to
        ``batch``.  Raising kinds record first, then raise.  Returns the
        (possibly mutated) batch."""
        for i in self._due():
            self._fired[i] = True
            f = self.faults[i]
            logger.warning("chaos: firing %s at batch %d", f.kind,
                           self.consumed)
            getattr(self, f"_fire_{f.kind}")(f, i)
        for i, f in enumerate(self.faults):
            if f.kind not in NUMERICAL_KINDS or self._fired[i]:
                continue
            if not (f.at_batch <= self.consumed < f.at_batch + f.batches):
                continue
            logger.warning("chaos: %s poisoning batch %d (window %d..%d)",
                           f.kind, self.consumed, f.at_batch,
                           f.at_batch + f.batches - 1)
            # seed = global batch index: forensics replay re-applies the
            # identical corruption to the re-materialized clean batch
            batch = mutate_batch(f.kind, batch, seed=self.consumed)
            self._record(f, scheduled_at=f.at_batch, seed=self.consumed)
            if self.consumed >= f.at_batch + f.batches - 1:
                self._fired[i] = True
        return batch

    def _record(self, f: FaultSpec, **detail) -> None:
        self.events.append({"kind": f.kind, "at_batch": self.consumed,
                            **detail})

    # -- fault kinds -------------------------------------------------------
    def _fire_crash(self, f: FaultSpec, i: int) -> None:
        self._record(f)
        raise InjectedFault(f"injected crash at batch {self.consumed}")

    def _fire_xla_transient(self, f: FaultSpec, i: int) -> None:
        self._record(f)
        raise transient_xla_error(
            f"injected transient device error at batch {self.consumed}")

    def _fire_sigterm(self, f: FaultSpec, i: int) -> None:
        self._record(f)
        os.kill(os.getpid(), _signal.SIGTERM)

    def _fire_stall(self, f: FaultSpec, i: int) -> None:
        self._record(f, stall_s=self.stall_s)
        time.sleep(self.stall_s)

    def _fire_mid_save_kill(self, f: FaultSpec, i: int) -> None:
        from analytics_zoo_tpu.parallel import checkpoint as ckpt

        if self.checkpoint_path is None:
            raise ValueError("mid_save_kill needs ChaosMonkey("
                             "checkpoint_path=...) — an unscoped hook "
                             "could detonate in an unrelated job's save")
        armed_at = self.consumed
        scope = os.path.abspath(self.checkpoint_path)

        def hook(phase: str, path: str) -> None:
            if phase != "pre_publish":
                return
            # scoped to this monkey's checkpoint tree: an armed hook
            # must never detonate inside an unrelated job's save
            if not os.path.abspath(path).startswith(scope + os.sep):
                return
            ckpt.set_fault_hook(None)  # one-shot
            self._armed_hook = None
            self.events.append({"kind": "mid_save_kill",
                                "armed_at_batch": armed_at,
                                "fired_in_save": os.path.basename(path)})
            raise InjectedFault(
                f"injected crash mid-save of {path} (before publish)")

        self._armed_hook = hook
        ckpt.set_fault_hook(hook)

    def _fire_bit_flip(self, f: FaultSpec, i: int) -> None:
        from analytics_zoo_tpu.resilience import health

        replica = int(f.detail.get("replica", 0))
        element = int(f.detail.get("element", 0))
        bit = int(f.detail.get("bit", 0))
        health.arm_bit_flip(replica, element=element, bit=bit)
        self._armed_flip = True
        self._record(f, replica=replica, element=element, bit=bit)

    def _fire_corrupt_latest(self, f: FaultSpec, i: int) -> None:
        if self.checkpoint_path is None:
            raise ValueError("corrupt_latest needs ChaosMonkey("
                             "checkpoint_path=...)")
        try:
            snap, rel = corrupt_snapshot(self.checkpoint_path)
            self._record(f, snapshot=os.path.basename(snap), file=rel)
        except FileNotFoundError:
            # nothing on disk yet — re-arm one batch later
            self._fired[i] = False
            self.faults[i] = FaultSpec(f.kind, f.at_batch + 1)

    # -- serving hooks -----------------------------------------------------
    def serving_active(self, kind: str, index: int,
                       consume: bool = True) -> Optional[FaultSpec]:
        """Window query for the SERVING fault kinds: return the spec of
        ``kind`` whose ``[at_batch, at_batch + batches)`` window covers
        ``index``, else ``None``.  Serving drills drive their OWN
        counter (dispatch index for ``slow_forward``/``replica_crash``,
        request index for ``burst_load``) — independent of the training
        batch counter the dataset wrapper advances.

        ``consume=True`` marks the spec fired once ``index`` reaches the
        window's last slot (so a one-shot ``replica_crash`` fires on
        exactly one dispatch) and records an event; ``consume=False`` is
        a pure peek (the workload generator probes ``burst_load`` before
        time reaches the window)."""
        if kind not in SERVING_KINDS + ("slow_device",):
            raise ValueError(
                f"not a serving-consumed fault kind: {kind!r}; one of "
                f"{SERVING_KINDS + ('slow_device',)}")
        for i, f in enumerate(self.faults):
            if f.kind != kind or self._fired[i]:
                continue
            if not (f.at_batch <= index < f.at_batch + f.batches):
                continue
            if consume:
                self.events.append({"kind": kind, "at_index": int(index),
                                    **f.detail})
                if index >= f.at_batch + f.batches - 1:
                    self._fired[i] = True
            return f
        return None

    def disarm(self) -> None:
        """Clear any still-armed process-global hooks — a
        ``mid_save_kill`` hook on the checkpoint module and/or a
        ``bit_flip`` on the health module.  Call when the drill/test
        ends (whether or not the hook ever fired) so no armed fault
        leaks into a later job in the same process."""
        from analytics_zoo_tpu.parallel import checkpoint as ckpt

        if self._armed_hook is not None:
            prev = ckpt.set_fault_hook(None)
            if prev is not None and prev is not self._armed_hook:
                ckpt.set_fault_hook(prev)   # not ours — put it back
            self._armed_hook = None
        if self._armed_flip:
            from analytics_zoo_tpu.resilience import health

            health.clear_bit_flip()
            self._armed_flip = False

    def __enter__(self) -> "ChaosMonkey":
        return self

    def __exit__(self, *exc) -> None:
        self.disarm()

    # -- reporting ---------------------------------------------------------
    def fired_kinds(self) -> List[str]:
        return sorted({e["kind"] for e in self.events})

    def all_fired(self) -> bool:
        return all(self._fired)


class ChaosDataset:
    """Re-iterable dataset wrapper bound to a :class:`ChaosMonkey`.
    Unknown attributes delegate to the wrapped dataset, so loader
    metadata (``base_seed``, ``last_epoch``, ``num_workers`` — the
    anomaly-forensics RNG coordinates) stays visible through the wrap."""

    def __init__(self, monkey: ChaosMonkey, ds):
        self.monkey = monkey
        self.ds = ds

    def __iter__(self):
        for batch in self.ds:
            batch = self.monkey.on_batch(batch)
            self.monkey.consumed += 1
            yield batch

    def __len__(self):
        return len(self.ds)

    def __getattr__(self, name):
        return getattr(self.__dict__["ds"], name)
