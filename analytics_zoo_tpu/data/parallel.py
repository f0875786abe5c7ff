"""Multiprocess host input pipeline: decode + augmentation fan-out.

A serial loader makes the device step wait on ONE Python thread doing
decode + augment + collate.  The reference got its input
throughput from Spark's coarse-grained executor parallelism (SURVEY §0);
the JAX-native equivalent here is a process pool feeding the device
asynchronously — the same host/accelerator split tf.data and Grain use.

Design (one producer ring per worker, order-preserving):

- The wrapped :class:`~analytics_zoo_tpu.data.dataset.DataSet` is split
  into *leading stream stages* (cheap, e.g. ``ShuffleBuffer``), the
  *per-sample chain* (the expensive decode/augment stages), and
  *trailing stream stages* (batchers).  Every worker iterates the raw
  source + leading stages identically (cheap byte reads), but applies
  the per-sample chain only to its own sample *groups* (group ``g``
  belongs to worker ``g % num_workers``), so the heavy work — JPEG
  decode, ColorJitter, RandomSampler — is done exactly once across the
  pool.  The parent merges groups back in order and applies the
  trailing stages, so batch boundaries, remainder handling and sample
  drops are byte-identical to the serial path.
- Groups travel through a per-worker **shared-memory ring**: ndarray
  payloads are extracted out-of-band (pickle protocol 5
  ``buffer_callback``) and memcpy'd through the ring slots — zero
  pickle on the hot path for array bytes; only the tiny structural
  metadata is pickled.  The ring is the ONLY channel (headers included,
  no pipes): a slot is published by releasing the ``items`` semaphore
  strictly AFTER the slot is fully written, so a worker killed mid-write
  can never leave a truncated message for the consumer to block on —
  the unreleased slot simply never becomes visible (a ``mp.Queue`` here
  measurably hangs the parent when SIGKILL lands mid pipe-write).
  Groups larger than a slot degrade gracefully to a spill file
  (counted).
- **Determinism**: each worker's base PRNG is seeded from ``(base_seed,
  epoch, shard)`` and every sample's augmentation RNG is then folded in
  from the sample's *global* stream index, so the batch stream is
  byte-identical for ANY worker count — including ``num_workers=0``
  (the in-process serial reference path), pinned by
  ``tests/test_parallel_loader.py``.
- **The pool outlives an epoch**: workers are forked once and run on
  from epoch to epoch — after an epoch's end marker a worker reseeds for
  the next epoch exactly as a fresh fork of that epoch would, opens the
  source again and goes on putting groups.  The ring's back-pressure is
  the only throttle, so the next epoch's first groups are decoded while
  this epoch's last ones drain.  The pool stops when an epoch is closed
  early or raises, on :meth:`ParallelLoader.close`, when the loader is
  collected and at interpreter exit.
- **Worker death** flows into the PR-1 resilience classification: a crashed
  worker is respawned (deterministic seeding lets it recompute from its
  next owed group of the epoch in flight, and it too runs on into later
  epochs) at most ``max_respawns`` times per epoch, after
  which :class:`~analytics_zoo_tpu.resilience.errors.PrefetchWorkerDied`
  (retryable) escalates to the supervisor.

Overlapped H2D: compose with :func:`~analytics_zoo_tpu.data.prefetch.
device_prefetch` (``make_input_pipeline`` below, or
``PrefetchDataSet(..., num_workers=N)``) so the sharded host→device
transfer of batch ``t+1`` — one packed uint8 transfer on the
``DeviceAugBatch(pack=True)`` path — overlaps the device step on ``t``.
"""

from __future__ import annotations

import contextlib
import hashlib
import logging
import multiprocessing as mp
import os
import pickle
import random
import shutil
import struct
import tempfile
import threading
import time
import warnings
import weakref
from multiprocessing.sharedctypes import RawArray
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.data.transformer import (ChainedTransformer,
                                                ParallelTransformer,
                                                Transformer,
                                                walk_rngs)
from analytics_zoo_tpu.obs import span as obs_span
from analytics_zoo_tpu.resilience.errors import PrefetchWorkerDied

logger = logging.getLogger("analytics_zoo_tpu")

_DEFAULT_SLOT_BYTES = 32 << 20
_POLL_S = 0.2
# a worker's counters of the epoch it is in: indices into
# ``_Ring.counters``, and the layout of an end marker's copy of them
_CHAIN_S, _PUT_S, _WALK_S, _GROUPS, _T0, _T1, _EPOCH = range(7)
_COUNTERS = struct.Struct("<7d")


# ---------------------------------------------------------------------------
# Deterministic seeding
# ---------------------------------------------------------------------------


_SEEDABLE = (int, float, bool, str, bytes, type(None))


def stable_seed(*keys) -> int:
    """Stable 63-bit seed from scalar keys (process/run independent —
    Python's ``hash`` is salted, so it cannot be used here).  Keys are
    restricted to value-repr'd scalars (and tuples/lists of them): an
    arbitrary object's default repr embeds its ADDRESS, which would
    silently break the stability promise."""
    def check(k):
        if isinstance(k, (tuple, list)):
            for v in k:
                check(v)
        elif not isinstance(k, _SEEDABLE):
            raise TypeError(
                f"stable_seed keys must be int/float/bool/str/bytes/"
                f"None (or tuples of them), got {type(k).__name__} — "
                "an object repr would make the seed address-dependent")

    check(keys)
    h = hashlib.blake2s(repr(keys).encode())
    return struct.unpack("<q", h.digest()[:8])[0] & 0x7FFFFFFFFFFFFFFF


def seed_rngs(obj: Any, seed: int) -> None:
    """Deterministically seed every ``random.Random`` /
    ``np.random.RandomState`` / ``np.random.Generator`` reachable from
    ``obj`` (the shared ``transformer.walk_rngs`` discovery walk, so
    this and ``clone()``'s entropy reseed can never drift)."""
    count = [0]

    def visit(rng):
        s = stable_seed(seed, count[0])
        count[0] += 1
        if isinstance(rng, random.Random):
            rng.seed(s)
        elif isinstance(rng, np.random.RandomState):
            rng.seed(s & 0xFFFFFFFF)
        else:   # np.random.Generator — rebuild with the Generator's OWN
            # bit-generator type (a Philox state assigned to a PCG64
            # raises ValueError)
            rng.bit_generator.state = type(rng.bit_generator)(s).state

    walk_rngs(obj, visit)


def _rng_signature(rng: Any) -> str:
    """Value-based fingerprint of an RNG's CURRENT state (stable across
    processes — no addresses).  Folding a leading stage's construction-
    time signature into its per-epoch seeding key preserves the user's
    own seed choice (e.g. ``DataSet.shuffle(seed=...)``): two loaders
    built with different shuffle seeds keep producing different
    streams, while the reseed still pins determinism per epoch."""
    if isinstance(rng, random.Random):
        return repr(rng.getstate())
    if isinstance(rng, np.random.RandomState):
        kind, keys, pos, has_g, g = rng.get_state()
        return f"{kind}:{keys.tobytes().hex()}:{pos}:{has_g}:{g}"
    return repr(rng.bit_generator.state)        # np.random.Generator


def stream_stage_keys(leading: Sequence[Transformer]) -> List[str]:
    """One seeding key per leading stream stage, capturing the stage
    index and its RNGs' construction-time state signatures."""
    keys = []
    for i, stage in enumerate(leading):
        sigs: List[str] = []
        walk_rngs(stage, lambda r: sigs.append(_rng_signature(r)))
        keys.append(f"{i}:{':'.join(sigs)}")
    return keys


#: Process-local numpy Generator for per-sample transform randomness —
#: the sanctioned replacement for drawing from numpy's process-GLOBAL
#: RNG (which ``seed_sample`` historically ``np.random.seed``-ed per
#: sample; az-analyze's seeded-rng-only rule now bans both the global
#: seed and global draws: global state any import can perturb is
#: exactly what the byte-identical-for-any-worker-count contract cannot
#: be built on).  ``seed_sample`` rewinds THIS Generator from
#: ``(base_seed, epoch, sample_index)`` in whichever process runs the
#: chain, so a transform drawing from ``sample_rng()`` sees the same
#: stream in a forked worker, a respawned worker, and the serial
#: reference.
_SAMPLE_RNG = np.random.Generator(np.random.PCG64(0))


def sample_rng() -> np.random.Generator:
    """The per-sample-seeded local Generator for transform chains."""
    return _SAMPLE_RNG


def seed_sample(chain: Optional[Sequence[Transformer]], base_seed: int,
                epoch: int, index: int) -> None:
    """Pin ALL randomness for one sample's trip through the chain.

    The vision transforms draw from the module-level ``random`` (and the
    samplers derive their numpy Generators from it), numpy consumers
    draw from the loader's local :func:`sample_rng`, and chain-held RNG
    instances are reseeded by ``seed_rngs`` — all from ``(base_seed,
    epoch, sample_index)``, so the augmentation decisions are a pure
    function of the sample's stream position, independent of which
    worker (or thread, or respawn attempt) runs it.  The numpy GLOBAL
    RNG is deliberately left alone."""
    s = stable_seed("sample", base_seed, epoch, index)
    random.seed(s)
    _SAMPLE_RNG.bit_generator.state = np.random.PCG64(s).state
    if chain:
        seed_rngs(chain, stable_seed("chain", base_seed, epoch, index))


# ---------------------------------------------------------------------------
# Stage classification
# ---------------------------------------------------------------------------


def _is_per_sample(stage: Transformer) -> bool:
    """True when ``stage`` is a 1->1 transformer (safe to run per sample
    inside a worker): it overrides ``transform`` and keeps the base
    streaming ``apply_iter`` (chains of such stages count too)."""
    if isinstance(stage, ParallelTransformer):
        return _is_per_sample(stage.inner)
    if isinstance(stage, ChainedTransformer):
        return all(_is_per_sample(s) for s in stage.stages)
    cls = type(stage)
    return (cls.transform is not Transformer.transform
            and cls.apply_iter is Transformer.apply_iter)


def _flatten_per_sample(stage: Transformer) -> List[Transformer]:
    """Unwrap a per-sample stage into its atomic 1->1 transformers:
    ``ParallelTransformer`` wrappers dissolve (the process pool replaces
    the thread pool) and chains flatten — at EVERY nesting level, so a
    wrapper nested inside a chain can never survive into the worker
    chain where its base-class identity ``transform`` would silently
    skip the wrapped work."""
    if isinstance(stage, ParallelTransformer):
        return _flatten_per_sample(stage.inner)
    if isinstance(stage, ChainedTransformer):
        out: List[Transformer] = []
        for s in stage.stages:
            out.extend(_flatten_per_sample(s))
        return out
    return [stage]


def split_stages(stages: Sequence[Transformer]
                 ) -> Tuple[List[Transformer], List[Transformer],
                            List[Transformer]]:
    """(leading stream stages, per-sample chain stages, trailing stages).

    ``ParallelTransformer`` wrappers are unwrapped — the process pool
    replaces the thread pool.  Everything from the first per-sample
    stage up to the next stream stage becomes the worker chain; the
    remainder (batchers etc.) runs in the parent."""
    leading: List[Transformer] = []
    chain: List[Transformer] = []
    trailing: List[Transformer] = []
    for stage in stages:
        if isinstance(stage, ParallelTransformer):
            stage = stage.inner
        if trailing:
            trailing.append(stage)
        elif _is_per_sample(stage):
            chain.extend(_flatten_per_sample(stage))
        elif chain:
            trailing.append(stage)
        else:
            leading.append(stage)
    return leading, chain, trailing


def _apply_chain(chain: Sequence[Transformer], sample: Any) -> Any:
    """Per-sample chain application with the streaming drop semantics:
    a ``None`` from any stage drops the sample (base ``apply_iter``)."""
    for stage in chain:
        sample = stage.transform(sample)
        if sample is None:
            return None
    return sample


# ---------------------------------------------------------------------------
# Shared-memory ring (headers + payload; crash-atomic, no pipes)
# ---------------------------------------------------------------------------

_KIND_GRP = 0
_KIND_END = 1
_KIND_ERR = 2
_KIND_SPILL = 3
# u32 kind | u64 idx | u64 meta_len | u32 nbufs  (then nbufs u64 lens,
# meta bytes, payload bytes — all inside one slot)
_HDR = struct.Struct("<IQQI")


class _Ring:
    """Single-producer single-consumer shared-memory ring.

    ``slots`` fixed-size slots used strictly round-robin; ``free``
    counts writable slots (producer acquires before writing), ``items``
    counts published slots (released only after a slot is COMPLETELY
    written — the crash-atomicity invariant: a producer killed at any
    instant leaves either a fully-published slot or an invisible one,
    never a truncated message).  The consumer copies out, then releases
    ``free``.  No pipes anywhere, so a SIGKILLed producer cannot wedge
    the consumer in a blocking read."""

    def __init__(self, ctx, slots: int, slot_bytes: int, spill_dir: str):
        from multiprocessing import shared_memory

        self.slots = slots
        self.slot_bytes = slot_bytes
        self.spill_dir = spill_dir
        self.shm = shared_memory.SharedMemory(create=True,
                                              size=slots * slot_bytes)
        self.free = ctx.Semaphore(slots)
        self.items = ctx.Semaphore(0)
        self.seq = 0            # producer- and consumer-side slot cursor
        # what the producer has spent in the epoch it is in, for the
        # parent to record (a forked worker can open no obs.stage):
        # seconds in the per-sample chain, in put_group and blocked on
        # full slots, reading groups that are another worker's; groups
        # shipped; ``time.monotonic()`` at the epoch's start and at its
        # end marker; the epoch.  An end marker carries a copy, so the
        # parent reads these only of an epoch that never ended (the pool
        # stopped, the worker died).  Single writer, so no lock.
        self.counters = RawArray("d", _COUNTERS.size // 8)
        self.spilled = 0        # consumer side: groups read from a file

    def close(self) -> None:
        try:
            self.shm.close()
            self.shm.unlink()
        except Exception:
            pass

    # -- producer side (worker process) -----------------------------------
    def reserve(self, stop_event) -> bool:
        """Wait for a writable slot; False when cancelled via
        ``stop_event``.  This is where a worker that is ahead of the
        parent stands still: the pool's only throttle."""
        while not self.free.acquire(timeout=_POLL_S):
            if stop_event.is_set():
                return False
        return True

    def publish(self, kind: int, idx: int, meta: bytes,
                lens: Sequence[int], payload: Sequence) -> None:
        """Write one message into the reserved slot and make it visible."""
        base = (self.seq % self.slots) * self.slot_bytes
        buf = self.shm.buf
        _HDR.pack_into(buf, base, kind, idx, len(meta), len(lens))
        off = base + _HDR.size
        for n in lens:
            struct.pack_into("<Q", buf, off, n)
            off += 8
        buf[off:off + len(meta)] = meta
        off += len(meta)
        for m in payload:
            buf[off:off + len(m)] = m
            off += len(m)
        self.seq += 1
        self.items.release()          # publish — ONLY after a full write

    def put(self, kind: int, idx: int, meta: bytes, lens: Sequence[int],
            payload: Sequence, stop_event) -> bool:
        """Publish one message; False when cancelled via ``stop_event``."""
        need = _HDR.size + 8 * len(lens) + len(meta) + sum(lens)
        if need > self.slot_bytes:
            raise ValueError(
                f"message needs {need} bytes > slot_bytes={self.slot_bytes}"
                " (spill should have caught this)")
        if not self.reserve(stop_event):
            return False
        self.publish(kind, idx, meta, lens, payload)
        return True

    def put_group(self, group_idx: int, samples: List[Any],
                  stop_event) -> Tuple[bool, bool]:
        """Ship one group of transformed samples.  Returns (ok,
        spilled): ndarray payloads go out-of-band through the slot;
        oversize groups degrade to a spill file referenced from the
        slot (written and fsync'd BEFORE the slot publishes, so the
        crash-atomicity invariant holds for them too)."""
        raw: List[memoryview] = []

        def grab(b) -> bool:
            # a falsy return serializes OUT-of-band (we captured the
            # buffer); True keeps a non-contiguous buffer in-band
            try:
                raw.append(b.raw())
                return False
            except BufferError:
                return True

        meta = pickle.dumps(samples, protocol=5, buffer_callback=grab)
        lens = [len(m) for m in raw]
        need = _HDR.size + 8 * len(lens) + len(meta) + sum(lens)
        if need <= self.slot_bytes:
            return (self.put(_KIND_GRP, group_idx, meta, lens, raw,
                             stop_event), False)
        # spill file carries meta AND payload: a group whose IN-BAND
        # pickle alone exceeds the slot (e.g. raw JPEG bytes objects)
        # must degrade the same way as one with big ndarray buffers
        # named by the slot cursor: a kept worker ships group_idx again
        # every epoch, and may do so before the parent has read the last
        path = os.path.join(self.spill_dir,
                            f"spill-{os.getpid()}-{self.seq}.bin")
        with open(path, "wb") as f:
            f.write(meta)
            for m in raw:
                f.write(m)
            f.flush()
            os.fsync(f.fileno())
        blob = pickle.dumps((len(meta), lens, path))
        return (self.put(_KIND_SPILL, group_idx, blob, (), (),
                         stop_event), True)

    # -- consumer side (parent) --------------------------------------------
    def get(self, timeout: float):
        """One published message or None on timeout: (kind, idx, obj)
        where obj is the unpickled group for GRP/SPILL, the pickled
        payload bytes for ERR, and the worker's packed counters of the
        epoch for END."""
        if not self.items.acquire(timeout=timeout):
            return None
        base = (self.seq % self.slots) * self.slot_bytes
        buf = self.shm.buf
        kind, idx, meta_len, nbufs = _HDR.unpack_from(buf, base)
        off = base + _HDR.size
        lens = []
        for _ in range(nbufs):
            lens.append(struct.unpack_from("<Q", buf, off)[0])
            off += 8
        meta = bytes(buf[off:off + meta_len])
        off += meta_len
        if kind == _KIND_GRP:
            bufs = []
            for n in lens:
                bufs.append(bytearray(buf[off:off + n]))    # copy out
                off += n
            self.seq += 1
            self.free.release()
            return kind, idx, pickle.loads(meta, buffers=bufs)
        self.seq += 1
        self.free.release()
        if kind == _KIND_SPILL:
            meta_len, s_lens, path = pickle.loads(meta)
            with open(path, "rb") as f:
                # bytearray: reconstructed arrays must be WRITABLE like
                # the ring path's (immutable bytes would make in-place
                # mutation fail only on groups that happened to spill)
                data = bytearray(f.read())
            os.unlink(path)
            view = memoryview(data)
            bufs, off2 = [], meta_len
            for n in s_lens:
                bufs.append(view[off2:off2 + n])
                off2 += n
            return _KIND_SPILL, idx, pickle.loads(view[:meta_len],
                                                  buffers=bufs)
        return kind, idx, meta          # ERR, END


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------


def _advance_source_epochs(source_fn, n: int) -> None:
    """Fast-forward a DataSet source's per-epoch closure state by ``n``
    epochs.  Every DataSet constructor advances its epoch counter inside
    the generator body, so creating the generator and pulling ONE item
    is enough to step the state without reading the whole epoch."""
    for _ in range(n):
        it = source_fn()
        next(iter(it), None)


def _worker_main(worker_id: int, num_workers: int, epoch: int,
                 start_group: int, ring: _Ring, stop_event,
                 source_fn, leading: List[Transformer],
                 stream_keys: List[str],
                 chain: List[Transformer], group_size: int,
                 base_seed: int) -> None:
    """Producer body (runs in a forked child; must never touch jax — so
    it opens no ``obs.stage`` either: what it spends goes into
    ``ring.counters``).

    Epoch after epoch, from ``epoch`` on, until ``stop_event``: iterates
    the full raw stream (cheap), transforms only the groups owned by
    this shard (of the first epoch those from ``start_group`` on — a
    respawn's), ships them through the ring and ends the epoch with an
    end marker that carries its counters.  All randomness is pinned
    anew every epoch, as a fresh fork of that epoch would pin it:
    worker-level RNGs from ``(base_seed, epoch, shard)``, per-sample
    RNGs folded in from the global stream index.  The worker's own copy
    of the source's per-epoch state advances by walking the stream."""
    clock, counters = time.monotonic, ring.counters
    warned = [False]

    def one_epoch(epoch: int, start_group: int) -> bool:
        """False when cancelled."""
        counters[:] = [0.0] * len(counters)
        counters[_EPOCH] = epoch
        counters[_T0] = clock()
        # per-worker base PRNG: worker-local decisions (none on the hot
        # path today, but the contract is part of the API)
        random.seed(stable_seed("worker", base_seed, epoch, worker_id))
        for stage, key in zip(leading, stream_keys):
            seed_rngs(stage, stable_seed("stream", base_seed, epoch, key))
        it: Iterator[Any] = iter(source_fn())
        for stage in leading:
            it = stage.apply_iter(it)

        group: List[Any] = []
        g = 0
        idx = 0
        mine = (g % num_workers == worker_id) and g >= start_group

        def flush() -> bool:
            if mine:
                t = clock()
                ok, spilled = ring.put_group(g, group, stop_event)
                counters[_PUT_S] += clock() - t
                counters[_GROUPS] += ok
                if spilled and not warned[0]:
                    warned[0] = True
                    logger.warning(
                        "input worker %d: group %d exceeded slot_bytes; "
                        "spilling to disk (size the ring slots to the "
                        "batch — further spills not logged)", worker_id, g)
                return ok
            return True

        t_group = clock()
        for sample in it:
            if stop_event.is_set():
                return False
            if mine:
                t = clock()
                seed_sample(chain, base_seed, epoch, idx)
                out = _apply_chain(chain, sample)
                counters[_CHAIN_S] += clock() - t
                if out is not None:
                    group.append(out)
            idx += 1
            if idx % group_size == 0:
                if not mine:
                    counters[_WALK_S] += clock() - t_group
                if not flush():
                    return False
                group = []
                g += 1
                mine = ((g % num_workers == worker_id)
                        and g >= start_group)
                t_group = clock()
        if idx % group_size:
            if not mine:
                counters[_WALK_S] += clock() - t_group
            if not flush():
                return False
            g += 1
        t = clock()
        if not ring.reserve(stop_event):
            return False
        counters[_T1] = clock()
        counters[_PUT_S] += counters[_T1] - t
        ring.publish(_KIND_END, g, _COUNTERS.pack(*counters), (), ())
        return True

    try:
        while one_epoch(epoch, start_group):
            epoch, start_group = epoch + 1, 0
    except BaseException as e:  # noqa: BLE001 - shipped to the parent
        import traceback

        tb = traceback.format_exc()
        try:
            payload = pickle.dumps((e, tb))
        except Exception:
            payload = pickle.dumps((None, tb))
        try:
            ring.put(_KIND_ERR, 0, payload, (), (), stop_event)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# The pool
# ---------------------------------------------------------------------------


def _record_worker(w: int, counters: Sequence[float], spills: int) -> None:
    """One ``az/input/worker`` record from what worker ``w`` counted in
    one epoch (nothing of a worker that never began one)."""
    chain_s, put_s, walk_s, groups, t0, t1, epoch = counters
    if t0:
        obs_span.record_stage(
            "az/input/worker", t0, t1, worker=w, epoch=int(epoch),
            chain_s=chain_s, put_s=put_s, walk_s=walk_s,
            groups=int(groups), spills=spills)


class _Pool:
    """What outlives an epoch: the forked workers, a ring each, the event
    that cancels them and the directory their oversize groups spill to.
    ``epoch`` is the one whose messages are next in the rings.  The
    collection of ``owner`` (the loader) and the interpreter's exit stop
    it too."""

    def __init__(self, ctx, epoch: int, owner: Any):
        self.ctx = ctx
        self.epoch = epoch
        self.stop_event = ctx.Event()
        self.spill_dir = tempfile.mkdtemp(prefix="azt-loader-")
        self.rings: List[_Ring] = []
        self.procs: List[mp.Process] = []
        self.respawns_left = 0
        self.stopped = False
        self._lock = threading.Lock()
        self._finalizer = weakref.finalize(owner, self.stop)

    def retire(self, w: int) -> None:
        """Record what worker ``w`` (gone) counted in the epoch it never
        ended — which therefore lasts until now — and unlink its ring."""
        ring = self.rings[w]
        counters = list(ring.counters)
        counters[_T1] = obs_span.now()
        _record_worker(w, counters, ring.spilled)
        ring.close()

    def stop(self) -> None:
        """Cancel and reap the workers, record each one's unfinished
        epoch, unlink the rings, remove the spill directory.  Idempotent,
        and complete when it returns, whichever thread came first."""
        with self._lock:
            if self.stopped:
                return
            self.stopped = True
            self.stop_event.set()
            for proc in self.procs:
                proc.join(timeout=1.0)
                if proc.is_alive():
                    proc.terminate()
                    proc.join(timeout=5.0)
            for w in range(len(self.rings)):
                self.retire(w)
            shutil.rmtree(self.spill_dir, ignore_errors=True)
            self._finalizer.detach()


# ---------------------------------------------------------------------------
# The loader
# ---------------------------------------------------------------------------


class ParallelLoader:
    """Order-preserving multiprocess loader over a ``DataSet``.

    ``num_workers=0`` runs the SAME deterministically-seeded pipeline
    in-process (the serial reference the parallel stream is pinned
    byte-identical to); ``num_workers>0`` fans the per-sample chain out
    to forked worker processes with shared-memory rings.

    One live iterator at a time: each ``iter()`` call starts a new
    epoch (advancing the shuffle state exactly like serial epochs do);
    a second ``iter()`` while an epoch's iterator is open (neither
    exhausted nor closed) raises.

    The worker pool outlives an epoch: it is forked by the first epoch
    that needs one and serves every later epoch from the same processes
    and rings, the workers decoding the next epoch's first groups while
    this epoch's last ones drain.  An epoch that is closed early or
    raises stops the pool, and the next ``iter()`` forks a new one.
    While it idles between epochs a pool holds its rings
    (``num_workers x slots x slot_bytes`` of shared memory) and its
    processes; :meth:`close` releases them (the loader stays usable:
    the next epoch forks again), and so do the loader's collection and
    the interpreter's exit.

    Note on shared RNGs: the vision/augment transforms draw from the
    process-global ``random`` (pre-existing design) and numpy consumers
    from the loader-local :func:`sample_rng` Generator, so pinning them
    means ``seed_sample`` reseeds both per sample in whichever process
    runs the chain (numpy's process-GLOBAL RNG is never touched —
    seeded-rng-only rule).  With ``num_workers>0`` that is a forked
    worker; with ``num_workers=0`` it is THIS process (the prefetch
    thread, when composed with ``device_prefetch``) — code that draws
    from those RNGs concurrently with a serial-mode epoch will see
    sample-pinned values, exactly as it already would next to a
    ``ParallelTransformer`` thread pool.
    """

    def __init__(self, dataset, num_workers: int = 0, *,
                 base_seed: int = 0, group_size: Optional[int] = None,
                 slots: int = 4, slot_bytes: int = _DEFAULT_SLOT_BYTES,
                 max_respawns: int = 2, start_epoch: int = 0):
        if num_workers < 0:
            raise ValueError(f"num_workers must be >= 0, got {num_workers}")
        if num_workers > 0 and not getattr(dataset, "_order_deterministic",
                                           True):
            # every worker replays the raw stream independently; a
            # nondeterministically-ordered source (native_threads>0
            # record reader) would give each worker a DIFFERENT order
            # and the group partition would silently duplicate/drop
            # samples — refuse instead of corrupting the stream
            raise ValueError(
                "ParallelLoader(num_workers>0) requires a source with "
                "reproducible iteration order; this dataset's source is "
                "marked nondeterministic (e.g. from_record_files with "
                "native_threads>0) — use native_threads=0 or "
                "num_workers=0")
        self.dataset = dataset
        self.num_workers = num_workers
        self.base_seed = base_seed
        self.slots = max(2, slots)
        self.slot_bytes = slot_bytes
        self.max_respawns = max_respawns
        self._epoch = start_epoch
        if start_epoch:
            # resume contract (mid-epoch checkpoint restart): the caller
            # hands a FRESHLY-constructed dataset plus the checkpointed
            # epoch, and the loader owns BOTH halves of the coordinate —
            # the per-epoch seeding keys (stable_seed folds the epoch
            # index) AND the source's own per-epoch closure state
            # (e.g. from_arrays' reshuffle counter), which replay_batches
            # always had to advance by hand.  Without this, a resumed
            # process replays epoch 0's sample ORDER under epoch N's
            # seeds — a silently different stream.
            _advance_source_epochs(self.dataset._source_fn, start_epoch)
        self.leading, self.chain, self.trailing = split_stages(
            dataset._stages)
        # construction-time RNG signatures: the per-epoch reseed of
        # leading stream stages folds in the user's own seed choice
        self._stream_keys = stream_stage_keys(self.leading)
        if group_size is None:
            group_size = next((s.batch_size for s in self.trailing
                               if hasattr(s, "batch_size")), 32)
        self.group_size = max(1, int(group_size))
        # observability (tests + chaos drills read these)
        self.respawns = 0
        self.spills = 0
        #: epoch index of the most recently STARTED epoch (None before
        #: the first) — the anomaly sentinel records it as the replay
        #: coordinate of a bad batch (with base_seed + batch index, the
        #: determinism contract pins the batch; see replay_batches)
        self.last_epoch: Optional[int] = None
        self._pool: Optional[_Pool] = None
        self._epoch_open = False
        if num_workers > 0 and not hasattr(os, "fork"):  # pragma: no cover
            warnings.warn("platform lacks fork(); ParallelLoader falls "
                          "back to the serial path")
            self.num_workers = 0

    # -- public surface ----------------------------------------------------
    def __len__(self) -> int:
        return len(self.dataset)

    def worker_pids(self) -> List[int]:
        """Live worker PIDs of the pool (chaos drills)."""
        pool = self._pool
        return [p.pid for p in pool.procs if p.is_alive()] if pool else []

    def close(self) -> None:
        """Stop the worker pool: no child, no shared-memory segment and
        no spill directory is left.  Idempotent; the loader stays usable
        (the next ``iter()`` forks a new pool)."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.stop()

    def __iter__(self) -> Iterator[Any]:
        if self._epoch_open:
            # enforce the one-live-iterator contract: both iterators
            # would read the same rings, and the second would take the
            # first's groups for its own epoch's
            raise RuntimeError(
                "the previous epoch's iterator is still open — exhaust "
                "or close() it before starting a new epoch "
                "(ParallelLoader supports one live iterator)")
        epoch = self._epoch
        self._epoch += 1
        self.last_epoch = epoch
        if self.num_workers == 0:
            return self._serial_epoch(epoch)
        return self._apply_trailing(self._merged_samples(epoch))

    # -- serial reference path --------------------------------------------
    def _serial_epoch(self, epoch: int) -> Iterator[Any]:
        for stage, key in zip(self.leading, self._stream_keys):
            seed_rngs(stage, stable_seed("stream", self.base_seed, epoch,
                                         key))
        it: Iterator[Any] = iter(self.dataset._source_fn())
        for stage in self.leading:
            it = stage.apply_iter(it)

        def samples():
            for idx, sample in enumerate(it):
                seed_sample(self.chain, self.base_seed, epoch, idx)
                out = _apply_chain(self.chain, sample)
                if out is not None:
                    yield out

        return self._apply_trailing(samples())

    def _apply_trailing(self, it: Iterator[Any]) -> Iterator[Any]:
        for stage in self.trailing:
            it = stage.apply_iter(it)
        return it

    # -- parallel path ----------------------------------------------------
    def _spawn(self, pool: "_Pool", worker_id: int, epoch: int,
               start_group: int) -> Tuple[_Ring, mp.Process]:
        ring = _Ring(pool.ctx, self.slots, self.slot_bytes, pool.spill_dir)
        proc = pool.ctx.Process(
            target=_worker_main,
            args=(worker_id, self.num_workers, epoch, start_group, ring,
                  pool.stop_event, self.dataset._source_fn, self.leading,
                  self._stream_keys, self.chain, self.group_size,
                  self.base_seed),
            daemon=True)
        with warnings.catch_warnings():
            # CPython warns that fork + multithreaded jax may deadlock;
            # workers never touch jax (data/transform code only), which
            # is the specific hazard the warning is about.  Checked where
            # it matters (PR 21, TPU v5e, one chip and four): forked from
            # a trainer that already holds the TPU and libtpu's threads,
            # two workers fed 7 steps of SSD300 at batch 32 with no hang
            # and no child left alive afterwards — a child inherits the
            # parent's backend state and never calls into it
            warnings.filterwarnings(
                "ignore", message=".*fork.*", category=RuntimeWarning)
            proc.start()
        return ring, proc

    def _fork_pool(self, epoch: int) -> "_Pool":
        """A new pool whose workers begin at ``epoch``.  Forked children
        inherit the parent's source state verbatim, so the parent never
        consumes the source itself: it advances its copy once an epoch,
        at the epoch's end."""
        pool = _Pool(mp.get_context("fork"), epoch, self)
        try:
            for w in range(self.num_workers):
                ring, proc = self._spawn(pool, w, epoch, 0)
                pool.rings.append(ring)
                pool.procs.append(proc)
        except BaseException:
            pool.stop()
            raise
        return pool

    def _merged_samples(self, epoch: int) -> Iterator[Any]:
        self._epoch_open = True
        W = self.num_workers
        if self._pool is not None and self._pool.epoch != epoch:
            self.close()        # not the epoch its rings hold: start anew
        pool = self._pool
        # both stages are open until the first group has arrived, so
        # inside the consumer's first next() of the epoch — never across
        # a yield
        opening = contextlib.ExitStack()
        opening.enter_context(obs_span.stage("az/input/epoch_start",
                                             kept=pool is not None))
        ended = False
        try:
            if pool is None:
                opening.enter_context(obs_span.stage("az/input/pool_start",
                                                     workers=W))
                pool = self._pool = self._fork_pool(epoch)
            pool.respawns_left = self.max_respawns
            g = 0
            while True:
                samples = self._next_message(pool, g % W, g, epoch)
                if samples is None:
                    break
                opening.close()
                for sample in samples:
                    yield sample
                g += 1
            # every worker's end marker of this epoch: what follows in
            # its ring is the next epoch's
            for w in range(W):
                if w != g % W:
                    self._next_message(pool, w, g, epoch)
            pool.epoch = epoch + 1
            ended = True
        finally:
            opening.close()                 # no sample ever arrived
            self._epoch_open = False
            # closed early or raised: the pool stops FIRST (a failing
            # source advance must never leave workers spinning on live
            # rings)...
            if not ended:
                self.close()
            # ...then advance the parent's copy of the source state by
            # one epoch, so serial and parallel epochs stay
            # interchangeable.  Respawns fork from the UN-advanced state
            # of the epoch in flight — they happen only inside the loop,
            # never after this point.
            try:
                _advance_source_epochs(self.dataset._source_fn, 1)
            except BaseException:
                self.close()    # its workers have run on from that state
                raise

    def _next_message(self, pool: "_Pool", w: int, g: int, epoch: int):
        """Wait for worker ``w``'s next ring message, handling death.

        Returns group ``g``'s samples, or None for the worker's end
        marker of the epoch (at ``g`` groups: its counters are recorded).
        A dead worker with an empty ring is respawned from the group it still owes of the
        epoch in flight — deterministic seeding makes the respawn
        recompute the identical stream — until the epoch's respawn
        budget is exhausted, then PrefetchWorkerDied (retryable)
        escalates."""
        rings, procs = pool.rings, pool.procs
        while True:
            msg = rings[w].get(timeout=_POLL_S)
            if msg is None:
                if procs[w].is_alive():
                    continue
                # dead — drain the publish-vs-death race window before
                # declaring the ring empty
                msg = rings[w].get(timeout=0.0)
                if msg is None:
                    if pool.respawns_left <= 0:
                        raise PrefetchWorkerDied(
                            f"input worker {w} (pid {procs[w].pid}) died "
                            f"at group {g} with the respawn budget "
                            f"exhausted (max_respawns="
                            f"{self.max_respawns}) — input pipeline is "
                            "gone; restart the attempt")
                    pool.respawns_left -= 1
                    logger.warning(
                        "input worker %d died (exitcode %s); respawning "
                        "from group %d of epoch %d (%d respawns left)", w,
                        procs[w].exitcode, g, epoch, pool.respawns_left)
                    pool.retire(w)
                    rings[w], procs[w] = self._spawn(pool, w, epoch, g)
                    self.respawns += 1
                    continue
            kind, idx, obj = msg
            if kind == _KIND_ERR:
                try:
                    exc, tb = pickle.loads(obj)
                except Exception:
                    exc, tb = None, "<worker exception unpicklable — " \
                        "traceback lost in transit>"
                if exc is not None:
                    # chain the worker-side traceback (the parent-side
                    # raise alone would point only at this frame)
                    raise exc from RuntimeError(
                        f"input worker {w} traceback:\n{tb}")
                # unknown exception type: re-raise as a BARE RuntimeError
                # (NOT retryable PrefetchWorkerDied — a deterministic
                # programming error must propagate, never be retried;
                # docs/RESILIENCE.md fatal-propagation contract)
                raise RuntimeError(
                    f"input worker {w} raised an unpicklable exception:"
                    f"\n{tb}")
            if kind == _KIND_SPILL:
                self.spills += 1
                rings[w].spilled += 1
                kind = _KIND_GRP
            if idx != g:  # pragma: no cover - protocol bug
                raise PrefetchWorkerDied(
                    f"worker {w} sent group {idx} (kind {kind}), "
                    f"expected {g}")
            if kind == _KIND_END:
                _record_worker(w, _COUNTERS.unpack(obj), rings[w].spilled)
                rings[w].spilled = 0
                return None
            return obj


# ---------------------------------------------------------------------------
# Deterministic replay (anomaly forensics re-seek hook)
# ---------------------------------------------------------------------------


def replay_batches(dataset, epoch: int, batch_indices: Sequence[int],
                   base_seed: int = 0, batch_transform=None):
    """Re-materialize exact batches of ``epoch`` under the determinism
    contract — the forensics hook behind ``tools/replay_batch.py``.

    ``dataset`` must be FRESHLY CONSTRUCTED (its source at epoch-0
    state): a :class:`ParallelLoader` (its own ``base_seed``/grouping
    win) or a bare ``DataSet`` (wrapped on the serial path with
    ``base_seed``).  The source is fast-forwarded ``epoch`` epochs, the
    per-epoch/per-sample RNGs are re-pinned exactly as the live run
    pinned them — for ANY worker count, including the failed run's —
    and the requested 0-based batch indices of that epoch are returned
    as ``{index: batch}``.  ``batch_transform(batch, index)``
    post-processes each batch (drills re-apply a recorded injected
    corruption here so the replayed bytes match the recorded hash).
    """
    if isinstance(dataset, ParallelLoader):
        loader = ParallelLoader(dataset.dataset, 0,
                                base_seed=dataset.base_seed,
                                group_size=dataset.group_size)
    else:
        loader = ParallelLoader(dataset, 0, base_seed=base_seed)
    want = sorted({int(i) for i in batch_indices})
    if not want:
        return {}
    _advance_source_epochs(loader.dataset._source_fn, epoch)
    out = {}
    for i, batch in enumerate(loader._serial_epoch(epoch)):
        if i in want:
            out[i] = (batch_transform(batch, i) if batch_transform
                      else batch)
        if i >= want[-1]:
            break
    missing = [i for i in want if i not in out]
    if missing:
        raise ValueError(
            f"epoch {epoch} ended before batch index(es) {missing} — "
            "wrong epoch coordinate, or the dataset was not freshly "
            "constructed (its source state already advanced)")
    return out


def elastic_resume_coordinates(epoch: int, samples_into_epoch: int,
                               global_batch: int):
    """Translate a checkpoint's GLOBAL stream coordinate into loader
    re-seek terms under a (possibly different) batch geometry.

    The deterministic stream is defined over the merged global SAMPLE
    sequence — per-sample seeds fold the global index (``seed_sample``),
    batching is a trailing stage — so the stream itself is independent
    of world size and worker count.  What changes across an elastic
    resize is only how many samples each BATCH carries: a run that
    checkpointed ``samples_into_epoch`` samples into ``epoch`` resumes
    on any geometry by constructing the loader with
    ``start_epoch=epoch`` and skipping ``samples_into_epoch //
    global_batch`` whole batches of the new stream.

    Returns ``(start_epoch, skip_batches)``.  Raises ``ValueError``
    when the saved offset does not land on a batch boundary of the new
    stream — resuming there would re-train (or silently drop) a partial
    batch, so the geometries are incompatible (pick a global batch that
    divides the offset, or resume at the old geometry).
    """
    if epoch < 0 or samples_into_epoch < 0 or global_batch < 1:
        raise ValueError(
            f"elastic_resume_coordinates: invalid coordinate (epoch="
            f"{epoch}, samples={samples_into_epoch}, batch={global_batch})")
    if samples_into_epoch % global_batch:
        raise ValueError(
            f"elastic resume: sample offset {samples_into_epoch} is not "
            f"a multiple of the new global batch {global_batch} — the "
            f"checkpoint boundary does not land on a batch boundary of "
            f"the resumed stream")
    return int(epoch), samples_into_epoch // global_batch


# ---------------------------------------------------------------------------
# Device-overlap composition
# ---------------------------------------------------------------------------


def make_input_pipeline(dataset, mesh, num_workers: int = 0,
                        prefetch: int = 2, base_seed: int = 0,
                        loader: Optional[ParallelLoader] = None,
                        **loader_kw):
    """One-stop host→device input pipeline: multiprocess decode/augment
    (``ParallelLoader``) composed with ``device_prefetch`` so the packed
    H2D transfer of batch ``t+1`` overlaps the device step on ``t``.

    Returns an iterable; each ``iter()`` is one epoch of device-resident
    sharded batches, staying ``prefetch`` batches ahead."""
    from analytics_zoo_tpu.data.prefetch import PrefetchDataSet

    if loader is None:
        loader = ParallelLoader(dataset, num_workers, base_seed=base_seed,
                                **loader_kw)
    return PrefetchDataSet(loader, mesh, size=prefetch)
