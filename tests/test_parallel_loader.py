"""Multiprocess input pipeline (data.parallel): determinism pinned
byte-identical to the serial path, the worker pool kept from epoch to
epoch (and gone on close(), collection and exit), worker-crash ->
respawn -> PrefetchWorkerDied escalation, ring spill fallback, and the
tier-1 smoke over the real SSD chain (2 workers, tiny synthetic set).

A wait on a pool that this file adds is bounded by the test itself
(``bounded``, ``gone``): a hung pool fails its test and does not eat the
suite's clock."""

import gc
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.data import (
    DataSet,
    FnTransformer,
    ParallelLoader,
    ParallelTransformer,
    RandomTransformer,
    ShuffleBuffer,
)
from analytics_zoo_tpu.data.parallel import seed_rngs, split_stages, stable_seed
from analytics_zoo_tpu.resilience.errors import PrefetchWorkerDied


def _rng_ds():
    """Dataset whose stream exercises every RNG surface the loader must
    pin: source shuffle, a held-Random transformer, global random AND
    the loader-local numpy sample Generator (the sanctioned replacement
    for global ``np.random`` draws — seeded-rng-only rule)."""
    from analytics_zoo_tpu.data import sample_rng

    ds = DataSet.from_list(list(range(40)), shuffle=True, seed=4)
    aug = RandomTransformer(FnTransformer(lambda x: x + 1000), prob=0.5)
    noise = FnTransformer(
        lambda x: (x, round(random.random(), 6),
                   float(sample_rng().random())))
    return (ds.transform(aug).transform(noise)
            .batch(8, collate_fn=lambda b: b, drop_remainder=False))


def _array_ds(n=24, sleep=0.0):
    ds = DataSet.from_arrays(x=np.arange(n * 4, dtype=np.float32).reshape(n, 4))

    def fn(s):
        if sleep:
            time.sleep(sleep)
        return {"x": s["x"] * 2, "img": np.full((16, 16), s["x"][0])}

    return ds.transform(FnTransformer(fn)).batch(4)


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert repr(type(x)) == repr(type(y))
        if isinstance(x, dict):
            assert sorted(x) == sorted(y)
            for k in x:
                np.testing.assert_array_equal(x[k], y[k], err_msg=str(k))
        else:
            assert repr(x) == repr(y)


def bounded(fn, seconds=60.0):
    """``fn()`` on a thread of its own, given up after ``seconds``."""
    out = []

    def run():
        try:
            out.append((fn(), None))
        except BaseException as e:  # noqa: BLE001 - raised again below
            out.append((None, e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"the loader hung for {seconds} s"
    value, error = out[0]
    if error is not None:
        raise error
    return value


def gone(pid, seconds=5.0):
    """True once no process ``pid`` is left (a zombie counts as gone
    only when reaped: the loader joins what it forked)."""
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.05)
    return False


def holdings(loader):
    """What a live pool holds outside the process: (worker pids, paths of
    its rings' shared-memory segments, its spill directory)."""
    pool = loader._pool
    return (loader.worker_pids(),
            [os.path.join("/dev/shm", r.shm.name.lstrip("/"))
             for r in pool.rings], pool.spill_dir)


def assert_released(pids, segments, spill_dir):
    assert all(gone(pid) for pid in pids), pids
    assert not [p for p in segments if os.path.exists(p)]
    assert not os.path.exists(spill_dir)


@pytest.mark.parametrize("workers", [1, 2, 3])
def test_byte_identical_across_worker_counts_and_epochs(workers):
    """Three epochs of a reshuffling source from ONE pool: the kept
    workers reseed and re-open the source as a fresh fork would."""
    serial = ParallelLoader(_rng_ds(), 0, base_seed=9)
    ref = [list(serial) for _ in range(3)]
    assert len({repr(e) for e in ref}) == 3  # epochs genuinely differ
    loader = ParallelLoader(_rng_ds(), workers, base_seed=9)
    got, pids = [], []
    for _ in range(3):
        got.append(bounded(lambda: list(loader)))
        pids.append(sorted(loader.worker_pids()))
    assert repr(got) == repr(ref)
    # the same processes served all three
    assert len(pids[0]) == workers and pids[0] == pids[1] == pids[2]
    loader.close()


def test_ndarray_payloads_through_ring():
    ref = list(ParallelLoader(_array_ds(), 0))
    got = list(ParallelLoader(_array_ds(), 2))
    _assert_batches_equal(ref, got)


def _shuffled_ds(sleep=0.01):
    """24 rows reshuffled every epoch, so a respawn that forked from
    another epoch's source state would show."""
    ds = DataSet.from_arrays(shuffle=True, seed=3,
                             x=np.arange(96, dtype=np.float32).reshape(24, 4))

    def fn(s):
        time.sleep(sleep)
        return {"x": s["x"] * 2}

    return ds.transform(FnTransformer(fn)).batch(4)


@pytest.mark.parametrize("kill_epoch", [0, 1])
def test_worker_crash_respawns_and_stream_is_unchanged(kill_epoch):
    """A worker lost in the pool's first epoch, or in the SECOND epoch of
    a kept pool, is re-forked from the group it owes of the epoch in
    flight; the respawned worker too runs on into the next epoch."""
    serial = ParallelLoader(_shuffled_ds(), 0)
    ref = [list(serial) for _ in range(kill_epoch + 2)]
    loader = ParallelLoader(_shuffled_ds(), 2, max_respawns=2)
    got = [bounded(lambda: list(loader)) for _ in range(kill_epoch)]
    it = iter(loader)
    epoch = [next(it)]
    pids = loader.worker_pids()
    assert len(pids) == 2
    os.kill(pids[0], signal.SIGKILL)         # chaos: lose one worker
    epoch.extend(bounded(lambda: list(it)))
    got.append(epoch)
    assert loader.respawns >= 1
    after = loader.worker_pids()
    assert len(after) == 2 and pids[0] not in after and pids[1] in after
    got.append(bounded(lambda: list(loader)))
    assert sorted(loader.worker_pids()) == sorted(after)
    for want, have in zip(ref, got):
        _assert_batches_equal(want, have)
    loader.close()


def test_the_respawn_budget_is_an_epochs():
    """``max_respawns`` a epoch, in a kept pool too: one loss in each of
    two epochs passes with a budget of one."""
    ref = list(ParallelLoader(_array_ds(sleep=0.01), 0))
    loader = ParallelLoader(_array_ds(sleep=0.01), 2, max_respawns=1)
    for _ in range(2):
        it = iter(loader)
        got = [next(it)]
        os.kill(loader.worker_pids()[0], signal.SIGKILL)
        got.extend(bounded(lambda: list(it)))
        _assert_batches_equal(ref, got)
    assert loader.respawns == 2
    loader.close()


def test_crash_escalates_to_prefetch_worker_died():
    loader = ParallelLoader(_array_ds(sleep=0.01), 2, max_respawns=0)
    it = iter(loader)
    next(it)
    for pid in loader.worker_pids():
        os.kill(pid, signal.SIGKILL)
    with pytest.raises(PrefetchWorkerDied, match="respawn budget"):
        list(it)


def test_prefetch_worker_died_is_retryable():
    from analytics_zoo_tpu.resilience.errors import retryable_errors

    assert PrefetchWorkerDied in retryable_errors()


def test_worker_exception_propagates_original_type():
    def bad(s):
        if float(s["x"][0]) > 100:
            raise ValueError("poison sample")
        return s

    ds = (DataSet.from_arrays(x=np.arange(256, dtype=np.float32).reshape(32, 8))
          .transform(FnTransformer(bad)).batch(8))
    with pytest.raises(ValueError, match="poison sample"):
        list(ParallelLoader(ds, 2))


def test_oversize_group_spills_and_stays_correct():
    ds = (DataSet.from_arrays(x=np.arange(32, dtype=np.float32))
          .transform(FnTransformer(
              lambda s: {"big": np.full((64, 64), s["x"])}))
          .batch(8))
    loader = ParallelLoader(ds, 2, slot_bytes=4096)
    got = list(loader)
    assert loader.spills > 0
    _assert_batches_equal(list(ParallelLoader(ds, 0)), got)


def test_early_close_stops_the_pool_and_the_next_epoch_forks_anew():
    serial = ParallelLoader(_shuffled_ds(0), 0)
    ref = [list(serial) for _ in range(3)]
    loader = ParallelLoader(_shuffled_ds(0), 2)
    it = iter(loader)
    _assert_batches_equal(ref[0][:1], [next(it)])
    held = holdings(loader)
    it.close()                               # after one batch
    assert not loader.worker_pids() and loader._pool is None
    assert_released(*held)
    # from a new pool, still the serial stream: the closed epoch counted
    got = bounded(lambda: list(loader))
    assert not set(loader.worker_pids()) & set(held[0])
    _assert_batches_equal(ref[1], got)
    _assert_batches_equal(ref[2], bounded(lambda: list(loader)))
    loader.close()


def test_an_epoch_that_raises_stops_the_pool():
    def bad(s):
        if float(s["x"][0]) > 100:
            raise ValueError("poison sample")
        return s

    ds = (DataSet.from_arrays(x=np.arange(256, dtype=np.float32).reshape(32, 8))
          .transform(FnTransformer(bad)).batch(8))
    loader = ParallelLoader(ds, 2)
    it = iter(loader)
    next(it)
    held = holdings(loader)
    with pytest.raises(ValueError, match="poison sample"):
        bounded(lambda: list(it))
    assert loader._pool is None
    assert_released(*held)


def test_a_second_iter_on_an_open_epoch_raises():
    loader = ParallelLoader(_array_ds(), 2)
    it = iter(loader)
    next(it)
    with pytest.raises(RuntimeError, match="still open"):
        iter(loader)
    rest = bounded(lambda: list(it))         # exhausted: no longer open
    assert len(rest) == 5
    again = iter(loader)                     # the kept pool's next epoch
    next(again)
    again.close()                            # closed: no longer open
    assert len(bounded(lambda: list(loader))) == 6
    loader.close()


def _collected(loader):
    del loader
    gc.collect()


@pytest.mark.parametrize("end", [
    lambda loader: loader.close(),
    lambda loader: (loader.close(), loader.close()),    # idempotent
    _collected,
], ids=["close", "close_twice", "collected"])
def test_a_kept_pool_is_released(end):
    """After two whole epochs the pool idles, alive; ``close()`` or the
    loader's collection leaves no child, no shared-memory segment and no
    spill directory."""
    loader = ParallelLoader(_array_ds(), 2)
    for _ in range(2):
        assert len(bounded(lambda: list(loader))) == 6
    held = holdings(loader)
    assert len(held[0]) == 2 and all(map(os.path.exists, held[1]))
    assert os.path.isdir(held[2])
    bounded(lambda: end(loader))
    del loader
    assert_released(*held)


_EXITS_WITHOUT_CLOSING = """
import json, os, sys
import numpy as np
from analytics_zoo_tpu.data import DataSet, FnTransformer, ParallelLoader

ds = (DataSet.from_arrays(x=np.arange(96, dtype=np.float32).reshape(24, 4))
      .transform(FnTransformer(lambda s: {"x": s["x"] * 2})).batch(4))
loader = ParallelLoader(ds, 2)
assert len(list(loader)) == len(list(loader)) == 6
pool = loader._pool
print(json.dumps({"pids": loader.worker_pids(),
                  "segments": [r.shm.name for r in pool.rings],
                  "spill_dir": pool.spill_dir}))
"""


def test_a_process_that_exits_without_closing_leaves_nothing():
    """The benchmark's feed exposes no ``close``: the interpreter's exit
    ends the pool, and ``resource_tracker`` finds nothing to warn of."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", _EXITS_WITHOUT_CLOSING], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    held = json.loads(done.stdout.strip().splitlines()[-1])
    assert len(held["pids"]) == 2
    assert_released(held["pids"],
                    [os.path.join("/dev/shm", n.lstrip("/"))
                     for n in held["segments"]], held["spill_dir"])
    assert "resource_tracker" not in done.stderr, done.stderr
    assert "leaked" not in done.stderr, done.stderr


def test_split_stages_classification():
    chain = FnTransformer(lambda x: x) >> FnTransformer(lambda x: x)
    stages = [ShuffleBuffer(4), ParallelTransformer(chain, 4),
              FnTransformer(lambda x: x),
              _rng_ds()._stages[-1]]          # the Batcher
    leading, per_sample, trailing = split_stages(stages)
    assert [type(s).__name__ for s in leading] == ["ShuffleBuffer"]
    assert len(per_sample) == 3               # chain unwrapped + Fn
    assert [type(s).__name__ for s in trailing] == ["Batcher"]


def test_nested_parallel_transformer_still_applies():
    """Regression: a ParallelTransformer nested INSIDE a chain must
    dissolve into its inner transform, not survive as an identity."""
    inner = ParallelTransformer(FnTransformer(lambda x: x * 10), 4)
    chain = FnTransformer(lambda x: x + 1) >> inner
    _, per_sample, _ = split_stages([chain])
    assert not any(isinstance(s, ParallelTransformer) for s in per_sample)
    ds = DataSet.from_list([1, 2, 3]).transform(chain).batch(
        3, collate_fn=lambda b: b)
    for w in (0, 2):
        assert list(ParallelLoader(ds, w)) == [[20, 30, 40]], w


def test_oversize_inband_meta_spills():
    """Regression: a group whose IN-BAND pickle (bytes payloads) alone
    exceeds slot_bytes must spill, not raise."""
    ds = (DataSet.from_list(list(range(8)))
          .transform(FnTransformer(lambda x: {"jpeg": bytes([x]) * 8192}))
          .batch(4, collate_fn=lambda b: b))
    loader = ParallelLoader(ds, 2, slot_bytes=4096)
    got = list(loader)
    assert loader.spills > 0
    assert got == list(ParallelLoader(ds, 0))


def test_user_shuffle_seed_survives_loader_reseed():
    """Regression: the per-epoch stream-stage reseed must FOLD IN the
    user's own seed (DataSet.shuffle(seed=...)), not overwrite it."""
    def stream(seed, w):
        ds = (DataSet.from_list(list(range(30))).shuffle(8, seed=seed)
              .batch(5, collate_fn=lambda b: b))
        return list(ds.parallel(w, base_seed=0))

    assert stream(1, 2) != stream(2, 2)       # seeds distinguish
    assert stream(1, 0) == stream(1, 2)       # serial == parallel


def test_nondeterministic_source_refused():
    ds = DataSet.from_list([1, 2, 3]).batch(2, collate_fn=lambda b: b)
    ds._order_deterministic = False           # e.g. native_threads>0
    with pytest.raises(ValueError, match="reproducible iteration order"):
        ParallelLoader(ds, 2)
    ParallelLoader(ds, 0)                     # serial path still fine


def test_seed_rngs_deterministic_and_stable_seed():
    assert stable_seed("a", 1) == stable_seed("a", 1)
    assert stable_seed("a", 1) != stable_seed("a", 2)
    r1, r2 = random.Random(), random.Random()
    seed_rngs([r1], 123)
    seed_rngs([r2], 123)
    assert [r1.random() for _ in range(4)] == [r2.random() for _ in range(4)]


def test_prefetch_dataset_with_workers_yields_device_batches():
    from analytics_zoo_tpu.data import PrefetchDataSet
    from analytics_zoo_tpu.parallel import create_mesh

    def make_ds():        # batch 8: shards over the virtual 8-device mesh
        ds = DataSet.from_arrays(
            x=np.arange(24 * 4, dtype=np.float32).reshape(24, 4))
        return ds.transform(
            FnTransformer(lambda s: {"x": s["x"] * 2})).batch(8)

    mesh = create_mesh()
    ref = list(ParallelLoader(make_ds(), 0))
    seen = [b for b in PrefetchDataSet(make_ds(), mesh, size=2,
                                       num_workers=2)]
    assert len(seen) == len(ref)
    for r, d in zip(ref, seen):
        np.testing.assert_array_equal(r["x"], np.asarray(d["x"]))


def test_dataset_batch_num_workers_wiring():
    ds = DataSet.from_list(list(range(16))).transform(
        FnTransformer(lambda x: x * 3))
    loader = ds.batch(4, collate_fn=lambda b: b, num_workers=2)
    assert isinstance(loader, ParallelLoader)
    assert list(loader) == [[0, 3, 6, 9], [12, 15, 18, 21],
                            [24, 27, 30, 33], [36, 39, 42, 45]]


def test_ssd_chain_smoke_two_workers(tmp_path):
    """Tier-1 smoke (ISSUE r5 satellite): the REAL SSD augmentation
    chain through 2 worker processes on a tiny synthetic set, pinned
    byte-identical to the serial loader.  Small enough for CPU CI."""
    from analytics_zoo_tpu.data import generate_shapes_records
    from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                 load_train_set)

    generate_shapes_records(str(tmp_path / "s"), n_images=16,
                            resolution=64, num_shards=2, seed=0)
    pattern = str(tmp_path / "s-*.azr")

    def batches(wp):
        param = PreProcessParam(batch_size=4, resolution=64, max_gt=8,
                                worker_processes=wp, loader_seed=7)
        ds = load_train_set(pattern, param)
        if wp == 0:
            # same deterministic seeding regime as the parallel loader
            ds = ParallelLoader(load_train_set(pattern, param), 0,
                                base_seed=7)
        return list(ds)

    ref = batches(0)
    got = batches(2)
    assert len(ref) == len(got) > 0
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a["input"], b["input"])
        for k in ("bboxes", "labels", "mask"):
            np.testing.assert_array_equal(a["target"][k], b["target"][k])


def test_asr_train_set_parallel(tmp_path):
    """DS2 wiring: host featurization fans out and stays deterministic."""
    from analytics_zoo_tpu.pipelines.deepspeech2 import load_asr_train_set

    rng = np.random.RandomState(0)
    samples = rng.randn(12, 16000).astype(np.float32) * 0.1
    labels = rng.randint(1, 29, (12, 6)).astype(np.int32)
    ref = list(load_asr_train_set(samples, labels, batch_size=4,
                                  worker_processes=0).parallel(0))
    got = list(load_asr_train_set(samples, labels, batch_size=4,
                                  worker_processes=2))
    assert len(ref) == len(got) == 3
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a["input"], b["input"])
        np.testing.assert_array_equal(a["labels"], b["labels"])
        np.testing.assert_array_equal(a["label_mask"], b["label_mask"])


def test_start_epoch_resume_replays_interrupted_epoch_stream():
    """Resume contract (ISSUE 9 preemption drill): a FRESH loader built
    with ``start_epoch=N`` over a freshly-constructed per-epoch-shuffling
    source must yield byte-identically the stream epoch N of an
    uninterrupted loader produced — both the seeding keys AND the
    source's own reshuffle closure must land on the epoch-N coordinate
    (the latter silently stayed at epoch 0 before the fix)."""

    def fresh():
        return (DataSet.from_arrays(shuffle=True, seed=3,
                                    x=np.arange(96, dtype=np.float32)
                                    .reshape(24, 4))
                .batch(4))

    for workers in (0, 2):
        full = fresh().parallel(workers, base_seed=7)
        _ = list(full)                       # epoch 0 consumed
        epoch1_ref = list(full)              # the "interrupted" epoch
        resumed = fresh().parallel(workers, base_seed=7, start_epoch=1)
        epoch1_resumed = list(resumed)
        assert len(epoch1_ref) == len(epoch1_resumed) == 6
        for a, b in zip(epoch1_ref, epoch1_resumed):
            np.testing.assert_array_equal(a["x"], b["x"])
