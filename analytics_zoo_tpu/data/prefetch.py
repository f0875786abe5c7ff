"""Device prefetch: overlap host batch prep with device compute.

The reference hides data-prep latency by caching transformed RDD partitions
on executors (SURVEY.md §3.1 HOT LOOP #1); the TPU equivalent is a small
host-side pipeline that device_puts the next batch(es) while the current
step runs, double-buffering into HBM.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Iterable, Iterator, Optional

from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.parallel import mesh as mesh_lib
from analytics_zoo_tpu.resilience.errors import PrefetchWorkerDied


def _drain(q: "queue.Queue", stop: object, err: list, worker,
           poll_s: float = 0.2) -> Iterator[Any]:
    """Consumer side of the prefetch queue.

    A bare ``q.get()`` would block FOREVER if the worker thread died
    without enqueueing the stop sentinel (killed interpreter thread,
    c-extension abort) — the silent-hang failure mode.  Poll with a
    timeout instead and, when the queue is empty AND the worker is dead,
    raise a descriptive error: the worker's recorded exception if it left
    one, else :class:`PrefetchWorkerDied`."""
    def get():
        while True:
            try:
                return q.get(timeout=poll_s)
            except queue.Empty:
                if worker.is_alive():
                    continue
                # worker is gone, so nothing more can be enqueued — but
                # it may have delivered its tail (and the sentinel)
                # between our timeout and the liveness check: drain
                # before declaring death
                try:
                    return q.get_nowait()
                except queue.Empty:
                    if err:
                        raise err[0]
                    raise PrefetchWorkerDied(
                        "prefetch worker thread died without delivering "
                        "its stop sentinel (no exception recorded) — "
                        "input pipeline is gone; restart the attempt")

    while True:
        # closed before the yield: a stage is never held across one
        with stage("az/input/get_wait"):
            item = get()
        if item is stop:
            if err:
                raise err[0]
            return
        yield item


def device_prefetch(batches: Iterable[Any], mesh, size: int = 2,
                    close_source: bool = False) -> Iterator[Any]:
    """Yield device-resident, data-sharded batches, staying ``size`` ahead.

    Early consumer exit (e.g. the train loop breaking on ``end_when``) is
    handled: closing the generator signals the worker to stop, so no thread
    is left blocked holding device buffers.

    ``close_source=True`` additionally closes ``batches`` itself when the
    stream ends or is cancelled — FROM THE WORKER THREAD, which is the
    only thread ever executing the source generator (a consumer-side
    ``close()`` on a generator suspended inside another thread's
    ``next()`` raises).  Use it when the source owns real resources —
    e.g. a multiprocess ``ParallelLoader`` epoch: closing one that was
    cancelled midway stops the loader's worker pool, closing one that
    ran to its end does nothing (the pool is kept for the next epoch;
    ``ParallelLoader.close()`` ends it).  Leave it False when the caller
    reuses the source across several prefetch streams
    (``bench_overlap``).
    """
    if size < 1:
        # a non-positive maxsize would make the Queue UNBOUNDED and the
        # worker would transfer the whole epoch into HBM ahead of compute
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = object()
    cancelled = threading.Event()
    err: list = []

    def worker():
        try:
            source = iter(batches)
            while True:
                with stage("az/input/next"):
                    b = next(source, stop)
                if b is stop:
                    break
                with stage("az/input/place"):
                    item = mesh_lib.shard_batch(b, mesh)
                with stage("az/input/put_wait"):
                    while not cancelled.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                if cancelled.is_set():
                    return
        except BaseException as e:  # propagate to consumer
            err.append(e)
        finally:
            if close_source and hasattr(batches, "close"):
                try:
                    batches.close()
                except Exception:  # noqa: BLE001 - cleanup best-effort
                    pass
            # Block until the stop sentinel fits — NEVER pop queued real
            # batches to make room (a slow consumer keeps the queue full
            # at end-of-stream, and popping would silently drop batches).
            # A cancelled consumer is gone and needs no sentinel.
            while not cancelled.is_set():
                try:
                    q.put(stop, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        yield from _drain(q, stop, err, t)
    finally:
        cancelled.set()
        if close_source:
            # Wait for the worker to actually finish: its cleanup
            # (closing a cancelled multiprocess loader epoch = reaping
            # the pool's worker processes + advancing the source state)
            # must COMPLETE before control returns to the consumer — an
            # immediately restarted epoch would otherwise fork new
            # workers from the not-yet-advanced source state (replaying
            # the old shuffle order) while two pools briefly coexist.
            # Bounded: the worker observes ``cancelled`` within one
            # batch production.  Without close_source there is nothing to
            # reap, and blocking here would stall the very paths (e.g.
            # StallWatchdog recovery around a hung source) that close
            # early.  The timeout bounds stall-recovery latency when
            # the source itself is the thing that hung — but a timeout
            # means the completion invariant did NOT hold, so say so.
            t.join(timeout=5.0)
            if t.is_alive():
                import logging

                logging.getLogger("analytics_zoo_tpu").warning(
                    "prefetch worker still closing its source after the "
                    "5s grace — an immediately restarted epoch may fork "
                    "workers from a stale source state")


class PrefetchDataSet:
    """Wrap a DataSet so every epoch iterates device-resident batches.

    ``size`` is the staging depth: 2 = double buffering (batch ``t+1``
    transfers while the step runs on ``t``), 3 = triple.  ``num_workers
    > 0`` additionally fans the host decode/augment work out to that
    many processes (``data.parallel.ParallelLoader``) before the
    overlapped H2D stage — the full host-input pipeline in one wrapper.
    Early consumer exit closes the host iterator too, which stops the
    loader's worker pool; after an epoch that ran to its end the pool is
    kept for the next one, until :meth:`close`."""

    def __init__(self, dataset, mesh, size: int = 2, num_workers: int = 0,
                 base_seed: int = 0, **loader_kw):
        if num_workers > 0:
            from analytics_zoo_tpu.data.parallel import ParallelLoader
            dataset = ParallelLoader(dataset, num_workers,
                                     base_seed=base_seed, **loader_kw)
        self.dataset = dataset
        self.mesh = mesh
        self.size = size

    def __iter__(self):
        # close_source: the epoch iterator (possibly a multiprocess
        # loader owning worker processes) is closed by the prefetch
        # worker thread itself — the only thread executing it
        return device_prefetch(iter(self.dataset), self.mesh, self.size,
                               close_source=True)

    def __len__(self):
        return len(self.dataset)

    def close(self) -> None:
        """Close the wrapped data set, if it has a ``close`` (a
        ``ParallelLoader``'s stops its worker pool)."""
        if hasattr(self.dataset, "close"):
            self.dataset.close()


def overlap_window(items, dispatch, consume, max_inflight: int = 4) -> None:
    """Bounded-window overlap of host prep / device execution / readback.

    ``dispatch(item)`` must be async (a jit call returning a token);
    ``consume(token)`` forces the result to host and processes it.  Up to
    ``max_inflight`` items are in flight, so device execution and
    readback overlap with the next items' host prep WITHOUT
    letting the whole dataset's input buffers accumulate in HBM.  Used by
    the serving predictors, the Validator, and the ASR pipeline."""
    from collections import deque

    pending: "deque" = deque()
    for item in items:
        pending.append(dispatch(item))
        if len(pending) >= max_inflight:
            consume(pending.popleft())
    while pending:
        consume(pending.popleft())
