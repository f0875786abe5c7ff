"""The program's own stages (``analytics_zoo_tpu.obs.stage``, names under
``az/``) as the benchmark reads them, from the two places they land:

- the process's stage ring, cut to the measured window — every batch or
  step of the window on ``time.monotonic()``, traced run or not;
- the traced slice's ``/host:CPU`` plane, one list of events a host line
  (thread), on the clock of the device's ``XLA Ops`` line — so a stage can
  be laid against the device's idle gaps.

A stage's SELF time on its line is its interval less what its children
on the same line cover; a LEAF is a stage no other stage ran inside.  A
program without stages (the parent of the PR that brought them) gives
``None`` everywhere and the readers leave their metrics out.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import harness, trace_reduce

PREFIX = "az/"

Event = Tuple[str, float, float]                # name, start s, end s
Piece = Tuple[str, float, float, bool]          # name, start s, end s, leaf


def ring(ctx, reach_back: bool = False) -> Optional[list]:
    """The window's stage records (``.name .t0 .t1 .thread .attrs``),
    oldest first: the stages that began in the window.  With
    ``reach_back``, everything the ring still holds of the time before it
    as well — for what is there once an epoch, which a short window (a
    traced run's is 20 steps of an epoch of 32) need not see begin."""
    t_open = ctx["window"].get("t_open")
    if t_open is None:
        return None
    try:
        from analytics_zoo_tpu.obs import stages
    except ImportError:
        return None
    return stages(since=None if reach_back else t_open)


def feeding(ctx, name: str) -> list:
    """The records called ``name`` of the loader pools that fed the window:
    what began in the window, and what began since the last
    ``az/input/pool_start`` before it.  That pool delivers the window's
    first batches, and its workers may be done before the window opens
    (they fill the rings in 0.9 s of a 6 s epoch, PERF.md section 5)."""
    t_open = ctx["window"].get("t_open")
    records = ring(ctx, reach_back=True) or ()      # none: t_open unused
    since = max((r.t0 for r in records
                 if r.name == POOL_START and r.t0 < t_open), default=t_open)
    return [r for r in records if r.name == name and r.t0 >= since]


POOL_START = "az/input/pool_start"
WORKER = "az/input/worker"      # a forked worker's life, on no thread


def ring_lines(records) -> List[List[Event]]:
    """Ring records — ``StageRecord``s, or the same as lists from a JSON
    dump — as one list of events a thread, without the workers'."""
    lines: Dict[int, List[Event]] = {}
    for name, t0, t1, thread, _ in records:
        if name != WORKER:
            lines.setdefault(thread, []).append((name, t0, t1))
    return list(lines.values())


def lines_of_planes(planes) -> List[List[Event]]:
    """The ``az/`` events of the host plane, one list a line that has
    any.  ``planes`` as ``trace_reduce.reduce_planes`` takes them.  Reads
    that plane alone: the device's operations are in the harness's own
    reduction already."""
    lines = []
    for plane in planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events if e.name.startswith(PREFIX)]
            if events:
                lines.append(events)
    return lines


@functools.lru_cache(maxsize=1)
def host_lines(path: str) -> List[List[Event]]:
    """:func:`lines_of_planes` of an ``.xplane.pb``, read once a process."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)       # alive while its planes are read
    return lines_of_planes(data.planes)


def traced_lines(ctx) -> Optional[List[List[Event]]]:
    """The traced slice's stages by host line.  ``ctx`` carries no path;
    the harness writes a run's trace under ``harness.WORK/<cell>/trace``
    and empties that directory first, so the newest file there is this
    run's."""
    if ctx.get("trace") is None:
        return None
    path = trace_reduce.find_xplane(os.path.join(harness.WORK, "*", "trace"))
    if path is None:
        return None
    return host_lines(path) or None


def self_pieces(events: Sequence[Event]) -> List[Piece]:
    """One line's stages cut into the pieces that no child covers, in
    time order.  Stages of one thread nest, so a stack follows them."""
    out: List[Piece] = []
    stack: List[list] = []          # [name, end, cursor, has_child]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor, has_child = stack.pop()
            if end > cursor:
                out.append((name, cursor, end, not has_child))

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            parent = stack[-1]
            end = min(end, parent[1])
            if start > parent[2]:
                out.append((parent[0], parent[2], start, False))
            parent[2], parent[3] = end, True
        stack.append([name, end, start, False])
    close(float("inf"))
    return sorted(out, key=lambda p: p[1])


def overlap_by_name(pieces: Sequence[Piece],
                    gaps: Sequence[Tuple[float, float]],
                    leaves_only: bool = False) -> Dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) under each stage's pieces
    (sorted, disjoint: one line's)."""
    total: Dict[str, float] = {}
    g = 0
    for name, start, end, leaf in pieces:
        if leaves_only and not leaf:
            continue
        while g < len(gaps) and gaps[g][1] <= start:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < end:
            total[name] = (total.get(name, 0.0)
                           + min(end, gaps[k][1]) - max(start, gaps[k][0]))
            k += 1
    return total


def line_of(lines: Sequence[List[Event]], name: str) -> Optional[List[Event]]:
    """The line that carries most events called ``name``."""
    def count(events):
        return sum(e[0] == name for e in events)

    best = max(lines, key=count, default=None)
    return best if best is not None and count(best) else None


def device_gaps(reduction) -> List[Tuple[float, float]]:
    """The first chip's idle stretches between its first and last
    operation of the traced slice."""
    if not reduction.devices:
        return []
    return trace_reduce.gaps([(s, s + d)
                              for _, s, d in reduction.devices[0].ops])
