"""Structured spans: named, timed, parented intervals under a trace id.

The unit of the telemetry spine.  A *trace* is every span sharing one
``trace_id`` — e.g. one serving request's life (``request`` root →
``queue`` → ``dispatch`` children) or one train step at its loader
coordinates.  Trace ids are DERIVED from domain identity (request rid,
``(epoch, batch)``), never random, so the same seeded run produces the
same trace ids and the flight-recorder dump replays byte-identically.

Spans are recorded into the flight recorder when they END (one event
per span, carrying start/end/duration), which keeps the hot path to two
clock reads and one deque append.

:func:`stage` is the other kind of interval: a host STAGE of a batch, a
step or an epoch (collate, transfer, dispatch, the wait on a queue), on
the profiler's clock.  It needs no ``Observability`` bundle and is
always on — see its docstring for what that forbids.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from analytics_zoo_tpu.obs.recorder import FlightRecorder
from analytics_zoo_tpu.utils.clock import TimeSource, as_now_fn


class StageRecord(NamedTuple):
    """One closed :func:`stage`: ``t0``/``t1`` are ``time.monotonic()``
    seconds, ``thread`` the ``threading.get_ident()`` it ran on."""

    name: str
    t0: float
    t1: float
    thread: int
    attrs: Dict[str, Any]


#: the stages' clock: real monotonic seconds, whatever clock a drill
#: injects elsewhere — a stage is laid against the profiler's trace and a
#: benchmark's own ``time.monotonic()`` stamps, not against modelled time
now = as_now_fn(None)

#: every closed stage of the process, oldest dropped first
_STAGES: "collections.deque[StageRecord]" = collections.deque(maxlen=1 << 16)


class stage:
    """``with stage(name, **attrs):`` — one fully nested, per-thread host
    stage.  On entry it opens a ``jax.profiler.TraceAnnotation(name)``:
    with a profiler session running that is an event on the
    ``/host:CPU`` plane, on the same clock as the device's ``XLA Ops``
    line; with none it is a no-op of about a microsecond.  On exit (also
    on an exception) it appends a :class:`StageRecord` to the process's
    one bounded ring, which :func:`stages` reads.

    Always on, because nothing outside the program can switch it on.
    So: at batch / step / epoch granularity only — never per request,
    per sample or inside a jitted function; never with a fence, a
    ``block_until_ready`` or a device read that the code it brackets
    does not make anyway; never held across a ``yield`` (annotations
    close last-in-first-out on their thread).  Names are declared in
    ``obs/names.py::STAGES``.

    A generator that cannot use ``with`` (the stage ends before its
    first ``yield``) calls ``__enter__`` / ``__exit__`` itself."""

    __slots__ = ("name", "attrs", "_annotation", "_t0")

    def __init__(self, name: str, **attrs: Any):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "stage":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = now()
        self._annotation.__exit__(*exc)
        _STAGES.append(StageRecord(self.name, self._t0, t1,
                                   threading.get_ident(), self.attrs))


def record_stage(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """A stage measured where no :func:`stage` can go (a forked loader
    worker reports its counters through shared memory; the parent
    records them when the pool closes)."""
    _STAGES.append(StageRecord(name, t0, t1, threading.get_ident(), attrs))


def stages(since: Optional[float] = None) -> List[StageRecord]:
    """The ring's records, oldest first; with ``since`` (a
    ``time.monotonic()`` reading, e.g. a benchmark window's opening)
    only the stages that began at or after it."""
    while True:
        try:
            out = list(_STAGES)
            break
        except RuntimeError:        # another thread appended meanwhile
            continue
    if since is not None:
        out = [r for r in out if r.t0 >= since]
    return out


class Span:
    """One in-flight interval.  Created by :meth:`Tracer.start`; call
    :meth:`end` exactly once (idempotent-guarded) with the terminal
    status.  ``attrs`` merge across start and end."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "t_start", "t_end", "status", "attrs")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: int, parent_id: Optional[int], t_start: float,
                 attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t_start = t_start
        self.t_end: Optional[float] = None
        self.status: Optional[str] = None
        self.attrs = attrs

    @property
    def ended(self) -> bool:
        return self.t_end is not None

    def end(self, status: str = "ok", at: Optional[float] = None,
            **attrs: Any) -> None:
        """Close the span and emit it to the recorder.  A second call is
        a no-op (the serving shed paths can race a drain force-flush for
        who closes a request; first writer wins).  ``at`` stamps an
        explicit end instant instead of the clock read — the parallel
        service model computes each batch's completion on its replica's
        busy horizon, a future instant the clock has not reached when the
        dispatch bookkeeping runs."""
        if self.ended:
            return
        self.attrs.update(attrs)
        self.t_end = self.tracer.now() if at is None else float(at)
        self.status = status
        self.tracer._emit(self)

    def event(self) -> Dict[str, Any]:
        ev: Dict[str, Any] = {
            "kind": "span",
            "name": self.name,
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "t0": round(self.t_start, 6),
            "t1": round(self.t_end, 6) if self.t_end is not None else None,
            "dur": (round(self.t_end - self.t_start, 6)
                    if self.t_end is not None else None),
            "status": self.status,
        }
        if self.attrs:
            ev["attrs"] = dict(sorted(self.attrs.items()))
        return ev


class Tracer:
    """Span factory over one clock + recorder.

    Span ids are a per-tracer counter (deterministic); parenting is
    explicit — pass ``parent=`` (a :class:`Span`) rather than relying on
    an ambient context stack, because the serving scheduler interleaves
    many requests' spans in one thread and an implicit stack would
    mis-parent them.  The ``span`` context manager covers the common
    fully-nested case."""

    def __init__(self, clock: TimeSource = None,
                 recorder: Optional[FlightRecorder] = None):
        self.now = as_now_fn(clock)
        self.recorder = recorder
        self._next_id = 0
        self.spans_started = 0
        self.spans_ended = 0

    def start(self, name: str, trace_id: str,
              parent: Optional[Span] = None, **attrs: Any) -> Span:
        sid = self._next_id
        self._next_id += 1
        self.spans_started += 1
        if parent is not None and parent.trace_id != trace_id:
            raise ValueError(
                f"span {name!r}: parent belongs to trace "
                f"{parent.trace_id!r}, not {trace_id!r}")
        return Span(self, name, trace_id, sid,
                    parent.span_id if parent is not None else None,
                    self.now(), dict(attrs))

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str,
             parent: Optional[Span] = None, **attrs: Any):
        s = self.start(name, trace_id, parent=parent, **attrs)
        try:
            yield s
        except BaseException as e:
            s.end(status="error", error=f"{type(e).__name__}: {e}")
            raise
        else:
            s.end(status=s.status or "ok")

    def _emit(self, span: Span) -> None:
        self.spans_ended += 1
        if self.recorder is not None:
            self.recorder.record(span.event())


def span_conservation(events: List[Dict[str, Any]],
                      trace_prefix: str = "req-") -> Dict[str, Any]:
    """Structural check over a flight recording: every trace whose id
    starts with ``trace_prefix`` must form ONE rooted tree — exactly one
    parentless root span, every other span's parent present in the same
    trace, and every span ended.  Returns counts the caller reconciles
    against ground truth (e.g. ``ServingRuntime.accounting()``):
    ``roots_by_status`` maps root status → count."""
    traces: Dict[str, List[Dict[str, Any]]] = {}
    for e in events:
        if e.get("kind") != "span":
            continue
        tid = e.get("trace", "")
        if isinstance(tid, str) and tid.startswith(trace_prefix):
            traces.setdefault(tid, []).append(e)
    violations: List[str] = []
    roots_by_status: Dict[str, int] = {}
    total_spans = 0
    for tid, spans in sorted(traces.items()):
        total_spans += len(spans)
        ids = {s["span"] for s in spans}
        roots = [s for s in spans if s["parent"] is None]
        if len(roots) != 1:
            violations.append(f"{tid}: {len(roots)} roots")
            continue
        for s in spans:
            if s["parent"] is not None and s["parent"] not in ids:
                violations.append(
                    f"{tid}: span {s['span']} ({s['name']}) parent "
                    f"{s['parent']} missing from trace")
            if s["t1"] is None:
                violations.append(
                    f"{tid}: span {s['span']} ({s['name']}) never ended")
        st = str(roots[0]["status"])
        roots_by_status[st] = roots_by_status.get(st, 0) + 1
    return {
        "traces": len(traces),
        "spans": total_spans,
        "roots_by_status": dict(sorted(roots_by_status.items())),
        "violations": violations,
        "ok": not violations,
    }
