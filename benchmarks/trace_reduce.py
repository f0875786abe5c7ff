"""``.xplane.pb`` → numbers.  Reads the profiler's file with nothing but
JAX (``jax.profiler.ProfileData``): which intervals an operation ran on
the device, the device time of a named program, the device time of the
operations matching a pattern, the operations that took most time, and
the longest idle gaps by what the host was doing in them.

What a TPU v5e trace holds (looked at by hand, PR 25): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event for each
operation that ran (sequential on the core; its name is the operation's
whole HLO line, ``%fusion.617 = (bf16[64]...) fusion(...), kind=kOutput,
calls=...``, so the reduction keeps the part before `` = `` as the short
name and matches patterns against the whole line) and whose line ``XLA
Modules`` has one event for each run of a jitted program
(``jit_step_fn(<fingerprint>)``); the host's threads are lines of the
plane ``/host:CPU``, where a ``jax.profiler.TraceAnnotation`` shows under
its own name.  All planes share one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Event = Tuple[str, float, float]          # name, start s, duration s


def short_name(hlo_line: str) -> str:
    """``%fusion.617 = (bf16[64]...) fusion(...)`` → ``fusion.617``."""
    return hlo_line.split(" = ", 1)[0].lstrip("%")


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]]
         ) -> List[Tuple[float, float]]:
    """The (start, end) stretches between the first and the last interval
    that none covers."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Reduction:
    """One trace, reduced.  Seconds throughout."""

    devices: List[DevicePlane]
    host: List[Event]                      # the benchmark's annotations

    @property
    def window(self) -> Tuple[float, float]:
        starts = [e[1] for d in self.devices for e in d.ops]
        ends = [e[1] + e[2] for d in self.devices for e in d.ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return hi - lo

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_seconds([(s, s + d) for _, s, d in p.ops])
                   for p in self.devices) / len(self.devices)

    def program(self, name: str) -> Tuple[float, int]:
        """(device seconds, runs) of the jitted program ``name`` — the
        module events called ``name`` or ``name(<fingerprint>)`` — on the
        first chip (every chip of a mesh runs the same program)."""
        if not self.devices:
            return 0.0, 0
        hits = [d for n, _, d in self.devices[0].modules
                if n == name or n.startswith(name + "(")]
        return sum(hits), len(hits)

    def pattern_seconds(self, pattern: str) -> Tuple[float, int]:
        """(device seconds, events) of the operations whose name matches
        the regular expression, on the first chip."""
        if not self.devices:
            return 0.0, 0
        rx = re.compile(pattern)
        hits = [d for n, _, d in self.devices[0].ops if rx.search(n)]
        return sum(hits), len(hits)

    def matching_ops(self, pattern: str, n: int = 10) -> List[List]:
        """[whole HLO line, seconds] of the operations matching
        ``pattern``, most time first: how a kernel's pattern is found."""
        rx = re.compile(pattern)
        total: Dict[str, float] = {}
        for name, _, dur in self.devices[0].ops if self.devices else []:
            if rx.search(name):
                total[name] = total.get(name, 0.0) + dur
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time, first chip; the
        numbered instances of one HLO name (``fusion.12``) stay apart."""
        if not self.devices:
            return []
        total: Dict[str, float] = {}
        for name, _, dur in self.devices[0].ops:
            name = short_name(name)
            total[name] = total.get(name, 0.0) + dur
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds of the first chip by what the host was doing:
        every gap between operations is charged to the benchmark's
        annotation that overlaps it most (``(none)`` where no annotation
        does), summed by name, longest first."""
        if not self.devices:
            return []
        total: Dict[str, float] = {}
        for gs, ge in gaps([(s, s + d) for _, s, d in self.devices[0].ops]):
            best, best_ov = "(none)", 0.0
            for name, s, d in self.host:
                ov = min(ge, s + d) - max(gs, s)
                if ov > best_ov:
                    best, best_ov = name, ov
            total[best] = total.get(best, 0.0) + (ge - gs)
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_planes(planes, host_prefix: str = "bench/") -> Reduction:
    """``planes``: objects with ``.name`` and ``.lines``; a line has
    ``.name`` and ``.events``; an event ``.name``, ``.start_ns`` and
    ``.duration_ns`` (``ProfileData``'s shape; the tests build the same
    from tuples)."""
    devices, host = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = ops if line.name == OPS_LINE else modules
                into.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events)
            devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(host_prefix))
    devices.sort(key=lambda d: d.name)
    return Reduction(devices, host)


def reduce_file(path: str, host_prefix: str = "bench/") -> Reduction:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)       # alive while its planes are read
    return reduce_planes(data.planes, host_prefix)


# -- a cut of a trace, small enough to commit as a test's fixture -----------


def cut_file(path: str, out: str, seconds: float,
             host_prefix: str = "bench/") -> None:
    """Write the first ``seconds`` of the device's activity in ``path`` —
    only the planes, lines and events the reduction reads, verbatim — as
    gzipped JSON that :func:`load_cut` turns back into planes."""
    import gzip
    import json

    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)       # alive while its planes are read
    planes = list(data.planes)
    t0 = min(e.start_ns for p in planes if DEVICE_PLANE.match(p.name)
             for line in p.lines if line.name == OPS_LINE
             for e in line.events)
    t1 = t0 + int(seconds * 1e9)
    doc = []
    for p in planes:
        dev = bool(DEVICE_PLANE.match(p.name))
        if not dev and p.name != HOST_PLANE:
            continue
        lines = []
        for line in p.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, e.start_ns, e.duration_ns]
                      for e in line.events
                      if t0 <= e.start_ns and e.start_ns + e.duration_ns <= t1
                      and (dev or e.name.startswith(host_prefix))]
            if events:
                lines.append({"name": line.name, "events": events})
        doc.append({"name": p.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)


def load_cut(path: str):
    """The planes of a cut written by :func:`cut_file`, shaped as
    :func:`reduce_planes` takes them."""
    import collections
    import gzip
    import json

    E = collections.namedtuple("E", "name start_ns duration_ns")
    L = collections.namedtuple("L", "name events")
    P = collections.namedtuple("P", "name lines")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return [P(p["name"], [L(line["name"], [E(*e) for e in line["events"]])
                          for line in p["lines"]]) for p in doc]
