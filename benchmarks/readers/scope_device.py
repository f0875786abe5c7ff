"""Device time of a jitted program by named scope.  The trace names an
operation by its HLO line alone, so the PROGRAM hands over which of its
compiled instructions stand under which ``jax.named_scope``:
``analytics_zoo_tpu.obs.device_scopes`` (PR 37) keeps, for every step and
serve program the run built, how to compile it again from shapes, and
``program_scopes(name)`` gives ``{"module", "ops": {instruction: the
op_name it is charged to}, "mixed"}`` — a fusion that holds a convolution
or a dot is charged to that instruction's ``op_name``, anything else to
its own (so a kernel gradient fused with its SGD update is the backward
pass's, not the optimizer's).  This reader asks for the map after the
window and the peak were read and before the driver frees the program,
lays it over the operations INSIDE the module's runs on the first chip
(``fusion.1`` exists in every program: an operation outside the module's
intervals never counts), and writes every map it asked for beside the
trace (``<trace dir>/scopes.json``: ``benchmarks/scope_table.py`` reads a
trace again by hand from that).  What the mapping took is printed on
standard error once a program.

``params``: ``program`` (the module's name in the trace, which the map's
own ``module`` has to equal); ``registered`` (a name of the registry, or
a prefix of several — then the one whose tier, the third part of
``serve/<model>/<tier>/<edge>``, answered most of the window,
``window["tiers_answered"]``); ``scopes`` (regular expression searched in
the charged ``op_name``); ``pass`` (``forward``: no ``transpose(`` in the
``op_name``; ``backward``: one; absent: both); ``as``:

- ``ms_per_run``: device seconds of the matching operations ÷ the
  module's runs × 1e3 (an operation's seconds are its own: where events
  nest, as a loop's body inside the loop, less its children's);
- ``coverage``: percent of the module's operations' device seconds
  charged to ANY name of ``obs.names.SCOPES``;
- ``mfu``: ``flops`` (``ssd_train_step`` or ``ssd_forward``, as
  ``program_mfu``'s) × runs ÷ (the matching seconds × chips × peak bf16),
  percent.

``None`` without a trace, and wherever the registry (a program that has
none reads nothing, never 0), the registered program, the module's runs
or the matching operations are missing."""

from __future__ import annotations

import glob
import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import flops
from benchmarks.trace_reduce import short_name

def registry():
    """The program's ``obs.device_scopes``, or ``None`` in a program that
    has none."""
    try:
        from analytics_zoo_tpu.obs import device_scopes
    except ImportError:
        return None
    return device_scopes


def pick_registered(names: List[str], want: str, window: Dict
                    ) -> Optional[str]:
    """The registered program ``want`` names: itself, or of those it is a
    prefix of the one whose tier answered most of the window."""
    if want in names:
        return want
    hits = [n for n in names if n.startswith(want)]
    answered = window.get("tiers_answered") or {}
    if len(hits) > 1 and answered:
        best = max(answered, key=answered.get)
        hits = [n for n in hits if n.split("/")[2:3] == [best]] or hits
    return hits[0] if len(hits) == 1 else None


def module_ops(red, program: str) -> Tuple[int, float, Dict[str, float]]:
    """(runs, their device seconds, {instruction: own device seconds}) of
    the module ``program`` on the first chip: only operations that begin
    inside one of its runs, each less the operations nested in it."""
    dev = red.devices[0]
    runs = sorted((s, s + d) for n, s, d in dev.modules
                  if n == program or n.startswith(program + "("))
    by_op: Dict[str, float] = {}
    if not runs:
        return 0, 0.0, by_op
    events = sorted(((s, s + d, short_name(n)) for n, s, d in dev.ops),
                    key=lambda e: (e[0], -e[1]))
    own = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    r = 0
    for (s, _, name), seconds in zip(events, own):
        while r < len(runs) and runs[r][1] <= s:
            r += 1
        if r == len(runs):
            break
        if s >= runs[r][0]:
            by_op[name] = by_op.get(name, 0.0) + seconds
    return len(runs), sum(e - s for s, e in runs), by_op


def trace_dir() -> Optional[str]:
    """The directory of the newest trace under the harness's scratch."""
    from benchmarks import harness

    paths = glob.glob(os.path.join(harness.WORK, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(newest))))


def table(ctx: Dict, params: Dict) -> Optional[Dict]:
    """{runs, module_s, by_op, ops, mixed} of the program ``params``
    names in this run's trace; one look a trace and program."""
    red, scopes = ctx["trace"], registry()
    if red is None or scopes is None or not getattr(red, "devices", None):
        return None
    name = pick_registered(scopes.registered(), params["registered"],
                           ctx["window"])
    if name is None:
        return None
    memo = ctx.setdefault("scope_tables", {})     # one look a program
    if name in memo:
        return memo[name]
    t0 = time.monotonic()
    mapped = scopes.program_scopes(name)
    print(f"scope map {name}: {time.monotonic() - t0:.2f} s "
          f"({len(mapped['ops']) if mapped else 0} instructions)",
          file=sys.stderr, flush=True)
    out = None
    if mapped is not None and mapped["module"] == params["program"]:
        runs, module_s, by_op = module_ops(red, params["program"])
        if runs and by_op:
            out = {"runs": runs, "module_s": module_s, "by_op": by_op,
                   "ops": mapped["ops"], "mixed": mapped["mixed"]}
        where = trace_dir()
        if where is not None:
            scopes.dump_program_scopes(os.path.join(where, "scopes.json"))
    memo[name] = out
    return out


def matching_seconds(tab: Dict, matches, which: Optional[str] = None
                     ) -> Tuple[float, int]:
    """(own device seconds, instructions) of the module's operations whose
    charged ``op_name`` ``matches`` (a predicate); ``which``: ``forward``
    / ``backward``."""
    seconds, hits = 0.0, 0
    for name, s in tab["by_op"].items():
        op_name = tab["ops"].get(name, "")
        if not matches(op_name):
            continue
        backward = "transpose(" in op_name
        if which is None or (which == "backward") == backward:
            seconds, hits = seconds + s, hits + 1
    return seconds, hits


def per_run_flops(window: Dict, which: str) -> int:
    if which == "ssd_train_step":
        return flops.ssd_train_step_flops(
            window["resolution"], window["batch"], window["num_classes"])
    if which == "ssd_forward":
        return window["batch"] * flops.ssd_forward_flops(
            window["resolution"], window["num_classes"])
    raise KeyError(f"unknown flops function {which!r}")


def read(ctx, params):
    tab = table(ctx, params)
    if tab is None:
        return None
    if params["as"] == "coverage":
        seconds, _ = matching_seconds(tab, registry().declared_scope)
        total = sum(tab["by_op"].values())
        return 100.0 * seconds / total if total > 0 else None
    seconds, hits = matching_seconds(
        tab, re.compile(params["scopes"]).search, params.get("pass"))
    if not hits or seconds <= 0:
        return None
    if params["as"] == "ms_per_run":
        return 1e3 * seconds / tab["runs"]
    if params["as"] == "mfu":
        return 100.0 * per_run_flops(ctx["window"], params["flops"]) \
            * tab["runs"] / (seconds * len(ctx["trace"].devices)
                             * ctx["peaks"]["bf16_flops_per_s"])
    raise KeyError(f"unknown reading {params['as']!r}")
