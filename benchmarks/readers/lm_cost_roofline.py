"""A decoder LM's step program, or the scopes of it, against the chip's
peaks: the least time the chip could take for one step's worth of the work
— from shapes, by a cost module under ``benchmarks/`` at the rows' context
lengths when the traced slice began (``window["lm"]["lengths"]``) — times
the program's runs in the slice, over the device seconds read from the
trace.  ``params``: ``module`` (the cost module, e.g. ``flops_lm_mla``),
``cost`` (which of its ``COSTS``), ``program`` (the jitted function's name
in the trace: its runs count the steps), ``scopes`` (optional: names of
``jax.named_scope`` — the seconds are then those of the operations the
compiled step holds under them, ``window["lm"]["op_scopes"]``,
benchmarks/hlo_scopes.py; without it, the program's own), ``against``:
``flops`` (operations over peak bf16), ``bytes`` (bytes over the memory's
bandwidth) or ``max`` (the larger: a roofline).  ``None`` where the trace
has no such program, the program no such scope, the trace none of its
operations, or the window no such shapes."""

import importlib

from benchmarks import hlo_scopes


def read(ctx, params):
    red, lm = ctx["trace"], ctx["window"].get("lm")
    if red is None or not lm or not lm.get("lengths"):
        return None
    seconds, runs = red.program(params["program"])
    if "scopes" in params:
        if not getattr(red, "devices", None):
            return None
        names = [n for scope in params["scopes"]
                 for n in lm.get("op_scopes", {}).get(scope, ())]
        seconds, events = hlo_scopes.scope_seconds(red.devices[0].ops, names)
        if not events:
            return None
    if not runs or seconds <= 0:
        return None
    costs = importlib.import_module(f"benchmarks.{params['module']}").COSTS
    cost = costs[params["cost"]](lm["config"], lm["lengths"])
    by = {"flops": cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
          "bytes": cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]}
    by["max"] = max(by.values())
    return 100.0 * by[params["against"]] * runs / seconds
