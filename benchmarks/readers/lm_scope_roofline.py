"""A scope of the decoder LM's decode step against its roofline: the least
time the chip could take for the scope's work in one step — the larger of
operations over peak and bytes over the memory's bandwidth, from shapes
(flops_lm.py, at the rows' context lengths when the traced slice began) —
times the step program's runs, over the device seconds of the operations
that stand under the scope.  The trace names no scope, so the driver
hands over, from the compiled step's text, which operations each
``jax.named_scope`` holds (``window["lm"]["op_scopes"]``,
benchmarks/hlo_scopes.py).  ``params``: ``scopes`` (names of
``jax.named_scope``), ``cost`` (which of flops_lm.COSTS), and ``program``
(whose runs count the steps).  ``None`` where the program names no such
scope or the trace has none of its operations."""

from benchmarks import flops_lm, hlo_scopes


def read(ctx, params):
    red, lm = ctx["trace"], ctx["window"].get("lm")
    if red is None or not lm or not getattr(red, "devices", None):
        return None
    names = [n for scope in params["scopes"]
             for n in lm.get("op_scopes", {}).get(scope, ())]
    seconds, events = hlo_scopes.scope_seconds(red.devices[0].ops, names)
    _, runs = red.program(params["program"])
    if not events or not runs or seconds <= 0:
        return None
    cost = flops_lm.COSTS[params["cost"]](lm["config"], lm["lengths"])
    least = max(cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
