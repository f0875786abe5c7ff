"""The decoder LM's decode step against the chip's peaks, from the device
seconds of the step program's runs in the traced slice and flops_lm.py's
cost of one step at the rows' context lengths when the slice began
(``window["lm"]["lengths"]``).  ``params``: ``program`` (the jitted
function's name in the trace) and ``against``: ``flops`` — operations over
(seconds x peak bf16), a share of the whole step's peak — or ``bytes`` —
the bytes a step has to move over (seconds x the memory's bandwidth).
``None`` where the trace has no such program or the window no such
shapes."""

from benchmarks import flops_lm


def read(ctx, params):
    red, lm = ctx["trace"], ctx["window"].get("lm")
    if red is None or not lm:
        return None
    seconds, runs = red.program(params["program"])
    if not runs or seconds <= 0:
        return None
    cost = flops_lm.decode_step_cost(lm["config"], lm["lengths"])
    if params["against"] == "flops":
        least = cost["flops"] / ctx["peaks"]["bf16_flops_per_s"]
    elif params["against"] == "bytes":
        least = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    else:
        raise KeyError(f"unknown peak {params['against']!r}")
    return 100.0 * least * runs / seconds
