"""A jitted program's share of the chip's bf16 peak: the operations its
runs in the traced slice need (benchmarks/flops.py, from shapes) over
their device seconds times the peak.  ``params``: ``program`` (the jitted
function's name in the trace) and ``flops`` (``ssd_train_step`` or
``ssd_forward``)."""

from benchmarks import flops


def read(ctx, params):
    red = ctx["trace"]
    if red is None:
        return None
    seconds, runs = red.program(params["program"])
    if not runs or seconds <= 0:
        return None
    w = ctx["window"]
    if params["flops"] == "ssd_train_step":
        per_run = flops.ssd_train_step_flops(w["resolution"], w["batch"],
                                             w["num_classes"])
    elif params["flops"] == "ssd_forward":
        per_run = w["batch"] * flops.ssd_forward_flops(w["resolution"],
                                                       w["num_classes"])
    else:
        raise KeyError(f"unknown flops function {params['flops']!r}")
    n_chips = len(red.devices)
    return 100.0 * per_run * runs / (
        seconds * n_chips * ctx["peaks"]["bf16_flops_per_s"])
