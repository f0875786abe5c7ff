"""A kernel's share of its roofline: the least time the chip could take
for its calls — the larger of operations over peak and bytes over the
memory's bandwidth, from shapes (benchmarks/flops.py) — over the device
seconds of the kernel's events in the traced slice.  ``params``:
``pattern`` (regular expression on the operation's name in the trace) and
``cost`` (which of flops.py's functions prices one call)."""

from benchmarks import flops


def read(ctx, params):
    red = ctx["trace"]
    if red is None:
        return None
    seconds, calls = red.pattern_seconds(params["pattern"])
    if not calls or seconds <= 0:
        return None
    w = ctx["window"]
    if params["cost"] == "detection_output":
        cost = flops.detection_output_cost(w["batch"], w["resolution"],
                                           w["num_classes"])
    else:
        raise KeyError(f"unknown cost function {params['cost']!r}")
    least = max(cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds
