"""A number out of the window's counters.  ``params``: ``path`` (keys
from ``ctx["counters"]`` down), ``field`` (for a histogram's snapshot,
which of its numbers), ``scale`` (multiplied in; default 1) and ``over``
(a second path whose number divides the first).  ``None`` where the
counter is not there."""


def _get(ctx, path, field):
    node = ctx["counters"]
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if isinstance(node, dict):
        node = node.get(field)
    return node


def read(ctx, params):
    field = params.get("field", "mean")
    value = _get(ctx, params["path"], field)
    if value is None:
        return None
    if "over" in params:
        base = _get(ctx, params["over"], params.get("over_field", field))
        if not base:
            return None
        value = value / base
    return float(value) * params.get("scale", 1.0)
