"""The serving runtime's own ``serve/batch_fill`` counter: mean share of
``max_batch`` that a dispatched batch held, in percent."""


def read(ctx, params):
    fill = ctx["counters"].get("mean_batch_fill")
    return None if fill is None else 100.0 * fill
