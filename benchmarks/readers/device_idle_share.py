"""Percent of the traced slice in which no operation ran on the device."""


def read(ctx, params):
    red = ctx["trace"]
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
