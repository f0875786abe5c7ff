"""Mean milliseconds a batch inside the loader's ``next`` (the train
driver's own span around it), over the window."""


def read(ctx, params):
    waits = ctx["window"].get("loader_waits_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
