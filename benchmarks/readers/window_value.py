"""A number the driver's window measured itself on the host's clock and
returned under ``end_to_end`` without its being one of the cell's
end-to-end metrics (a tail that swings too widely to carry a bound).
``params``: ``key``."""


def read(ctx, params):
    return ctx["window"].get("end_to_end", {}).get(params["key"])
