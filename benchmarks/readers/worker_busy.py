"""Percent of the forked loader workers' lives spent in the per-sample
chain (decode + augment of their own samples): summed ``chain_s`` over
summed lifetimes of the ``az/input/worker`` records — which the program
writes, one a worker, when an epoch's pool closes — of the pools that fed
the window (``program_spans.feeding``): a short window (a traced run's)
lies inside one epoch, whose workers were born before it."""

from benchmarks import program_spans


def read(ctx, params):
    workers = program_spans.feeding(ctx, program_spans.WORKER)
    alive = sum(r.t1 - r.t0 for r in workers)
    if alive <= 0:
        return None
    return 100.0 * sum(r.attrs["chain_s"] for r in workers) / alive
