"""Milliseconds inside one of the program's stages
(``analytics_zoo_tpu.obs.stage``), over the stage ring of the whole
window, so the few traced batches do not set the number.  ``params``:
``span`` (the stage's name); ``stat``: ``median`` of its occurrences, or
``mean`` — the stage's summed time, less the summed time of the stages in
``minus``, over the occurrences of ``per`` (default: of ``span`` itself).
``reach_back`` (for a stage that is there once an epoch): the occurrences
of the loader pools that fed the window (``program_spans.feeding``), so a
window that no epoch begins in still has a reading."""

import statistics

from benchmarks import program_spans


def read(ctx, params):
    records = program_spans.ring(ctx)
    if records is None:
        return None
    seconds = {}
    for r in records:
        seconds.setdefault(r.name, []).append(r.t1 - r.t0)
    if params.get("reach_back"):
        seconds[params["span"]] = [
            r.t1 - r.t0 for r in program_spans.feeding(ctx, params["span"])]
    own = seconds.get(params["span"])
    if not own:
        return None
    if params["stat"] == "median":
        return 1e3 * statistics.median(own)
    if params["stat"] != "mean":
        raise KeyError(f"unknown stat {params['stat']!r}")
    per = len(seconds.get(params.get("per", params["span"]), ()))
    if not per:
        return None
    rest = sum(sum(seconds.get(name, ())) for name in params.get("minus", ()))
    return 1e3 * (sum(own) - rest) / per
