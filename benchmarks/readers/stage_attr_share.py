"""Percent of one of the program's stages' occurrences in the window that
carry a true attribute (``analytics_zoo_tpu.obs.stage(name, **attrs)``),
over the stage ring of the whole window.  ``params``: ``span`` (the
stage's name), ``attr`` (the attribute's).  ``None`` where the window has
no such stage or none of them carries the attribute (a program from before
the attribute: the metric is left out, not read as 0)."""

from benchmarks import program_spans


def read(ctx, params):
    flags = [r.attrs[params["attr"]]
             for r in program_spans.ring(ctx) or ()
             if r.name == params["span"] and params["attr"] in r.attrs]
    if not flags:
        return None
    return 100.0 * sum(map(bool, flags)) / len(flags)
