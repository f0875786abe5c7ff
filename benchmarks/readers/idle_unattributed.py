"""Percent of the first chip's idle seconds in the traced slice that lie
under no leaf stage's self time on the host line (thread) that drives
the device — the line that carries the stage ``params["line_of"]``.  The
check that the program's stages cover the gaps: where it reads high a
stage is missing."""

from benchmarks import program_spans


def read(ctx, params):
    lines = program_spans.traced_lines(ctx)
    if not lines:
        return None
    line = program_spans.line_of(lines, params["line_of"])
    gaps = program_spans.device_gaps(ctx["trace"])
    idle = sum(e - s for s, e in gaps)
    if line is None or idle <= 0:
        return None
    under = program_spans.overlap_by_name(program_spans.self_pieces(line),
                                          gaps, leaves_only=True)
    return 100.0 * (1.0 - sum(under.values()) / idle)
