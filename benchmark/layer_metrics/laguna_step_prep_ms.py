"""Median over the window's steps of `solver.prep` in the Laguna cell: the
part of `train_step` before the jitted call, on the program's own clock
(the accepted readers of the same span list the cells they were accepted
with; this cell brings its own)."""

import statistics

import program_spans

META = {"name": "laguna_step_prep_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "solver step",
        "moves": "train_rate"}


def read(ctx):
    preps = program_spans.last(ctx, "solver.prep")
    if not preps:
        return None
    return statistics.median(p["dur_ms"] for p in preps)
