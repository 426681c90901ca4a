"""Median over the window's steps of `solver.prep` in the SmallThinker
cell: the part of `train_step` before the jitted call, on the program's own
clock (`step_prep_ms` and `lm_step_prep_ms` list the cells they were
accepted with; this cell brings its own reader)."""

import statistics

import program_spans

META = {"name": "st_step_prep_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "solver step", "moves": "train_rate"}


def read(ctx):
    preps = program_spans.last(ctx, "solver.prep")
    if not preps:
        return None
    return statistics.median(p["dur_ms"] for p in preps)
