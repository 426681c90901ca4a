"""Median over the window's steps of `solver.prep`: the part of
`train_step` before the jitted call (batch check, key split, wrapping the
arrays), on the program's own clock."""

import statistics

import program_spans

META = {"name": "step_prep_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "solver step", "moves": "train_rate"}


def read(ctx):
    preps = program_spans.last(ctx, "solver.prep")
    if not preps:
        return None
    return statistics.median(p["dur_ms"] for p in preps)
