"""Median time per step that `solver.train_step` took to return: the
host's enqueue (batch check, key split, dispatch), not device time."""

import statistics

META = {"name": "dispatch_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "solver step", "moves": "train_rate"}


def read(ctx):
    if not ctx["dispatch_s"]:
        return None
    return 1e3 * statistics.median(ctx["dispatch_s"])
