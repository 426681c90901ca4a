"""Arithmetic of the timed window, kept apart so that tests can reach it."""

import math


def percentile(values, q):
    """q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default method), in plain Python."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_metrics(unit_starts, unit_ends, sync_every, batch):
    """End-to-end numbers of one window of complete sync units.

    train_rate: samples of all complete units over the time from the first
    unit's start to the last unit's fetch. step_ms_p95: 95th percentile
    over units of unit time / sync_every."""
    if not unit_ends or len(unit_starts) != len(unit_ends):
        raise ValueError("a window needs complete units")
    span = unit_ends[-1] - unit_starts[0]
    per_step_ms = [(e - s) * 1e3 / sync_every
                   for s, e in zip(unit_starts, unit_ends)]
    return {
        "train_rate": len(unit_ends) * sync_every * batch / span,
        "step_ms_p95": percentile(per_step_ms, 95),
        "step_ms_median": percentile(per_step_ms, 50),
        "units": len(unit_ends),
        "span_s": span,
    }
