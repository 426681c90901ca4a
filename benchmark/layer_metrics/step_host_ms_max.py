"""The longest `solver.step` record of the window. A unit that stalls for
seconds while this reads milliseconds stalled outside the program's host
path (in the fetch, the harness or the machine), not inside `train_step`."""

import program_spans

META = {"name": "step_host_ms_max", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "solver step", "moves": "train_rate"}


def read(ctx):
    steps = program_spans.last(ctx, "solver.step")
    if not steps:
        return None
    return max(s["dur_ms"] for s in steps)
