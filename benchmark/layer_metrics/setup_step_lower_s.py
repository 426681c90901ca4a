"""Seconds of set-up in the part `step.lower` of the set-up ledger: the
`compile.lower` intervals under `solver.enqueue` before the window, one a
build of the step."""

import setup_parts

META = {"name": "setup_step_lower_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "step.lower")
