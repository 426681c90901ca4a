"""Seconds of set-up in the part `step.backend` of the set-up ledger: the
`compile.backend` intervals under `solver.enqueue` before the window — the
chip's compile, or the persistent cache's load."""

import setup_parts

META = {"name": "setup_step_backend_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "step.backend")
