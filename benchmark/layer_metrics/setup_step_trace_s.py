"""Seconds of set-up in the part `step.trace` of the set-up ledger: the
union of the `compile.trace` intervals under `solver.enqueue` before the
window, less `import.kernel`."""

import setup_parts

META = {"name": "setup_step_trace_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "step.trace")
