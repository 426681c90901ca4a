"""Seconds of set-up inside `import.kernel` records alone: a kernel module's
in-branch import that found the module not loaded yet (the first brings
`jax.experimental.pallas`). 0 in a cell whose step holds no kernel module's
import."""

import setup_parts

META = {"name": "setup_kernel_import_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.count(ctx, "kernel_import_s")
