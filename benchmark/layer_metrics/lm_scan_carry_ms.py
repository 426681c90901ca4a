"""Device milliseconds a step that a scan over blocks costs beside its
body: the part `scan_carry` of `step_parts`' ledger — operations under the
scan's own scope (`layer_scan.<block>`) and no layer: the groups'
parameters stacked before the loop and their gradients unstacked after it,
the `while` itself, its carry, and the buffers jax stacks for the backward
pass with what remat keeps in them (graph/remat.py)."""

import step_parts

META = {"name": "lm_scan_carry_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PARTS = ("scan_carry",)


def read(ctx):
    return step_parts.ms(ctx, PARTS) or None
