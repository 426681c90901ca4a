"""Device milliseconds a step spent computing a block's forward a second
time inside the backward pass: the phase `recompute` of `step_parts`'
ledger (a path under `jax.checkpoint`'s `rematted_computation`), every
part. What `remat` costs in time; the kernels whose outputs are kept by
name (graph/remat.py) are not in it."""

import step_parts

META = {"name": "step_recompute_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PHASES = ("recompute",)


def read(ctx):
    return step_parts.ms(ctx, None, PHASES) or None
