"""Device milliseconds a step spends under the `rope` scope in the Laguna
cell: the rotary on all 128 dimensions of 64 query and 8 key heads in the
three window layers (the plain table), and on the first 64 of 48 query and
8 key heads in the two full layers (YaRN's table, cos and sin times the
attention factor, the other 64 dimensions joined on again), forward,
recomputation and backward."""

import laguna_flops

META = {"name": "laguna_rope_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scope_ms(ctx, ["rope"])
