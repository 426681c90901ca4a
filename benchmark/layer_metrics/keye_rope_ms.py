"""Device milliseconds a step spends under the `rope` scope in the Keye
cell: the rotary on the whole head of 32 query and 4 key heads at 32,768
positions, four layers, forward, recomputation and backward (no accepted
reader takes this scope; here it is 2% of the step)."""

import keye_vl2_flops

META = {"name": "keye_rope_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["rope"])
