"""Device milliseconds a step spends under the `rope` scope in the GLM
cell: the rotary on the last 64 dimensions of 20 query heads and on the one
shared key part of 64 a token, five layers, forward, recomputation and
backward."""

import glm4_moe_lite_flops

META = {"name": "glm_rope_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scope_ms(ctx, ["rope"])
