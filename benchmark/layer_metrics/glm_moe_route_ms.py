"""Device milliseconds a step spends under the `moe_route` scope in the GLM
cell: the router's float32 product, sigmoid, the bias, top-4 of 64 and the
one sort of the token-expert pairs into the window plan, four MoE layers,
forward, recomputation and backward."""

import glm4_moe_lite_flops

META = {"name": "glm_moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scope_ms(ctx, ["moe_route"])
