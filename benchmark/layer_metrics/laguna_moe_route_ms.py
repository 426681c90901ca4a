"""Device milliseconds a step spends under the `moe_route` scope in the
Laguna cell: the router's float32 product, sigmoid, top-8 of 256 and the
one sort of the token-expert pairs into the window plan, four MoE layers,
forward, recomputation and backward."""

import laguna_flops

META = {"name": "laguna_moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scope_ms(ctx, ["moe_route"])
