"""Device milliseconds a step spends under the `moe_route` scope in the
Keye cell: the router's float32 product, softmax, top-8 of 128 and the one
sort of the token-expert pairs into the window plan, four MoE layers,
forward, recomputation and backward."""

import keye_vl2_flops

META = {"name": "keye_moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["moe_route"])
