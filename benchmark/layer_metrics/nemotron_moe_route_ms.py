"""Device milliseconds a step spends under the `moe_route` scope in the
Nemotron cell: the router's float32 product, sigmoid, the bias, top-6 of
128 and the one sort of the token-expert pairs into the window plan, three
MoE layers, forward, recomputation and backward."""

import nemotron_h_flops

META = {"name": "nemotron_moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_ms(ctx, ["moe_route"])
