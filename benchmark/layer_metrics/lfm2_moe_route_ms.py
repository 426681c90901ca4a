"""Device milliseconds a step spends under the `moe_route` scope in the
LFM2 cell: the router's float32 product, sigmoid, the selection bias,
top-4 of 32 and the one sort of the token-expert pairs into the window
plan, four MoE layers, forward, recomputation and backward."""

import scope_seconds

META = {"name": "lfm2_moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    got, n = (scope_seconds.seconds(ctx, ["moe_route"]),
              scope_seconds.steps(ctx))
    if not got or not n or got["moe_route"] <= 0:
        return None
    return got["moe_route"] / n * 1e3
