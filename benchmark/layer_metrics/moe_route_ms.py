"""Device milliseconds a step spends under the `moe_route` scope alone:
the router's float32 product, softmax, top-k and the one sort of the
token-expert pairs into the window plan, all blocks, forward,
recomputation and backward. What `moe_route_dispatch_ms` reads less the
windows' gathers (`moe_dispatch`) and scatter-adds (`moe_combine`)."""

import scope_seconds

META = {"name": "moe_route_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

SCOPES = ["moe_route"]


def read(ctx):
    got, n = scope_seconds.seconds(ctx, SCOPES), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
