"""Device milliseconds a step spends on the MoE besides its experts'
products: under `moe_route` (softmax, top-k, sort, the tile plan),
`moe_dispatch` (gathering a tile's rows) and `moe_combine` (weighting and
scattering back), all blocks, forward, recomputation and backward."""

import scope_seconds

META = {"name": "moe_route_dispatch_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

SCOPES = ["moe_route", "moe_dispatch", "moe_combine"]


def read(ctx):
    got, n = scope_seconds.seconds(ctx, SCOPES), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
