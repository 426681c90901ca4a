"""Device milliseconds a step spends in the SmallThinker cell's MoE moving
rows: under `moe_dispatch` (gathering a window's rows of the post-attention
norm) and `moe_combine` (weighting a window's float32 rows and scattering
them back onto their tokens), all blocks, forward, recomputation and
backward. The scatter-add is the larger part by far (PERF.md, PR 33)."""

import scope_seconds

META = {"name": "st_moe_dispatch_combine_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

SCOPES = ["moe_dispatch", "moe_combine"]


def read(ctx):
    got, n = scope_seconds.seconds(ctx, SCOPES), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
