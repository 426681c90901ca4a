"""Device milliseconds a step spends in the LFM2 cell's MoE moving rows:
under `moe_dispatch` (gathering a window's rows) and `moe_combine`
(weighting a window's float32 rows of 2,048 and scattering them back onto
their tokens), four MoE layers, forward, recomputation and backward."""

import scope_seconds

META = {"name": "lfm2_moe_dispatch_combine_ms", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "layer": "ops kernels", "moves": "train_rate"}

SCOPES = ["moe_dispatch", "moe_combine"]


def read(ctx):
    got, n = scope_seconds.seconds(ctx, SCOPES), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
