"""Device milliseconds a step spends in the Keye cell's MoE moving rows:
under `moe_dispatch` (gathering a window's rows) and `moe_combine`
(weighting a window's rows and gathering them back onto their tokens),
four MoE layers, forward, recomputation and backward."""

import keye_vl2_flops

META = {"name": "keye_moe_dispatch_combine_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["moe_dispatch", "moe_combine"])
