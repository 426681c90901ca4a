"""Device milliseconds a step spends under the `moe_shared` scope in the
Laguna cell: the ungated shared expert's three products at width 512 and
its SiLU gate over all 16,384 tokens, four MoE layers, forward,
recomputation and backward."""

import laguna_flops

META = {"name": "laguna_moe_shared_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scope_ms(ctx, ["moe_shared"])
