"""Device milliseconds a step spends under the `moe_dispatch` and
`moe_combine` scopes in the Laguna cell: a window's rows gathered, and
brought back to their tokens through the token-major sort, two gathers and
the segment add; four MoE layers, forward, recomputation and backward."""

import laguna_flops

META = {"name": "laguna_moe_dispatch_combine_ms", "unit": "ms",
        "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scope_ms(ctx, ["moe_dispatch", "moe_combine"])
