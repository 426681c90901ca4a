"""Device milliseconds a step spends under the `moe_shared` scope in the
Nemotron cell: the ungated shared expert's two products at width 3,712 and
its squared ReLU over all 16,384 tokens, three MoE layers, forward,
recomputation and backward."""

import nemotron_h_flops

META = {"name": "nemotron_moe_shared_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_ms(ctx, ["moe_shared"])
