"""Device milliseconds a step spends under the `ssm_conv` scope in the
Nemotron cell: the causal depthwise conv of 4 taps over 6,144 channels, its
bias and the silu, three Mamba-2 mixers, forward, recomputation and
backward: shifted multiply-adds, nothing for the MXU."""

import nemotron_h_flops

META = {"name": "nemotron_ssm_conv_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_ms(ctx, ["ssm_conv"])
