"""The gated short convolutions' mix's share of its roofline in the LFM2
cell: both gates and the conv of 3 taps, four conv layers, forward and
backward. Operations and bytes from `lfm2_moe_flops.shortconv_mix_cost`
(bound by memory: [B | C | u] read and the gated rows written, and their
gradients), over the device seconds a step spends under the
`shortconv_mix` scope (the forward runs twice under recomputation). A
program without the scope gives nothing to read."""

import lfm2_moe_flops

META = {"name": "lfm2_shortconv_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return lfm2_moe_flops.scope_roofline_pct(
        ctx, "shortconv_mix", lfm2_moe_flops.shortconv_mix_cost)
