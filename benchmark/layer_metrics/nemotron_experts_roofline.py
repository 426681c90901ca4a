"""The held two-matrix squared-ReLU experts' grouped products' share of
their roofline in the Nemotron cell: operations and bytes from
`nemotron_h_flops.experts_cost` (the expected pairs on held experts, 768
rows an expert, TWO products a pair at the published width 1,856, forward
and backward), over the device seconds a step spends under the
`moe_experts` scope. The kernels see the width padded to 1,920 in the cast
copies and read low by that."""

import nemotron_h_flops

META = {"name": "nemotron_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_roofline_pct(
        ctx, "moe_experts", nemotron_h_flops.experts_cost)
