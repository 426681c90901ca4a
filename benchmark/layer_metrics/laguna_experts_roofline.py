"""The held three-matrix SiLU experts' grouped products' share of their
roofline in the Laguna cell: operations and bytes from
`laguna_flops.experts_cost` (the expected pairs on held experts, 512 rows
an expert, three products a pair at width 512, forward and backward), over
the device seconds a step spends under the `moe_experts` scope, four MoE
layers."""

import laguna_flops

META = {"name": "laguna_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scopes_roofline_pct(
        ctx, ["moe_experts"], laguna_flops.experts_cost)
