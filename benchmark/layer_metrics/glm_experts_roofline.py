"""The held three-matrix SiLU experts' grouped products' share of their
roofline in the GLM cell: operations and bytes from
`glm4_moe_lite_flops.experts_cost` (the expected pairs on held experts, 512
rows an expert, three products a pair at width 1,536, forward and
backward), over the device seconds a step spends under the `moe_experts`
scope, four MoE layers."""

import glm4_moe_lite_flops

META = {"name": "glm_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scopes_roofline_pct(
        ctx, ["moe_experts"], glm4_moe_lite_flops.experts_cost)
