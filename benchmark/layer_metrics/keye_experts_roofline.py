"""The held SiLU-gated experts' grouped products' share of their roofline
in the Keye cell: operations and bytes from `keye_vl2_flops.experts_cost`
(the expected pairs on held experts, 2,048 rows an expert, forward and
backward), over the device seconds a step spends under the `moe_experts`
scope."""

import keye_vl2_flops

META = {"name": "keye_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_roofline_pct(
        ctx, "moe_experts", keye_vl2_flops.experts_cost)
