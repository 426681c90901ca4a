"""The held SiLU-gated experts' grouped products' share of their roofline
in the LFM2 cell: operations and bytes from `lfm2_moe_flops.experts_cost`
(the expected pairs on held experts, 3,072 rows an expert, forward and
backward), over the device seconds a step spends under the `moe_experts`
scope."""

import lfm2_moe_flops

META = {"name": "lfm2_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return lfm2_moe_flops.scope_roofline_pct(
        ctx, "moe_experts", lfm2_moe_flops.experts_cost)
