"""The held experts' grouped product's share of its roofline: operations
and bytes from `qwen3_next_flops.moe_experts_cost` (the expected pairs on
held experts, forward and backward), over the device seconds a step spends
under the `moe_experts` scope."""

import qwen3_next_flops
import scope_seconds

META = {"name": "moe_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return scope_seconds.scope_roofline_pct(
        ctx, "moe_experts", qwen3_next_flops.moe_experts_cost)
