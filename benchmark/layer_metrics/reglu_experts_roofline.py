"""The held ReLU-gated experts' grouped products' share of their roofline:
operations and bytes from `smallthinker_flops.reglu_experts_cost` (the
expected pairs on held experts, forward and backward), over the device
seconds a step spends under the `moe_experts` scope."""

import scope_seconds
import smallthinker_flops

META = {"name": "reglu_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    got = scope_seconds.seconds(ctx, ["moe_experts"])
    if not got:
        return None
    return smallthinker_flops.roofline_pct(
        ctx, smallthinker_flops.reglu_experts_cost, got["moe_experts"])
