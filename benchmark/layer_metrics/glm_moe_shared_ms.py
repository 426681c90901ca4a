"""Device milliseconds a step spends under the `moe_shared` scope in the
GLM cell: the ungated shared expert's three products at width 1,536 and
its SiLU gate over all 8,192 tokens, four MoE layers, forward,
recomputation and backward."""

import glm4_moe_lite_flops

META = {"name": "glm_moe_shared_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scope_ms(ctx, ["moe_shared"])
