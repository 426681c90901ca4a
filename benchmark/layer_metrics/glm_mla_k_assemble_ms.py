"""Device milliseconds a step spends under the `mla_k_assemble` scope in
the GLM cell: the one rotary key a token broadcast over the 20 heads and
joined to every head's 192 non-rotary dimensions (and, backward, the 20
heads' gradients summed into it), five layers, forward, recomputation and
backward. What a flash kernel that reads the shared key in place would
save."""

import glm4_moe_lite_flops

META = {"name": "glm_mla_k_assemble_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scope_ms(ctx, ["mla_k_assemble"])
