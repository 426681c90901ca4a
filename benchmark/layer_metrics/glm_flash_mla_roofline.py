"""The latent attention's flash passes' share of their roofline in the GLM
cell (the causal half at 20 query heads on 20 key-value heads of 256: a key
192 + 64 with its rotary part broadcast in memory, a value 256): operations
and bytes from `glm4_moe_lite_flops.flash_cost`, over the device seconds a
step spends in the kernels `flash_fwd`, `flash_dq`, `flash_dkv` (by name in
`op_seconds`), five layers."""

import glm4_moe_lite_flops

META = {"name": "glm_flash_mla_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.kernels_roofline_pct(
        ctx, ("flash_fwd", "flash_dq", "flash_dkv"),
        glm4_moe_lite_flops.flash_cost)
