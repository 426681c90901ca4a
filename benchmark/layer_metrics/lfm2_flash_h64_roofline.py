"""The flash pass's share of its roofline in the LFM2 cell (one attention
layer, the causal half, 32 query heads on 8 key-value heads of 64: a block
half a lane row wide): operations and bytes from
`lfm2_moe_flops.flash_h64_cost`, over the device seconds a step spends in
the kernels `flash_fwd`, `flash_dq`, `flash_dkv` (by name in
`op_seconds`)."""

import lfm2_moe_flops

META = {"name": "lfm2_flash_h64_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    return lfm2_moe_flops.kernels_roofline_pct(
        ctx, KERNELS, lfm2_moe_flops.flash_h64_cost)
