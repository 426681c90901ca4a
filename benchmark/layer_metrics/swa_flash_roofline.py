"""The window layers' flash pass's share of its roofline: operations and
bytes from `smallthinker_flops.swa_flash_cost` (the exact band of 4,096
keys a query, 28 query heads on 4 key-value heads of 128, forward and
backward), over the device seconds a step spends in the kernels
`flash_swa_fwd`, `flash_swa_dq`, `flash_swa_dkv` (by name in `op_seconds`;
the forward runs twice under recomputation). A program without those
kernels gives nothing to read."""

import smallthinker_flops

META = {"name": "swa_flash_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

KERNELS = ("flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv")


def read(ctx):
    return smallthinker_flops.kernels_roofline_pct(
        ctx, KERNELS, smallthinker_flops.swa_flash_cost)
