"""The full-attention layers' flash passes' share of their roofline in the
Laguna cell (the causal half at 48 query heads on 8 key-value heads of 128,
two layers): operations and bytes from `laguna_flops.full_flash_cost`, over
the device seconds a step spends in the kernels `flash_fwd`, `flash_dq`,
`flash_dkv` (by name in `op_seconds`)."""

import laguna_flops

META = {"name": "laguna_full_flash_roofline", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.kernels_roofline_pct(
        ctx, ("flash_fwd", "flash_dq", "flash_dkv"),
        laguna_flops.full_flash_cost)
