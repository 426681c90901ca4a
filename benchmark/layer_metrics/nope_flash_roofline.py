"""The global layer's flash pass's share of its roofline in the
SmallThinker cell (no positional encoding, the causal half at 28 / 4 heads
of 128): operations and bytes from `smallthinker_flops.nope_flash_cost`,
over the device seconds a step spends in the kernels `flash_fwd`,
`flash_dq`, `flash_dkv` (by name in `op_seconds`; the window layers'
kernels carry other names)."""

import smallthinker_flops

META = {"name": "nope_flash_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    return smallthinker_flops.kernels_roofline_pct(
        ctx, KERNELS, smallthinker_flops.nope_flash_cost)
