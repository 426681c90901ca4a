"""The attention layer's flash pass's share of its roofline in the Nemotron
cell (no positional encoding, the causal half at 32 query heads on 2
key-value heads of 128, 16 query heads a key-value head): operations and
bytes from `nemotron_h_flops.flash_cost`, over the device seconds a step
spends in the kernels `flash_fwd`, `flash_dq`, `flash_dkv` (by name in
`op_seconds`)."""

import nemotron_h_flops

META = {"name": "nemotron_flash_g16_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.kernels_roofline_pct(
        ctx, ("flash_fwd", "flash_dq", "flash_dkv"),
        nemotron_h_flops.flash_cost)
