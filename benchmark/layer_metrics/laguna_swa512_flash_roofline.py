"""The window layers' flash passes' share of their roofline in the Laguna
cell (a band of 512 keys a query at 64 query heads on 8 key-value heads of
128, three layers): operations and bytes of the EXACT band from
`laguna_flops.swa_flash_cost` — the algorithm's count: the kernels' blocks
are 512 wide too, so every tile they visit is masked and holds about twice
the band's pairs, which reads here as loss —, over the device seconds a
step spends in the kernels `flash_swa_fwd`, `flash_swa_dq`,
`flash_swa_dkv` (by name in `op_seconds`)."""

import laguna_flops

META = {"name": "laguna_swa512_flash_roofline", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.kernels_roofline_pct(
        ctx, ("flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv"),
        laguna_flops.swa_flash_cost)
