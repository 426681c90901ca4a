"""The flash pass's share of its roofline at head size 256 with shared
key-value heads: operations and bytes from
`qwen3_next_flops.flash_gqa_cost`, over the device seconds a step spends in
the kernels `flash_fwd`, `flash_dq`, `flash_dkv` (by name in
`op_seconds`; the forward runs twice under recomputation)."""

import qwen3_next_flops
import scope_seconds

META = {"name": "flash_gqa_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    spent = sum(s for name, s in (ctx.get("op_seconds") or {}).items()
                if name.split(".")[0] in KERNELS)
    return scope_seconds.roofline_pct(ctx, qwen3_next_flops.flash_gqa_cost,
                                      spent)
