"""The index-score kernel's share of its roofline in the Keye cell: the
index scores' forward over the causal half (16 index heads of 64, four
layers) from `keye_vl2_flops.indexer_cost`, over the device seconds a step
spends in `dsa_index_select` (by name in `op_seconds`), which also finds
every query's threshold by 32 counting passes: a selection has no
multiply-accumulate, so the share reads what the selection leaves."""

import keye_vl2_flops

META = {"name": "keye_indexer_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.kernels_roofline_pct(
        ctx, ("dsa_index_select",), keye_vl2_flops.indexer_cost)
