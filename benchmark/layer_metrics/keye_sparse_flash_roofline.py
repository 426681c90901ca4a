"""The sparse core's share of its roofline in the Keye cell: operations and
bytes from `keye_vl2_flops.sparse_flash_cost` (the keys a query SELECTS,
1,984 on average, 32 heads on 4 key-value heads of 128, forward and
backward, with the index's backward on the set), over the device seconds a
step spends in `flash_sparse_fwd`, `flash_sparse_dq`, `flash_sparse_dkv`
(by name in `op_seconds`). The kernels run every tile of the causal half
under the set's mask, 8 times the selected pairs: the share reads low by
that."""

import keye_vl2_flops

META = {"name": "keye_sparse_flash_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.kernels_roofline_pct(
        ctx, ("flash_sparse_fwd", "flash_sparse_dq", "flash_sparse_dkv"),
        keye_vl2_flops.sparse_flash_cost)
