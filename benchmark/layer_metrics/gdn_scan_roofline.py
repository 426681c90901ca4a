"""The chunked gated delta rule's share of its roofline: the operations and
bytes `qwen3_next_flops.gdn_scan_cost` counts for a step (forward and
backward, no recomputation), the larger of operations / peak FLOP/s and
bytes / peak bytes/s, over the device seconds a step spends under the
`gdn_scan` scope (recomputation included: that is what lowers the share)."""

import qwen3_next_flops
import scope_seconds

META = {"name": "gdn_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return scope_seconds.scope_roofline_pct(ctx, "gdn_scan",
                                            qwen3_next_flops.gdn_scan_cost)
