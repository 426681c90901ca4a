"""The Mamba-2 chunked scan's share of its roofline in the Nemotron cell:
operations and bytes from `nemotron_h_flops.ssd_cost` (the chunked
algorithm's causal count at 64 heads of 64, state 128, chunks of 128,
forward and backward, no recomputation; the chunks' states count no byte),
over the device seconds a step spends under the `ssm_scan` scope, whatever
implements the scan there (XLA's chunked form today; recomputation
included: that is what lowers the share)."""

import nemotron_h_flops

META = {"name": "nemotron_ssd_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_roofline_pct(
        ctx, "ssm_scan", nemotron_h_flops.ssd_cost)
