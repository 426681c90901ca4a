"""Device milliseconds a step spends under the `ssm_gate_norm` scope in the
Nemotron cell: y * silu(z) and the RMSNorm over each of the 8 groups of
512 channels, in float32, three Mamba-2 mixers, forward, recomputation and
backward."""

import nemotron_h_flops

META = {"name": "nemotron_ssm_gate_norm_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_ms(ctx, ["ssm_gate_norm"])
