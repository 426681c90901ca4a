"""Device milliseconds a step spends under the `attn_gate` scope in the
Laguna cell: the per-head output gate — W_g's product (one row a head) and
its sigmoid inside `attn_proj_in`, the multiply of every head's output
inside `attn_proj_out` —, five layers, forward, recomputation and
backward."""

import laguna_flops

META = {"name": "laguna_attn_gate_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return laguna_flops.scope_ms(ctx, ["attn_gate"])
