"""Device milliseconds a step spends under the `dsa_kl` scope in the Keye
cell: the value of the indexer's own loss, which needs every head's
probabilities of a tile once more (its gradient is made inside the core's
backward kernels and counts there)."""

import keye_vl2_flops

META = {"name": "keye_dsa_kl_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["dsa_kl"])
