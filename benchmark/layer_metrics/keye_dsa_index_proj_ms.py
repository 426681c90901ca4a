"""Device milliseconds a step spends under the `dsa_index_proj` scope in
the Keye cell: the indexer's three products from the block's norm (16 index
queries of 64, ONE index key, 16 weights a token), the key's LayerNorm and
the rotary on the first half of both, four layers, forward and
recomputation: the indexer's blobs get their gradient from the core's
backward kernels, and the products' own backward counts here."""

import keye_vl2_flops

META = {"name": "keye_dsa_index_proj_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["dsa_index_proj"])
