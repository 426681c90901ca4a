"""Device milliseconds a step spends under the `dsa_select` scope in the
Keye cell: the index scores by tiles and every query's threshold (the
2,048th largest of up to 32,768, by counting), four layers, forward only:
the backward keeps the threshold."""

import keye_vl2_flops

META = {"name": "keye_dsa_select_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return keye_vl2_flops.scope_ms(ctx, ["dsa_select"])
