"""Device milliseconds a step spends under the `ssm_proj_in` and
`ssm_proj_out` scopes in the Nemotron cell: W_in (10,304 x 2,688) with its
split and W_out (2,688 x 4,096) of three Mamba-2 mixers, forward,
recomputation and backward."""

import nemotron_h_flops

META = {"name": "nemotron_ssm_proj_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return nemotron_h_flops.scope_ms(ctx, ["ssm_proj_in", "ssm_proj_out"])
