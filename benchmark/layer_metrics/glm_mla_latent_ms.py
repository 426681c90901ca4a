"""Device milliseconds a step spends under the `mla_q_latent` and
`mla_kv_latent` scopes in the GLM cell: the four latent products, the two
latent norms and the splits of five layers, forward, recomputation and
backward."""

import glm4_moe_lite_flops

META = {"name": "glm_mla_latent_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scope_ms(ctx, ["mla_q_latent",
                                              "mla_kv_latent"])
