"""The four latent products' share of their roofline in the GLM cell (W_qa
768 x 2,048 and W_qb 5,120 x 768 with the query latent's norm between
them, W_kva 576 x 2,048, its split and norm, W_kvb 8,960 x 512): operations
and bytes from `glm4_moe_lite_flops.latent_cost` (forward and backward, no
recomputation), over the device seconds a step spends under the
`mla_q_latent` and `mla_kv_latent` scopes, which count the recomputation
and the norms too and read low by them."""

import glm4_moe_lite_flops

META = {"name": "glm_mla_latent_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    return glm4_moe_lite_flops.scopes_roofline_pct(
        ctx, ["mla_q_latent", "mla_kv_latent"],
        glm4_moe_lite_flops.latent_cost)
