"""Device milliseconds a step in what a language model has between its
products: the parts `norm` (the blocks' norms), `residual` (the sums onto
the residual stream), `embed` (the table's gather and, backward, its
scatter-add), `act` (the element-wise layers of a dense feed-forward) and
`moe_glue` (what an MoE layer's window loop costs beside its body: the held
weights cast to the compute type and their gradients cast back, the loop's
zero start), forward, recomputation and backward. Nothing here is for the
MXU. With `lm_proj_ms`, `lm_head_loss_ms`, `lm_scan_carry_ms`,
`step_unscoped_ms`, the mixers' and the MoE's own scopes and `update`, a
language model's step is accounted for whole."""

import step_parts

META = {"name": "lm_glue_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PARTS = ("norm", "residual", "embed", "act", "moe_glue")


def read(ctx):
    return step_parts.ms(ctx, PARTS) or None
