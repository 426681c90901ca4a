"""Device milliseconds a step in a language model's last three layers: the
parts `final_norm` (the norm that heads alone read), `head` (the
`InnerProduct` whose top a loss layer reads) and `loss` (the blocked
float32 log-softmax, with the weighted sum of the loss tops), forward and
backward."""

import step_parts

META = {"name": "lm_head_loss_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PARTS = ("final_norm", "head", "loss")


def read(ctx):
    return step_parts.ms(ctx, PARTS) or None
