"""Device milliseconds a step in a language model's dense projections: the
mixers' in and out projections (`attn_proj_in` with the head norms and the
relayouts of q, k, v, `attn_proj_out` with the output gate, `gdn_proj_in`,
`gdn_proj_out`, `shortconv_in`, `shortconv_out`) and every `InnerProduct`
that is no head (part `proj`: a dense feed-forward), forward, recomputation
and backward. Milliseconds and no share of the peak: a fusion answers with
the path of its root (PERF.md sets the FLOPs beside them)."""

import step_parts

META = {"name": "lm_proj_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PARTS = ("attn_proj_in", "attn_proj_out", "gdn_proj_in", "gdn_proj_out",
         "shortconv_in", "shortconv_out", "proj")


def read(ctx):
    return step_parts.ms(ctx, PARTS) or None
