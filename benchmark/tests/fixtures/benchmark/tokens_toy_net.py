"""The fixture's net through the program's own DSL, and its FLOP count."""


def net(batch_size, vocab_size, seq_len, d_model, num_heads):
    from sparknet_tpu.models import dsl
    return dsl.NetParam(
        "TokensToy",
        dsl.RDDLayer("data", [batch_size, seq_len]),
        dsl.RDDLayer("label", [batch_size, seq_len]),
        dsl.EmbedLayer("tok_embed", ["data"], vocab_size, d_model),
        dsl.AttentionLayer("attn", ["tok_embed"], num_heads, causal=True),
        dsl.EltwiseLayer("res", ["tok_embed", "attn"]),
        dsl.InnerProductLayer(
            "lm_head", ["res"], vocab_size, axis=2,
            param=[dict(lr_mult=1, decay_mult=1),
                   dict(lr_mult=2, decay_mult=0)]),
        dsl.SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2))


def train_flops(config):
    """FLOPs to train on one sequence: 3 x 2 x the forward pass's
    multiply-accumulates (projections, causal scores and mixing counted
    as the half they are, head)."""
    a = config["builder_args"]
    s, e, v = a["seq_len"], a["d_model"], a["vocab_size"]
    macs = s * e * 3 * e + s * (s + 1) // 2 * e * 2 + s * e * e + s * e * v
    return 3 * 2 * macs
