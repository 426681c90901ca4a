"""Operations to train Qwen3-Next on one sequence, and the operations and
bytes of its three new kernels, from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: projections, the depthwise conv, the chunked delta
rule as the program computes it, attention by its causal half, router,
shared expert, routed experts at the EXPECTED number of token-expert pairs
on held experts (top_k x held / router outputs a token: what a balanced
router sends here), head over the held rows of the vocabulary.
Recomputation never counts.
"""

from reference.qwen3_next import dims, is_attention

CHUNK = 64


def _delta_rule_macs(d, chunk=CHUNK):
    """Per token and value head: K K^T, the triangular inverse by
    log2(chunk) - 1 squarings and as many products, T (beta V), T (beta
    exp(G) K), Q K^T inside the chunk; W S, (exp(G) Q) S, Att U and
    K^T U in the scan over chunks."""
    dk, dv = d["linear_key_head_dim"], d["linear_value_head_dim"]
    doublings = 2 * (chunk.bit_length() - 2)
    per_chunk = (chunk * chunk * dk                 # K K^T
                 + doublings * chunk ** 3           # (I + A)^-1
                 + chunk * chunk * (dv + dk)        # T (beta V), T (.. K)
                 + chunk * chunk * dk               # Q K^T
                 + 2 * chunk * dk * dv              # W S, (exp(G) Q) S
                 + chunk * chunk * dv               # Att U
                 + chunk * dk * dv)                 # K^T U
    return per_chunk / chunk


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e = d["seq_len"], d["hidden_size"]
    layers = range(d["num_hidden_layers"])
    n_attn = sum(is_attention(i, d) for i in layers)
    n_gdn = d["num_hidden_layers"] - n_attn
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    lk, lv = d["linear_num_key_heads"], d["linear_num_value_heads"]
    kd, vd = lk * d["linear_key_head_dim"], lv * d["linear_value_head_dim"]
    f, fs = d["moe_intermediate_size"], d["shared_expert_intermediate_size"]
    pairs = d["num_experts_per_tok"] * d["num_experts"] / d["router_outputs"]
    return {
        "gdn_proj": n_gdn * s * e * (2 * kd + 2 * vd + 2 * lv + vd),
        "gdn_conv": n_gdn * s * (2 * kd + vd) * d["linear_conv_kernel_dim"],
        "gdn_scan": n_gdn * s * lv * _delta_rule_macs(d),
        "attn_proj": n_attn * s * e * (2 * h * dh + 2 * hk * dh + h * dh),
        "attn_core": n_attn * h * dh * 2 * s * (s + 1) // 2,
        "router": len(layers) * s * e * d["router_outputs"],
        "shared": len(layers) * s * (3 * e * fs + e),
        "routed": len(layers) * s * pairs * 3 * e * f,
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def gdn_scan_cost(config, batch):
    """The chunked delta rule under the `gdn_scan` scope: 3 x its forward
    operations; bytes: float32 q, k, v, beta, g in and o out per value
    head forward, the same again with the cotangents backward."""
    d = dims(config)
    n_gdn = sum(not is_attention(i, d) for i in range(d["num_hidden_layers"]))
    ops = 3 * 2 * batch * forward_macs(d)["gdn_scan"]
    per_token = d["linear_num_value_heads"] * (
        2 * d["linear_key_head_dim"] + 2 * d["linear_value_head_dim"] + 2)
    return ops, 3 * 4 * batch * d["seq_len"] * n_gdn * per_token


def moe_experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs: 3 x forward; bytes: the held experts' bfloat16 weights
    read forward and backward, their float32 gradients written, the rows
    in and out in bfloat16."""
    d = dims(config)
    layers, e, f = (d["num_hidden_layers"], d["hidden_size"],
                    d["moe_intermediate_size"])
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["num_experts"] * 3 * e * f
    pairs = batch * d["seq_len"] * d["num_experts_per_tok"] \
        * d["num_experts"] / d["router_outputs"]
    return ops, weights * (2 + 2 + 4) + layers * pairs * e * 2 * 4


def flash_gqa_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv: 2 products of the causal half
    forward, 5 backward (the scores once more, dV, dP, dQ, dK);
    bytes: q, k, v, o and the logsumexp forward; q, k, v, o, dO in and
    dq, dk, dv out backward, bfloat16."""
    d = dims(config)
    n_attn = sum(is_attention(i, d) for i in range(d["num_hidden_layers"]))
    s, h, hk, dh = (d["seq_len"], d["num_attention_heads"],
                    d["num_key_value_heads"], d["head_dim"])
    half = s * (s + 1) // 2
    ops = n_attn * batch * h * 7 * 2 * half * dh
    qo, kv = s * h * dh * 2, s * hk * dh * 2
    bytes_ = n_attn * batch * ((2 * qo + 2 * kv + s * h * 4)
                               + (4 * qo + 4 * kv + s * h * 4))
    return ops, bytes_
