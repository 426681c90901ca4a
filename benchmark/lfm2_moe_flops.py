"""Operations to train LFM2-MoE on one sequence, the operations and bytes
of its kernels and of its memory-bound mix, and the share of a roofline
they make, from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: the conv mixers' two projections, the attention's
projections and its core by the pairs a query may see (the causal half),
the dense feed-forward, the routers, the SiLU-gated experts at the EXPECTED
number of token-expert pairs on held experts (top_k x held / router outputs
a token: what a balanced router sends here), the tied head over the held
rows of the vocabulary. The conv mixers' taps and gates (8 operations a
channel a token) are left out of the count as the norms are. Recomputation
never counts.
"""

import json
import os

from reference.lfm2_moe import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def causal_pairs(s):
    return s * (s + 1) // 2


def _layers(d):
    """(conv layers, attention layers, dense layers, MoE layers) held."""
    n_conv = sum(1 for kind in d["layer_types"] if kind == "conv")
    return (n_conv, d["num_hidden_layers"] - n_conv, d["num_dense_layers"],
            d["num_hidden_layers"] - d["num_dense_layers"])


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e = d["seq_len"], d["hidden_size"]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    n_conv, n_attn, n_dense, n_moe = _layers(d)
    pairs = d["num_experts_per_tok"] * d["num_experts"] / d["router_outputs"]
    return {
        "conv_proj": n_conv * s * 4 * e * e,
        "attn_proj": n_attn * s * e * (2 * h * dh + 2 * hk * dh),
        "attn_core": n_attn * h * dh * 2 * causal_pairs(s),
        "dense_ff": n_dense * s * 3 * e * d["intermediate_size"],
        "router": n_moe * s * e * d["router_outputs"],
        "routed": n_moe * s * pairs * 3 * e * d["moe_intermediate_size"],
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def flash_h64_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv over the causal half at the
    configuration's head size: 2 products forward, 5 backward (the scores
    once more, dV, dP, dQ, dK); bytes: q, k, v, o and the logsumexp
    forward; q, k, v, o, dO in and dq, dk, dv out backward, bfloat16."""
    d = dims(config)
    s, h, hk, dh = (d["seq_len"], d["num_attention_heads"],
                    d["num_key_value_heads"], d["head_dim"])
    layers = _layers(d)[1]
    ops = layers * batch * h * 7 * 2 * causal_pairs(s) * dh
    qo, kv = s * h * dh * 2, s * hk * dh * 2
    bytes_ = layers * batch * ((2 * qo + 2 * kv + s * h * 4)
                               + (4 * qo + 4 * kv + s * h * 4))
    return ops, bytes_


def experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs: 3 x forward; bytes: the held experts' bfloat16 weights
    read forward and backward, their float32 gradients written, the rows
    in and out in bfloat16."""
    d = dims(config)
    layers, e, f = _layers(d)[3], d["hidden_size"], d["moe_intermediate_size"]
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["num_experts"] * 3 * e * f
    pairs = batch * d["seq_len"] * d["num_experts_per_tok"] \
        * d["num_experts"] / d["router_outputs"]
    return ops, weights * (2 + 2 + 4) + layers * pairs * e * 2 * 4


def shortconv_mix_cost(config, batch):
    """Both gates and the conv of K taps under `shortconv_mix`, all conv
    layers. Operations a channel a token: forward B * u, K multiply-adds,
    C * c (2K + 2); backward dC, dc, the transposed conv, dB, du and the
    taps' gradient (4K + 4). Bytes, bfloat16: forward [B | C | u] read
    and the gated rows written (4 channels' worth); backward [B | C | u]
    and the rows' gradient read, [dB | dC | du] written (7): bound by
    memory by three orders of magnitude."""
    d = dims(config)
    n_conv, k = _layers(d)[0], d["conv_L_cache"]
    cells = n_conv * batch * d["seq_len"] * d["hidden_size"]
    return cells * (6 * k + 6), cells * (4 + 7) * 2


# -- the share of a roofline, for this configuration's readers

def roofline_pct(ctx, cost, window_seconds):
    """The least time the chip could take for a step's `cost(config,
    batch)` = (operations, bytes) — the larger of operations over its peak
    and bytes over its bandwidth — over the device seconds a step spent,
    `window_seconds` being those of all the traced window's steps. None
    where nothing was read. The configuration is this file's own, by
    name."""
    import scope_seconds
    n = scope_seconds.steps(ctx)
    if not n or not window_seconds or window_seconds <= 0:
        return None
    with open(os.path.join(HERE, "configs", "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    ops, bytes_ = cost(config, ctx["batch"])
    peak = ctx["peak"]
    least = max(ops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * least / (window_seconds / n)


def scope_roofline_pct(ctx, scope, cost):
    """By the device seconds under a `jax.named_scope` of the program."""
    import scope_seconds
    got = scope_seconds.seconds(ctx, [scope])
    return roofline_pct(ctx, cost, got[scope]) if got else None


def kernels_roofline_pct(ctx, kernels, cost):
    """By kernel name in `op_seconds` (`flash_fwd.3` is `flash_fwd`)."""
    spent = sum(s for name, s in (ctx.get("op_seconds") or {}).items()
                if name.split(".")[0] in kernels)
    return roofline_pct(ctx, cost, spent)
