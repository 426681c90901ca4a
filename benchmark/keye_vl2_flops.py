"""Operations to train Keye-VL-2.0's decoder on one sequence, the
operations and bytes of its kernels BY THE ALGORITHM, and the share of a
roofline they make, from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: the attention's four projections, the indexer's three,
the index scores over the pairs a query may see (the causal half: 16 index
heads of 64), the core over the keys a query SELECTS (min(t + 1, topk) of
them: 1,984 on average at 32,768 positions and topk 2,048), the routers,
the SiLU-gated experts at the EXPECTED number of token-expert pairs on held
experts, the head over the held rows of the vocabulary. The selection
itself (a threshold a query) has no multiply-accumulate and counts nothing,
nor do L_I's value, the norms and the rotary. Recomputation never counts,
and neither does what an implementation computes beyond the algorithm: the
program's core runs every tile of the causal half under a mask and
recomputes the index tile in four kernels, and reads low by that much.
"""

import json
import os

from reference.keye_vl2 import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def causal_pairs(s):
    return s * (s + 1) // 2


def selected_pairs(s, topk):
    """sum over the queries of the keys in their set, min(t + 1, topk)."""
    k = min(s, topk)
    return causal_pairs(k) + (s - k) * k


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e, n = d["seq_len"], d["hidden_size"], d["num_hidden_layers"]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    hi, di = d["indexer_num_heads"], d["indexer_head_dim"]
    pairs = d["num_experts_per_tok"] * d["num_experts"] / d["router_outputs"]
    return {
        "attn_proj": n * s * e * (2 * h * dh + 2 * hk * dh),
        "index_proj": n * s * e * (hi * di + di + hi),
        "index_scores": n * hi * di * causal_pairs(s),
        "sparse_core": n * h * dh * 2 * selected_pairs(s, d["indexer_topk"]),
        "router": n * s * e * d["router_outputs"],
        "routed": n * s * pairs * 3 * e * d["moe_intermediate_size"],
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together, by the algorithm

def indexer_cost(config, batch):
    """`dsa_index_select`: the index scores' forward over the causal half,
    once a step (the backward keeps the threshold); bytes: qI, kI in
    bfloat16 and w in float32 in, the threshold and L_I's logsumexp out."""
    d = dims(config)
    s, hi, di = d["seq_len"], d["indexer_num_heads"], d["indexer_head_dim"]
    n = d["num_hidden_layers"]
    ops = 2 * batch * forward_macs(d)["index_scores"]
    return ops, n * batch * s * (hi * di * 2 + di * 2 + hi * 4 + 8)


def sparse_flash_cost(config, batch):
    """`flash_sparse_fwd` + `_dq` + `_dkv` over the SELECTED pairs: 2
    products forward and 5 backward (the scores once more, dV, dP, dQ, dK)
    at the head size, and the index's backward on the set (d qI and d kI,
    2 products of depth 64 an index head); bytes: q, k, v, o and the
    logsumexp forward; q, k, v, o, dO in and dq, dk, dv out backward,
    bfloat16, and the index's operands and gradients."""
    d = dims(config)
    s, h, hk, dh = (d["seq_len"], d["num_attention_heads"],
                    d["num_key_value_heads"], d["head_dim"])
    hi, di, n = (d["indexer_num_heads"], d["indexer_head_dim"],
                 d["num_hidden_layers"])
    sel = selected_pairs(s, d["indexer_topk"])
    ops = n * batch * 2 * sel * (7 * h * dh + 2 * hi * di)
    qo, kv, ix = s * h * dh * 2, s * hk * dh * 2, s * (hi * di + di + hi)
    bytes_ = n * batch * ((2 * qo + 2 * kv + s * h * 4 + ix * 2)
                          + (4 * qo + 4 * kv + s * h * 4 + ix * 2 + ix * 4))
    return ops, bytes_


def experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs: 3 x forward; bytes: the held experts' bfloat16 weights
    read forward and backward, their float32 gradients written, the rows
    in and out in bfloat16."""
    d = dims(config)
    n, e, f = d["num_hidden_layers"], d["hidden_size"], \
        d["moe_intermediate_size"]
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = n * d["num_experts"] * 3 * e * f
    pairs = batch * d["seq_len"] * d["num_experts_per_tok"] \
        * d["num_experts"] / d["router_outputs"]
    return ops, weights * (2 + 2 + 4) + n * pairs * e * 2 * 4


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
    with open(os.path.join(HERE, "configs", "keye_vl2_30b_a3b.json")) as f:
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
    """By kernel name in `op_seconds` (`dsa_kl.3` is `dsa_kl`)."""
    spent = sum(s for name, s in (ctx.get("op_seconds") or {}).items()
                if name.split(".")[0] in kernels)
    return roofline_pct(ctx, cost, spent)


def scope_ms(ctx, scopes):
    """Device milliseconds a step under the scopes together, or None."""
    import scope_seconds
    got, n = scope_seconds.seconds(ctx, scopes), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
