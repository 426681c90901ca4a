"""Operations to train SmallThinker on one sequence, the operations and
bytes of its kernels, and the share of a roofline they make, from shapes
alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: projections, attention by the pairs a query may see
(the causal half in a global layer, the band W(W+1)/2 + (S-W)W in a window
layer: never the blocks a kernel visits), router, the ReLU-gated experts at
the EXPECTED number of token-expert pairs on held experts (top_k x held /
router outputs a token: what a balanced router sends here), head over the
held rows of the vocabulary. Recomputation never counts.
"""

import json
import os

from reference.smallthinker import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def visible_pairs(s, window=0):
    """Query-key pairs of one head over a sequence of `s`: the causal half,
    or with a window the band of `window` keys a query, its own among
    them."""
    if not 0 < window < s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _layers(d):
    """(window layers, global layers) held."""
    n_win = sum(1 for w in d["sliding_window_layout"] if w)
    return n_win, d["num_hidden_layers"] - n_win


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e, layers = d["seq_len"], d["hidden_size"], d["num_hidden_layers"]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    n_win, n_glob = _layers(d)
    pairs = d["moe_num_active_primary_experts"] \
        * d["moe_num_primary_experts"] / d["router_outputs"]
    return {
        "attn_proj": layers * s * e * (2 * h * dh + 2 * hk * dh),
        "attn_window": n_win * h * dh * 2
        * visible_pairs(s, d["sliding_window_size"]),
        "attn_global": n_glob * h * dh * 2 * visible_pairs(s),
        "router": layers * s * e * d["router_outputs"],
        "routed": layers * s * pairs * 3 * e * d["moe_ffn_hidden_size"],
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def _flash_cost(d, batch, layers, pairs):
    """fwd + dq + dkv over `pairs` query-key pairs a head: 2 products
    forward, 5 backward (the scores once more, dV, dP, dQ, dK); bytes: q, k,
    v, o and the logsumexp forward; q, k, v, o, dO in and dq, dk, dv out
    backward, bfloat16."""
    s, h, hk, dh = (d["seq_len"], d["num_attention_heads"],
                    d["num_key_value_heads"], d["head_dim"])
    ops = layers * batch * h * 7 * 2 * pairs * dh
    qo, kv = s * h * dh * 2, s * hk * dh * 2
    bytes_ = layers * batch * ((2 * qo + 2 * kv + s * h * 4)
                               + (4 * qo + 4 * kv + s * h * 4))
    return ops, bytes_


def swa_flash_cost(config, batch):
    """flash_swa_fwd + flash_swa_dq + flash_swa_dkv, the window layers:
    the exact band, whatever blocks the kernels visit."""
    d = dims(config)
    return _flash_cost(d, batch, _layers(d)[0],
                       visible_pairs(d["seq_len"], d["sliding_window_size"]))


def nope_flash_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv, the global layers: the causal
    half."""
    d = dims(config)
    return _flash_cost(d, batch, _layers(d)[1], visible_pairs(d["seq_len"]))


def reglu_experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs: 3 x forward; bytes: the held experts' bfloat16 weights
    read forward and backward, their float32 gradients written, the rows
    in and out in bfloat16."""
    d = dims(config)
    layers, e, f = (d["num_hidden_layers"], d["hidden_size"],
                    d["moe_ffn_hidden_size"])
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["moe_num_primary_experts"] * 3 * e * f
    pairs = batch * d["seq_len"] * d["moe_num_active_primary_experts"] \
        * d["moe_num_primary_experts"] / d["router_outputs"]
    return ops, weights * (2 + 2 + 4) + layers * pairs * e * 2 * 4


# -- the share of a roofline, for this configuration's readers

def roofline_pct(ctx, cost, window_seconds):
    """The least time the chip could take for a step's `cost(config,
    batch)` = (operations, bytes) — the larger of operations over its peak
    and bytes over its bandwidth — over the device seconds a step spent,
    `window_seconds` being those of all the traced window's steps. None
    where nothing was read. The configuration is this file's own, by name:
    `scope_seconds.roofline_pct` prices another's shapes."""
    import scope_seconds
    n = scope_seconds.steps(ctx)
    if not n or not window_seconds or window_seconds <= 0:
        return None
    with open(os.path.join(HERE, "configs", "smallthinker_21b_a3b.json")) as f:
        config = json.load(f)
    ops, bytes_ = cost(config, ctx["batch"])
    peak = ctx["peak"]
    least = max(ops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * least / (window_seconds / n)


def kernels_roofline_pct(ctx, kernels, cost):
    """By kernel name in `op_seconds` (`flash_fwd.3` is `flash_fwd`)."""
    spent = sum(s for name, s in (ctx.get("op_seconds") or {}).items()
                if name.split(".")[0] in kernels)
    return roofline_pct(ctx, cost, spent)
