"""Operations to train Laguna-XS.2 on one sequence, the operations and bytes
of its kernels BY THE ALGORITHM, and the share of a roofline they make,
from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: every layer's four projections at ITS head count (48
query heads in a full layer, 64 in a window layer, on 8 key-value heads)
and its per-head gate's product; the core by the pairs a query may see (the
causal half in a full layer, the band W(W+1)/2 + (S-W)W in a window layer:
never the tiles a kernel visits); the dense layer's three products; the
routers; the three-matrix SiLU experts at the EXPECTED number of
token-expert pairs on held experts; the shared expert's three products; the
head over the held rows of the vocabulary. The norms, the rotary, the
gate's sigmoid and multiply and the sums are elementwise and count nothing.
Recomputation never counts.
"""

import json
import os

from reference.laguna import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def visible_pairs(s, window=0):
    """Query-key pairs of one head over a sequence of `s`: the causal half,
    or with a window the band of `window` keys a query, its own among
    them."""
    if not 0 < window < s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def heads_by_kind(d):
    """{kind of layer: [query heads of each layer of that kind held]}."""
    by = {}
    for kind, h in zip(d["layer_types"], d["num_attention_heads_per_layer"]):
        by.setdefault(kind, []).append(h)
    return by


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e, dh = d["seq_len"], d["hidden_size"], d["head_dim"]
    hk = d["num_key_value_heads"]
    by = heads_by_kind(d)
    heads = d["num_attention_heads_per_layer"]
    sparse = sum(1 for m in d["mlp_layer_types"] if m == "sparse")
    dense = d["num_hidden_layers"] - sparse
    pairs = d["num_experts_per_tok"] * d["num_experts"] / d["router_outputs"]
    return {
        "attn_proj": sum(s * e * (2 * h * dh + 2 * hk * dh) for h in heads),
        "attn_gate": sum(s * e * h for h in heads),
        "attn_full": sum(by.get("full_attention", [])) * dh * 2
        * visible_pairs(s),
        "attn_window": sum(by.get("sliding_attention", [])) * dh * 2
        * visible_pairs(s, d["sliding_window"]),
        "dense_ff": dense * s * 3 * e * d["intermediate_size"],
        "router": sparse * s * e * d["router_outputs"],
        "routed": sparse * s * pairs * 3 * e * d["moe_intermediate_size"],
        "shared": sparse * s * 3 * e * d["shared_expert_intermediate_size"],
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


def shares(config):
    """{part: its share of the forward pass's operations}."""
    macs = forward_macs(dims(config))
    total = sum(macs.values())
    return {part: n / total for part, n in macs.items()}


def parameters(config):
    """Parameters of the configuration as sized, from the reference's
    blob shapes."""
    import math
    from reference.laguna import layer_specs
    return sum(math.prod(shape) for _, blobs in layer_specs(dims(config))
               for shape, *_ in blobs)


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def _flash_cost(d, batch, heads, pairs):
    """fwd + dq + dkv over `pairs` query-key pairs a head, for layers of
    `heads` query heads each: 2 products forward, 5 backward (the scores
    once more, dV, dP, dQ, dK); bytes: q, k, v, o and the logsumexp
    forward; q, k, v, o, dO in and dq, dk, dv out backward, bfloat16."""
    s, hk, dh = d["seq_len"], d["num_key_value_heads"], d["head_dim"]
    ops = bytes_ = 0
    for h in heads:
        ops += batch * h * 7 * 2 * pairs * dh
        qo, kv = s * h * dh * 2, s * hk * dh * 2
        bytes_ += batch * ((2 * qo + 2 * kv + s * h * 4)
                           + (4 * qo + 4 * kv + s * h * 4))
    return ops, bytes_


def swa_flash_cost(config, batch):
    """flash_swa_fwd + flash_swa_dq + flash_swa_dkv, the window layers (64
    query heads on 8): the EXACT band of 512 keys a query, whatever tiles
    the kernels visit — a masked tile's hidden half reads as loss."""
    d = dims(config)
    return _flash_cost(d, batch,
                       heads_by_kind(d).get("sliding_attention", []),
                       visible_pairs(d["seq_len"], d["sliding_window"]))


def full_flash_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv, the full layers (48 query heads on
    8): the causal half."""
    d = dims(config)
    return _flash_cost(d, batch, heads_by_kind(d).get("full_attention", []),
                       visible_pairs(d["seq_len"]))


def experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs (512 an expert a step of 16,384 tokens): 3 x forward;
    bytes: the held experts' bfloat16 weights read forward and backward,
    their float32 gradients written, the rows in and out in bfloat16."""
    d = dims(config)
    e, f = d["hidden_size"], d["moe_intermediate_size"]
    layers = sum(1 for m in d["mlp_layer_types"] if m == "sparse")
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["num_experts"] * 3 * e * f
    pairs = batch * d["seq_len"] * d["num_experts_per_tok"] \
        * d["num_experts"] / d["router_outputs"]
    return ops, weights * (2 + 2 + 4) + layers * pairs * e * 2 * 4


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
    with open(os.path.join(HERE, "configs", "laguna_xs_2.json")) as f:
        config = json.load(f)
    ops, bytes_ = cost(config, ctx["batch"])
    peak = ctx["peak"]
    least = max(ops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * least / (window_seconds / n)


def scopes_roofline_pct(ctx, scopes, cost):
    """By the device seconds under the program's `jax.named_scope`s
    together (none of them inside another)."""
    import scope_seconds
    got = scope_seconds.seconds(ctx, scopes)
    return roofline_pct(ctx, cost, sum(got.values())) if got else None


def kernels_roofline_pct(ctx, kernels, cost):
    """By kernel name in `op_seconds` (`flash_fwd.3` is `flash_fwd`)."""
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
