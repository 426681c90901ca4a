"""Operations to train the Nemotron-H tower on one sequence, the operations
and bytes of its kernels BY THE ALGORITHM, and the share of a roofline they
make, from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: the Mamba-2 mixers' two projections; their scans by
the chunked algorithm's causal count (a chunk of Q tokens: the lower
triangle of C B^T a group and of its product with delta x a head, Q (Q + 1)
/ 2 pairs each; the chunk's own state and the carried state's readout, Q x
P x N a head each); the attention's four projections and its core over the
causal half; the routers; the two-matrix squared-ReLU experts at the
EXPECTED number of token-expert pairs on held experts (TWO products a pair
at the published width 1,856, whatever a tile pads); the shared expert's
two products; the head over the held rows of the vocabulary. The conv (4
taps a channel), the decays, the gate, the norms and the D skip are
elementwise and count nothing. Recomputation never counts, and neither does
what an implementation computes beyond the algorithm: a full square where
the causal half would do, a padded tile.
"""

import json
import os

from reference.nemotron_h import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def causal_pairs(s):
    return s * (s + 1) // 2


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e = d["seq_len"], d["hidden_size"]
    count = {letter: d["pattern"].count(letter) for letter in "ME*"}
    h, p, n, g = (d["mamba_num_heads"], d["mamba_head_dim"],
                  d["ssm_state_size"], d["n_groups"])
    q, inner = d["chunk_size"], h * p
    # whole chunks and what is left of the last
    chunks, rest = divmod(s, q)
    tri = chunks * causal_pairs(q) + causal_pairs(rest)
    hq, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                  d["head_dim"])
    pairs = d["num_experts_per_tok"] * d["n_routed_experts"] \
        / d["router_outputs"]
    return {
        "ssm_proj": count["M"] * s * e * (3 * inner + 2 * g * n + h),
        "ssm_scan": count["M"] * (tri * (g * n + h * p)
                                  + 2 * s * h * p * n),
        "attn_proj": count["*"] * s * e * (2 * hq * dh + 2 * hk * dh),
        "attn_core": count["*"] * hq * dh * 2 * causal_pairs(s),
        "router": count["E"] * s * e * d["router_outputs"],
        "routed": count["E"] * s * pairs * 2 * e
        * d["moe_intermediate_size"],
        "shared": count["E"] * s * 2 * e
        * d["moe_shared_expert_intermediate_size"],
        "head": s * e * d["vocab_size"],
    }


def train_flops(config):
    return 3 * 2 * sum(forward_macs(dims(config)).values())


def parameters(config):
    """Parameters of the configuration as sized, from the reference's
    blob shapes."""
    import math
    from reference.nemotron_h import layer_specs
    return sum(math.prod(shape) for _, blobs in layer_specs(dims(config))
               for shape, *_ in blobs)


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def ssd_cost(config, batch):
    """The chunked scan under `ssm_scan`: 3 x the forward's causal count;
    bytes: x, B, C in bfloat16 and delta in float32 in, y out in bfloat16
    forward; the same and dy in, dx, dB, dC and d delta out backward. The
    chunks' states are an implementation's (a kernel may carry them in
    fast memory) and count nothing: what stores and reads them reads
    low."""
    d = dims(config)
    s, m = d["seq_len"], d["pattern"].count("M")
    h, p, n, g = (d["mamba_num_heads"], d["mamba_head_dim"],
                  d["ssm_state_size"], d["n_groups"])
    ops = 3 * 2 * batch * forward_macs(d)["ssm_scan"]
    token = (h * p + 2 * g * n) * 2 + h * 4         # x, B, C, delta
    bytes_ = m * batch * s * ((token + h * p * 2)
                              + (2 * token + 2 * h * p * 2))
    return ops, bytes_


def flash_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv over the causal half, 16 query
    heads a key-value head: 2 products forward, 5 backward (the scores once
    more, dV, dP, dQ, dK); bytes: q, k, v, o and the logsumexp forward; q,
    k, v, o, dO in and dq, dk, dv out backward, bfloat16."""
    d = dims(config)
    s, h, hk, dh = (d["seq_len"], d["num_attention_heads"],
                    d["num_key_value_heads"], d["head_dim"])
    layers = d["pattern"].count("*")
    ops = layers * batch * h * 7 * 2 * causal_pairs(s) * dh
    qo, kv = s * h * dh * 2, s * hk * dh * 2
    return ops, layers * batch * ((2 * qo + 2 * kv + s * h * 4)
                                  + (4 * qo + 4 * kv + s * h * 4))


def experts_cost(config, batch):
    """The held experts' TWO products under `moe_experts`, at the expected
    pairs and the published width: 3 x forward; bytes: the held experts'
    bfloat16 weights read forward and backward, their float32 gradients
    written, the rows in and out in bfloat16."""
    d = dims(config)
    layers, e, f = (d["pattern"].count("E"), d["hidden_size"],
                    d["moe_intermediate_size"])
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["n_routed_experts"] * 2 * e * f
    pairs = batch * d["seq_len"] * d["num_experts_per_tok"] \
        * d["n_routed_experts"] / d["router_outputs"]
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
    with open(os.path.join(HERE, "configs",
                           "nemotron_twotower_30b_a3b.json")) as f:
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


def scope_ms(ctx, scopes):
    """Device milliseconds a step under the scopes together, or None."""
    import scope_seconds
    got, n = scope_seconds.seconds(ctx, scopes), scope_seconds.steps(ctx)
    if not got or not n or sum(got.values()) <= 0:
        return None
    return sum(got.values()) / n * 1e3
