"""Operations to train GLM-4.7-Flash on one sequence, the operations and
bytes of its kernels BY THE ALGORITHM, and the share of a roofline they
make, from shapes alone.

`train_flops(config)`: 3 x the forward pass (the backward pass contracts
once for the activations' gradient and once for the weights'), 2 FLOPs a
multiply-accumulate: the latent attention's five products (W_qa, W_qb,
W_kva, W_kvb, W_o); its core over the causal half, a key of 192 + 64 and a
value of 256 a head; the dense layer's three; the routers; the three-matrix
SiLU experts at the EXPECTED number of token-expert pairs on held experts;
the shared expert's three products; the head over the held rows of the
vocabulary; with the multi-token-prediction module also W_eh, one more MoE
block and the head once more. The norms, the rotary, the key's assembly,
the gates and the sums are elementwise and count nothing. Recomputation
never counts, and neither does what an implementation computes beyond the
algorithm: a full square where the causal half would do, a padded tile.
"""

import json
import os

from reference.glm4_moe_lite import dims

HERE = os.path.dirname(os.path.abspath(__file__))


def causal_pairs(s):
    return s * (s + 1) // 2


def latent_widths(d):
    """(rows of W_qa + W_qb + W_kva + W_kvb counted as multiply-accumulates
    a token, those of W_o)."""
    e, h = d["hidden_size"], d["num_attention_heads"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    dn, dr, dv = (d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                  d["v_head_dim"])
    return (rq * e + h * (dn + dr) * rq + (rkv + dr) * e
            + h * (dn + dv) * rkv), e * h * dv


def forward_macs(d):
    """{part: multiply-accumulates of one sequence's forward pass}."""
    s, e = d["seq_len"], d["hidden_size"]
    mtp = d["num_nextn_predict_layers"]
    layers = d["num_hidden_layers"]
    dense = min(d["first_k_dense_replace"], layers)
    sparse = layers - dense + mtp           # a module is one more MoE block
    h = d["num_attention_heads"]
    dqk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    latent, out = latent_widths(d)
    f = d["moe_intermediate_size"]
    pairs = d["num_experts_per_tok"] * d["n_routed_experts"] \
        / d["router_outputs"]
    return {
        "attn_latent": (layers + mtp) * s * latent,
        "attn_out": (layers + mtp) * s * out,
        "attn_core": (layers + mtp) * h * (dqk + d["v_head_dim"])
        * causal_pairs(s),
        "dense_ff": dense * s * 3 * e * d["intermediate_size"],
        "router": sparse * s * e * d["router_outputs"],
        "routed": sparse * s * pairs * 3 * e * f,
        "shared": sparse * s * 3 * e * d["n_shared_experts"] * f,
        "head": (1 + mtp) * s * e * d["vocab_size"],
        "mtp_proj": mtp * s * 2 * e * e,
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
    from reference.glm4_moe_lite import layer_specs
    return sum(math.prod(shape) for _, blobs in layer_specs(dims(config))
               for shape, *_ in blobs)


# -- the kernels: (operations, bytes) of one STEP of `batch` sequences, all
# layers that run the kernel together; forward and backward, no recompute

def flash_cost(config, batch):
    """flash_fwd + flash_dq + flash_dkv over the causal half, 20 query
    heads on 20 key-value heads of 256 (a key 192 + 64, a value 256): 2
    products forward, 5 backward (the scores once more, dV, dP, dQ, dK);
    bytes: q, k, v, o and the logsumexp forward; q, k, v, o, dO in and dq,
    dk, dv out backward, bfloat16."""
    d = dims(config)
    s, h = d["seq_len"], d["num_attention_heads"]
    layers = d["num_hidden_layers"] + d["num_nextn_predict_layers"]
    dqk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    dv = d["v_head_dim"]
    # by width: four of the seven products contract or produce the key's
    # width (the scores twice, dQ, dK), three the value's (P V, dV, dP)
    ops = layers * batch * h * 2 * causal_pairs(s) * (4 * dqk + 3 * dv)
    q, k, v = s * h * dqk * 2, s * h * dqk * 2, s * h * dv * 2
    return ops, layers * batch * ((q + k + 2 * v + s * h * 4)
                                  + (2 * q + 2 * k + 4 * v + s * h * 4))


def latent_cost(config, batch):
    """The FOUR latent products under `mla_q_latent` and `mla_kv_latent`
    (W_qa, W_qb, W_kva, W_kvb; the fifth narrow product, W_o, lies under
    `attn_proj_out` and `lm_proj_ms` reads it): 3 x forward; bytes: a
    token's bfloat16 rows forward — h in, c_q out and in again past its
    norm, q out, [c_kv | k_pe] out and c_kv in again, [k_nope | v] out —
    twice that backward (what was kept and the cotangents in, the
    gradients out), and the four matrices' bfloat16 copies read forward and
    backward, their float32 gradients written."""
    d = dims(config)
    s, e, h = d["seq_len"], d["hidden_size"], d["num_attention_heads"]
    layers = d["num_hidden_layers"] + d["num_nextn_predict_layers"]
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    dn, dr, dv = (d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                  d["v_head_dim"])
    latent, _ = latent_widths(d)
    ops = 3 * 2 * layers * batch * s * latent
    token = 2 * (e + 2 * rq + h * (dn + dr) + (rkv + dr) + rkv
                 + h * (dn + dv))
    return ops, layers * (batch * s * 3 * token + latent * (2 + 2 + 4))


def experts_cost(config, batch):
    """The held experts' three products under `moe_experts`, at the
    expected pairs (512 an expert a sequence of 8,192): 3 x forward; bytes:
    the held experts' bfloat16 weights read forward and backward, their
    float32 gradients written, the rows in and out in bfloat16."""
    d = dims(config)
    e, f = d["hidden_size"], d["moe_intermediate_size"]
    layers = d["num_hidden_layers"] + d["num_nextn_predict_layers"] \
        - min(d["first_k_dense_replace"], d["num_hidden_layers"])
    ops = 3 * 2 * batch * forward_macs(d)["routed"]
    weights = layers * d["n_routed_experts"] * 3 * e * f
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
    with open(os.path.join(HERE, "configs", "glm_4_7_flash.json")) as f:
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
