"""Plain float32 reference of Laguna-XS.2 (`model_type` laguna), as one
chip's share of an expert-parallel group holds it: nothing of the program,
`jax.numpy` only, every layer by its equation. No bias anywhere, plain
RMSNorm y = x / rms(x) * w with w filled with 1.

x in R^hidden per token, block l of a sequence:

  h   = RMSNorm_1(x);  y = x + Attn_l(h)
  out = y + FF_l(RMSNorm_2(y))
  Attn_l  H_l = num_attention_heads_per_layer[l] query heads on
        num_key_value_heads key-value heads of head_dim, key-value head j
        serving H_l / kv_heads query heads: q = W_q h, k = W_k h, v = W_v h,
        g = sigmoid(W_g h) in R^H_l, ONE scalar a head and a token;
        rotate-half rotary, positions 0..S-1, as rope_parameters[kind]
        says for the layer's kind (layer_types[l]): on the first
        partial_rotary_factor x head_dim dimensions of every query and key
        head, the rest passing; frequencies inv_i = theta^(-2i/d) over the
        d turned dimensions, and with rope_type yarn the blend the
        `transformers` library computes — inter_i = inv_i / factor, low =
        floor(d ln(L / (beta_fast 2 pi)) / (2 ln theta)), high = ceil(d
        ln(L / (beta_slow 2 pi)) / (2 ln theta)) inside [0, d - 1], ramp_i
        = clip((i - low) / (high - low), 0, 1), inv_i = inter_i ramp_i +
        inv_i (1 - ramp_i) — with cos and sin times attention_factor, so
        that the turned part of q and of k is scaled and the passing part
        is not;
        o_h = g_h softmax(q_h k^T / sqrt(head_dim) + mask) v, causal, and
        in a sliding_attention layer key j visible to query i iff
        i - sliding_window < j <= i; Attn = W_o [o_1 .. o_H]. A block of
        query rows at a time; a window layer's block reads only the window
        + rows keys that can be visible to it
  FF_l, mlp_layer_types[l] dense: W_2 (silu(W_1 u) * W_3 u)
  FF_l, sparse — the MoE: s = sigmoid(W_r u) over all the router's outputs,
        the k largest chosen, their weights s_e divided by (their sum +
        1e-20) times moe_routed_scaling_factor, on the experts' OUTPUTS;
        FF = sum over the chosen experts THAT THIS CHIP HOLDS of w_e W_2,e
        (silu(W_1,e u) * W_3,e u): a loop over the held experts with a
        mask, nothing dropped; plus one shared expert of the same form at
        shared_expert_intermediate_size, of every token, added with no gate
  head  logits = W_head RMSNorm_f(x_L) over the held rows of the
        vocabulary (untied), mean cross-entropy per token

Assumed (the configuration file lists the same): the gate one scalar a
head; sigmoid scores, renormalised, no correction bias; the ungated shared
expert; no head norm; rotate-half pairing; the 1e-20. Left out, as in the
program: the router's auxiliary loss, dropout, packing.

`output_gate` and `yarn_rope` false are CONTROLS (benchmark/
control_laguna.py), never the model: g = 1, and the full layers given the
window layers' rotary (the plain table on the whole head, no factor).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_key_value_heads", "head_dim", "layer_types",
    "num_attention_heads_per_layer", "mlp_layer_types", "sliding_window",
    "rope_parameters", "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "moe_routed_scaling_factor", "vocab_size")
PER_LAYER = ("layer_types", "num_attention_heads_per_layer",
             "mlp_layer_types")
TOPK_EPS = 1e-20


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `num_experts` is the number HELD; the router's width is
    `router_outputs` (the published count). The three per-layer lists are
    read at their first `num_hidden_layers` entries."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["router_outputs"] = config["published"]["num_experts"]
    d["first_expert"] = 0
    d["output_gate"] = d["yarn_rope"] = True
    d.update(config.get("builder_args", {}))
    for key in PER_LAYER:
        d[key] = list(d[key])[:d["num_hidden_layers"]]
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def frequencies(rp, dim):
    """-> (inv_freq (dim / 2,), the factor on cos and sin) of one kind of
    layer's `rope_parameters` over `dim` turned dimensions."""
    idx = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = rp["rope_theta"] ** (-idx / dim)
    if rp.get("rope_type", "default") != "yarn":
        return extra, 1.0
    inter = extra / rp["factor"]

    def turns(beta):    # the dimension that turns `beta` times inside L
        return dim * math.log(rp["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) \
            / (2 * math.log(rp["rope_theta"]))
    low = max(math.floor(turns(rp["beta_fast"])), 0)
    high = min(math.ceil(turns(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    factor = rp.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rp["factor"]) + 1.0
    return inter * ramp + extra * (1.0 - ramp), factor


def rope(x, rp, first=0):
    """Rotate-half rotary on the first partial_rotary_factor of the last
    axis; x is (S, ..., d) with head axes between, positions `first` ..
    `first` + S - 1."""
    s, dh = x.shape[0], x.shape[-1]
    dim = int(dh * rp.get("partial_rotary_factor", 1))
    inv, factor = frequencies(rp, dim)
    ang = (first + jnp.arange(s)).astype(jnp.float32)[:, None] \
        * inv[None, :]
    over_heads = (s,) + (1,) * (x.ndim - 2) + (dim,)
    cos = factor * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)],
                                   -1).reshape(over_heads)
    sin = factor * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)],
                                   -1).reshape(over_heads)
    turned, passing = x[..., :dim], x[..., dim:]
    rot = jnp.concatenate([-turned[..., dim // 2:], turned[..., :dim // 2]],
                          -1)
    return jnp.concatenate([turned * cos + rot * sin, passing], -1)


def attention(x, blobs, d, heads, kind, store=lambda a: a, rows=128):
    """x (S, hidden) of one sequence, already normalised; `heads` query
    heads; `kind` one of rope_parameters' keys. A block of `rows` queries
    at a time (their heads, rotary, gate and out projection made in the
    block) against the keys it can see; query head h reads key-value head
    h // (heads / kv_heads), in place."""
    wq, wk, wv, wo, wg = blobs
    s = x.shape[0]
    hk, dh = d["num_key_value_heads"], d["head_dim"]
    grp = heads // hk
    window = d["sliding_window"] if kind == "sliding_attention" else 0
    rp = d["rope_parameters"][
        kind if d.get("yarn_rope", True) else "sliding_attention"]
    k = rope(store(x @ store(wk).T).reshape(s, hk, dh), rp)
    v = store(x @ store(wv).T).reshape(s, hk, dh)
    rows = math.gcd(s, rows)
    if window and window + rows < s:
        # the keys a block of rows can see: from lo - window to its last
        # row, `window` zeros in front so that every block's span exists
        span, front = window + rows, window
        k = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))
    else:
        span, front = s, 0

    @jax.checkpoint
    def block(lo):
        xb = lax.dynamic_slice_in_dim(x, lo, rows, 0)
        qb = rope(store(xb @ store(wq).T).reshape(rows, hk, grp, dh), rp,
                  first=lo)
        gate = jax.nn.sigmoid(xb @ store(wg).T) \
            if d.get("output_gate", True) else jnp.ones((rows, heads),
                                                        x.dtype)
        first = lo if front else 0              # in the padded keys
        kb = lax.dynamic_slice_in_dim(k, first, span, 0)
        vb = lax.dynamic_slice_in_dim(v, first, span, 0)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, kb) / math.sqrt(dh)
        i = (lo + jnp.arange(rows))[:, None]
        j = (first - front + jnp.arange(span))[None, :]
        seen = (j <= i) & (j >= 0)
        if window:
            seen = seen & (i - j < window)
        mix = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", mix, vb).reshape(rows, heads, dh)
        return store(o * gate[:, :, None]).reshape(rows, heads * dh) \
            @ store(wo).T

    return lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)


def gated_ff(g, w1, w3, w2, store=lambda a: a):
    return store(jax.nn.silu(g @ store(w1).T) * (g @ store(w3).T)) \
        @ store(w2).T


def route(g, router, d):
    """-> (indices (n, k) into all the router's outputs, weights (n, k))."""
    score = jax.nn.sigmoid(g @ router.T)
    top, idx = lax.top_k(score, d["num_experts_per_tok"])
    top = top / (jnp.sum(top, -1, keepdims=True) + TOPK_EPS)
    return idx, top * d["moe_routed_scaling_factor"]


def moe(g, blobs, d, store=lambda a: a):
    """g (n, hidden). The held experts are `first_expert` ..
    `first_expert + held - 1` of the router's outputs; the shared expert
    sees every token."""
    router, w1, w3, w2, ws1, ws3, ws2 = blobs
    idx, top = route(g, router, d)

    # the sum is the loop's carry and no input of the checkpointed part
    @jax.checkpoint
    def expert(e, gate, up, down):
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        return weight[:, None] * gated_ff(g, gate, up, down, store)

    def one(y, inp):
        return y + expert(*inp), None

    held = d["first_expert"] + jnp.arange(w1.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(g), (held, w1, w3, w2))
    return routed + gated_ff(g, ws1, ws3, ws2, store)


FF_DENSE = ("ff_gate", "ff_up", "ff_down")


def forward_loss(params, tokens, labels, d, quant=None):
    """SUM over the tokens of `tokens` (rows, S) of the cross-entropy."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)
    eps = d["rms_norm_eps"]

    def block(i, x, p):
        ln1, attn, ln2, *ff = p
        h = store(rms_norm(x, ln1[0], eps))
        y = store(x + store(attention(
            h, attn, d, d["num_attention_heads_per_layer"][i],
            d["layer_types"][i], store)))
        u = store(rms_norm(y, ln2[0], eps))
        out = gated_ff(u, *[b[0] for b in ff], store) \
            if d["mlp_layer_types"][i] == "dense" else moe(u, ff[0], d, store)
        return store(y + store(out))

    def sequence(toks, labs):
        x = store(store(params["tok_embed"][0])[toks])
        for i in range(d["num_hidden_layers"]):
            ff = FF_DENSE if d["mlp_layer_types"][i] == "dense" else ("moe",)
            p = [params[f"block{i}/{n}"] for n in ("ln1", "attn", "ln2") + ff]
            x = jax.checkpoint(functools.partial(block, i))(x, p)
        x = store(rms_norm(x, params["ln_f"][0], eps))
        head = store(params["lm_head"][0])
        rows = math.gcd(x.shape[0], 1024)

        @jax.checkpoint
        def picked(inp):            # the logits a block of tokens at a time
            xb, lb = inp
            logits = store(xb @ head.T)
            return jnp.sum(jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), lb[:, None], axis=-1))
        return -jnp.sum(lax.map(picked, (
            x.reshape(-1, rows, x.shape[1]), labs.reshape(-1, rows))))

    return sum(sequence(tokens[r], labels[r])
               for r in range(tokens.shape[0]))


# ------------------------------------------------- what the harness reads

def layer_specs(d):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order. Matrices gaussian(0.02); the embedding gaussian(1)
    (the head is untied and the first mixer is attention, whose output all
    tokens share: at 1 a token's own vector carries the residual stream
    from the first step); norm weights 1 without decay."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    norm = [((e,), ("constant", 1.0), (1.0, 0.0))]
    hk, dh = d["num_key_value_heads"], d["head_dim"]
    held, f, fs, i_ = (d["num_experts"], d["moe_intermediate_size"],
                       d["shared_expert_intermediate_size"],
                       d["intermediate_size"])
    ffn = [((d["router_outputs"], e), mat, keep),
           ((held, f, e), mat, keep), ((held, f, e), mat, keep),
           ((held, e, f), mat, keep), ((fs, e), mat, keep),
           ((fs, e), mat, keep), ((e, fs), mat, keep)]
    specs = [("tok_embed", [((d["vocab_size"], e), ("gaussian", 1.0),
                             keep)])]
    for i in range(d["num_hidden_layers"]):
        h, p = d["num_attention_heads_per_layer"][i], f"block{i}/"
        specs += [(p + "ln1", norm),
                  (p + "attn", [((h * dh, e), mat, keep),
                                ((hk * dh, e), mat, keep),
                                ((hk * dh, e), mat, keep),
                                ((e, h * dh), mat, keep),
                                ((h, e), mat, keep)]),
                  (p + "ln2", norm)]
        if d["mlp_layer_types"][i] == "dense":
            specs += [(p + "ff_gate", [((i_, e), mat, keep)]),
                      (p + "ff_up", [((i_, e), mat, keep)]),
                      (p + "ff_down", [((e, i_), mat, keep)])]
        else:
            specs += [(p + "moe", ffn)]
    return specs + [("ln_f", norm),
                    ("lm_head", [((d["vocab_size"], e), mat, keep)])]


class Reference:
    def __init__(self, config, batch):
        self.d = dims(config)
        self.batch, self.seq = batch, self.d["seq_len"]
        self.specs = layer_specs(self.d)
        self.inputs = [("data", (batch, self.seq), "int32"),
                       ("label", (batch, self.seq), "int32")]

    def make_step(self, solver, block_rows=None, quant=None, masters=None):
        # the device keeps every loaded program's temporaries reserved for
        # as long as its executable lives; dropping jax's caches unloads
        # the timed solver's step before this reference's first program
        # (reference/keye_vl2.py found it)
        jax.clear_caches()
        n, rows = self.batch, block_rows or self.batch
        update = plain.make_update(
            solver, {name: [b[2] for b in blobs]
                     for name, blobs in self.specs}, masters)

        def block_grad(params, tokens, labels):
            return jax.value_and_grad(lambda p: forward_loss(
                p, tokens, labels, self.d, quant) / (n * self.seq))(params)

        # the gradient is summed into one set of buffers (donated), and the
        # update goes layer by layer: 490M parameters in float32 are 2.0 GB
        # a copy, and the harness keeps four of them besides
        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_block(acc, params, tokens, labels):
            loss, g = block_grad(params, tokens, labels)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss
        first_block = jax.jit(block_grad)

        # after the first step a layer's weights and moments are updated
        # in their own buffers
        @functools.partial(jax.jit, donate_argnums=(0, 2))
        def update_in_place(p, taken, s, g):
            return update(p, (taken, s), g)

        def step(params, history, data, labels, key):
            loss, grads = first_block(params, data[:rows], labels[:rows])
            for lo in range(rows, n, rows):
                grads, l = add_block(grads, params, data[lo:lo + rows],
                                     labels[lo:lo + rows])
                loss = loss + l
            # the harness reads the gradient of the first step only: after
            # it each layer's gradient is dropped as soon as it is applied
            keep = history is None
            taken, slots = (None, {}) if keep else history
            new_params, new_slots, given = {}, {}, grads if keep else None
            for name in list(params):
                if keep:    # the weights are the harness's own, w0
                    p, (taken_next, s) = update(
                        {name: params[name]}, None, {name: grads[name]})
                else:
                    p, (taken_next, s) = update_in_place(
                        {name: params[name]}, taken,
                        {name: slots[name]}, {name: grads.pop(name)})
                new_params[name], new_slots[name] = p[name], s[name]
            return new_params, (taken_next, new_slots), loss, given
        return step


def build(config, batch):
    return Reference(config, batch)
