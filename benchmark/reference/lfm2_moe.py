"""Plain float32 reference of LFM2-MoE (`model_type` lfm2_moe), as one
chip's share of an expert-parallel group holds it: nothing of the program,
`jax.numpy` only, every layer by its equation. No bias anywhere, plain
RMSNorm y = x / rms(x) * w with w filled with 1.

x in R^hidden per token, block l of a sequence:

  y   = x + Op_l(RMSNorm_1(x))
  out = y + FF_l(RMSNorm_2(y))
  Op_l, layer type "conv" — the gated short convolution:
        [B | C | u] = W_in h      three chunks of `hidden`, in that order
        z = B * u
        c_t = sum_{j<K} w[:, j] * z_{t-K+1+j}     depthwise, causal, K =
              conv_L_cache taps, the first K-1 positions see zeros
        Op = W_out (C * c)
  Op_l, layer type "full_attention" — grouped-query attention:
        q = W_q h (heads x d), k = W_k h, v = W_v h (kv_heads x d), each
        key-value head serving heads / kv_heads query heads; a plain
        RMSNorm with one weight of d on every query head and one on every
        key head; rotate-half rotary on all d dimensions, positions
        0..S-1; o = W_o softmax(q k^T / sqrt(d) + causal mask) v, a block
        of query rows at a time
  FF_l, l < num_dense_layers: W_2 (silu(W_1 g) * W_3 g)
  FF_l otherwise — the MoE: s = sigmoid(W_r g) over all the router's
        outputs; the k chosen are the largest of s + b, b the expert bias
        (a buffer: no gradient trains it); their weights the UNBIASED s_e
        divided by (their sum + 1e-6) (norm_topk_prob), times
        routed_scaling_factor; FF = sum over the chosen experts THAT THIS
        CHIP HOLDS of w_e W_2,e (silu(W_1,e g) * W_3,e g): a loop over the
        held experts with a mask, nothing dropped; no shared expert
  head  logits = E RMSNorm_f(x_L) with the EMBEDDING'S OWN table E over
        the held rows of the vocabulary (tied: the table stands once in
        `specs`, under `tok_embed`, and the head owns no blob), mean
        cross-entropy per token

Left out, as in the program: the bias's load-balancing update, any
auxiliary loss, dropout.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "norm_eps", "conv_L_cache", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
    "vocab_size")
TOPK_EPS = 1e-6


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `num_experts` is the number HELD; the router's width is
    `router_outputs` (the published `num_experts`). `layer_types` and
    `num_dense_layers` become this stage's: the entries of the published
    list that `layers_held` names, and how many of them lie before the
    published `num_dense_layers`."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["router_outputs"] = config["published"]["num_experts"]
    d["first_expert"] = 0
    d.update(config.get("builder_args", {}))
    held = config["layers_held"]
    d["layer_types"] = [config["layer_types"][i] for i in held]
    d["num_dense_layers"] = sum(1 for i in held
                                if i < config["num_dense_layers"])
    d["num_hidden_layers"] = len(held)
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half rotary embedding on the whole last axis; x is
    (S, heads, d), positions 0..S-1."""
    s, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + rot * sin


def short_conv(x, blobs, d, store=lambda a: a):
    """x (S, hidden) of one sequence, already normalised."""
    w_in, taps, w_out = blobs
    e, k = d["hidden_size"], taps.shape[1]
    bcu = store(x @ store(w_in).T)
    z = bcu[:, :e] * bcu[:, 2 * e:]
    zp = jnp.pad(z, ((k - 1, 0), (0, 0)))
    taps = store(taps)
    c = sum(zp[j:j + x.shape[0]] * taps[:, j] for j in range(k))
    return store(bcu[:, e:2 * e] * c) @ store(w_out).T


def attention(x, blobs, d, store=lambda a: a, rows=128):
    """x (S, hidden) of one sequence, already normalised."""
    wq, wk, wv, wo, q_norm, k_norm = blobs
    s = x.shape[0]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    q = (x @ store(wq).T).reshape(s, h, dh)
    k = (x @ store(wk).T).reshape(s, hk, dh)
    v = (x @ store(wv).T).reshape(s, hk, dh)
    q = rope(rms_norm(q, q_norm, d["norm_eps"]), d["rope_theta"])
    k = rope(rms_norm(k, k_norm, d["norm_eps"]), d["rope_theta"])
    k = jnp.repeat(k, h // hk, axis=1)          # kv head j serves h/hk heads
    v = jnp.repeat(v, h // hk, axis=1)
    rows = math.gcd(s, rows)

    @jax.checkpoint
    def block(lo):
        qb = lax.dynamic_slice_in_dim(q, lo, rows, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        seen = jnp.arange(s)[None, :] <= (lo + jnp.arange(rows))[:, None]
        mix = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", mix, v)

    o = lax.map(block, jnp.arange(0, s, rows)).reshape(s, h * dh)
    return o @ store(wo).T


def dense_ff(g, blobs, store=lambda a: a):
    w1, w3, w2 = blobs
    return store(jax.nn.silu(g @ store(w1).T) * (g @ store(w3).T)) \
        @ store(w2).T


def route(g, router, bias, d):
    """-> (indices (n, k) into all the router's outputs, weights (n, k)):
    chosen by score + bias, weighed by the score alone."""
    score = jax.nn.sigmoid(g @ router.T)
    _, idx = lax.top_k(score + bias, d["num_experts_per_tok"])
    top = jnp.take_along_axis(score, idx, axis=-1)
    if d["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + TOPK_EPS)
    return idx, top * d["routed_scaling_factor"]


def moe(g, blobs, d, store=lambda a: a):
    """g (n, hidden). The held experts are `first_expert` ..
    `first_expert + held - 1` of the router's outputs."""
    router, w1, w3, w2 = blobs[:4]
    bias = blobs[4] if d["use_expert_bias"] else 0.0
    idx, top = route(g, router, bias, d)

    @jax.checkpoint
    def one(y, inp):
        e, gate, up, down = inp
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        out = (jax.nn.silu(g @ store(gate).T) * (g @ store(up).T)) \
            @ store(down).T
        return y + weight[:, None] * out, None

    held = d["first_expert"] + jnp.arange(w1.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(g), (held, w1, w3, w2))
    return routed


def _ff_names(d, i):
    return ("ff_gate", "ff_up", "ff_down") if i < d["num_dense_layers"] \
        else ("moe",)


def forward_loss(params, tokens, labels, d, quant=None):
    """SUM over the tokens of `tokens` (rows, S) of the cross-entropy."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)

    def block(i, x, p):
        ln1, mixer, ln2, *ff = p
        h = store(rms_norm(x, ln1[0], d["norm_eps"]))
        op = short_conv(h, mixer, d, store) \
            if d["layer_types"][i] == "conv" else attention(h, mixer, d,
                                                            store)
        y = store(x + store(op))
        g = store(rms_norm(y, ln2[0], d["norm_eps"]))
        out = dense_ff(g, [b[0] for b in ff], store) \
            if i < d["num_dense_layers"] else moe(g, ff[0], d, store)
        return store(y + store(out))

    def sequence(toks, labs):
        table = store(params["tok_embed"][0])
        x = store(table[toks])
        for i in range(d["num_hidden_layers"]):
            p = [params[f"block{i}/{n}"]
                 for n in ("ln1", "mixer", "ln2") + _ff_names(d, i)]
            x = jax.checkpoint(block, static_argnums=0)(i, x, p)
        x = store(rms_norm(x, params["ln_f"][0], d["norm_eps"]))
        rows = math.gcd(x.shape[0], 1024)

        @jax.checkpoint
        def picked(inp):            # the logits a block of tokens at a time
            xb, lb = inp
            logits = store(xb @ table.T)
            return jnp.sum(jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), lb[:, None], axis=-1))
        return -jnp.sum(lax.map(picked, (
            x.reshape(-1, rows, x.shape[1]), labs.reshape(-1, rows))))

    return sum(sequence(tokens[r], labels[r])
               for r in range(tokens.shape[0]))


# ------------------------------------------------- what the harness reads

def layer_specs(d):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order. Matrices are filled gaussian(0.02), as the program's
    builder fills them, and the embedding with them: its table is the
    head's too, and filled gaussian(1) a token's own logit is 2048 / rms of
    the residual stream, a loss of 1,350 a token that no float32 softmax
    holds (PERF.md, PR 35); the first block's mixer is a conv, whose
    output is the token's own, so the routers see different inputs at
    0.02 (the other LMs' embeddings are filled at 1 for that);
    the conv taps uniform(+-1/sqrt(K)); the expert bias 0, and neither a
    rate nor a decay moves it. The tied table stands once, under
    `tok_embed`: the head owns no blob and has no entry."""
    e, k = d["hidden_size"], d["conv_L_cache"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    one_, nodecay = ("constant", 1.0), (1.0, 0.0)
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    held, f, i_ = (d["num_experts"], d["moe_intermediate_size"],
                   d["intermediate_size"])
    lim = 1.0 / math.sqrt(k)
    conv = [((3 * e, e), mat, keep), ((e, k), ("uniform", -lim, lim), keep),
            ((e, e), mat, keep)]
    attn = [((h * dh, e), mat, keep), ((hk * dh, e), mat, keep),
            ((hk * dh, e), mat, keep), ((e, h * dh), mat, keep),
            ((dh,), one_, nodecay), ((dh,), one_, nodecay)]
    ffn = [((d["router_outputs"], e), mat, keep),
           ((held, f, e), mat, keep), ((held, f, e), mat, keep),
           ((held, e, f), mat, keep)]
    if d["use_expert_bias"]:
        ffn.append(((d["router_outputs"],), ("constant", 0.0), (0.0, 0.0)))
    specs = [("tok_embed", [((d["vocab_size"], e), mat, keep)])]
    for i, kind in enumerate(d["layer_types"]):
        specs += [(f"block{i}/ln1", [((e,), one_, nodecay)]),
                  (f"block{i}/mixer", conv if kind == "conv" else attn),
                  (f"block{i}/ln2", [((e,), one_, nodecay)])]
        if i < d["num_dense_layers"]:
            specs += [(f"block{i}/ff_gate", [((i_, e), mat, keep)]),
                      (f"block{i}/ff_up", [((i_, e), mat, keep)]),
                      (f"block{i}/ff_down", [((e, i_), mat, keep)])]
        else:
            specs.append((f"block{i}/moe", ffn))
    specs.append(("ln_f", [((e,), one_, nodecay)]))
    return specs


class Reference:
    def __init__(self, config, batch):
        self.d = dims(config)
        self.batch, self.seq = batch, self.d["seq_len"]
        self.specs = layer_specs(self.d)
        self.inputs = [("data", (batch, self.seq), "int32"),
                       ("label", (batch, self.seq), "int32")]

    def make_step(self, solver, block_rows=None, quant=None, masters=None):
        n, rows = self.batch, block_rows or self.batch
        update = plain.make_update(
            solver, {name: [b[2] for b in blobs]
                     for name, blobs in self.specs}, masters)

        def block_grad(params, tokens, labels):
            return jax.value_and_grad(lambda p: forward_loss(
                p, tokens, labels, self.d, quant) / (n * self.seq))(params)

        # the gradient is summed into one set of buffers (donated), and the
        # update goes layer by layer: 508M parameters in float32 are 2 GB
        # a copy, and the harness keeps four of them besides
        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_block(acc, params, tokens, labels):
            loss, g = block_grad(params, tokens, labels)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss
        first_block = jax.jit(block_grad)

        # after the first step a layer's weights and moments are updated
        # in their own buffers: undonated, the old and the new set of all
        # layers stand side by side when a step ends (8 x 2 GB with the
        # first weights and the first gradient, which the harness keeps)
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
