"""Plain float32 reference of Keye-VL-2.0-30B-A3B's decoder (`model_type`
KeyeVL2, the language model's settings), as one chip's share of an
expert-parallel group holds it: nothing of the program, `jax.numpy` only,
every layer by its equation. No bias anywhere, plain RMSNorm y = x / rms(x)
* w with w filled with 1, every layer alike.

x in R^hidden per token, block l of a sequence, h = RMSNorm_1(x):

  y   = x + Attn(h)
  out = y + MoE(RMSNorm_2(y))
  Attn — grouped-query attention over an index-picked key set:
        q = W_q h (heads x d), k = W_k h, v = W_v h (kv_heads x d), each
        key-value head serving heads / kv_heads query heads; a plain
        RMSNorm with one weight of d on every query head and one on every
        key head; rotate-half rotary on all d dimensions, positions 0..S-1
        the lightning indexer, reading hs = stop_gradient(h):
          qI = W_qI hs (index heads x dI), rotary on its first dI/2
          kI = LayerNorm(W_kI hs) (ONE key of dI a token), rotary likewise
          w  = W_w hs / sqrt(index heads x dI)
          I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
        S_t = the keys s <= t with I[t, s] >= the topk-th largest of
          I[t, :t+1] (an exact jax.lax.top_k over the seen scores; every
          key while t < topk; ties AT the threshold all kept), from
          stop_gradient(I): a selection has no gradient
        o = W_o softmax over S_t of (q k^T / sqrt(d)) v, a block of query
          rows at a time, index scores and main scores alike
        L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}
          I[t, s]), p = stop_gradient(mean over the heads of the main
          softmax): the indexer's own loss, which reaches W_qI, W_kI, W_w
          and the LayerNorm alone
  MoE — p = softmax(W_r g) over all the router's outputs in float32, the k
        largest, their weights divided by their sum (norm_topk_prob);
        FF = sum over the chosen experts THAT THIS CHIP HOLDS of w_e W_2,e
        (silu(W_1,e g) * W_3,e g): a loop over the held experts with a
        mask, nothing dropped; no shared expert
  head  logits = W_head RMSNorm_f(x_L) over the held rows of the
        vocabulary (untied), mean cross-entropy per token
  loss  = cross-entropy + the sum over the layers of L_I

`selection` (a key of the sizes, "index" unless a control sets it) swaps
S_t for the last topk keys ("window") or for every key s <= t ("dense"):
what the limits of the check must tell this model from. "index_bf16" picks
S_t as the program's indexer does, from bfloat16 inputs, weights, qI and kI,
and leaves every other number float32 (L_I's scores too): what the keys
that fall the other way at a query's threshold do alone, which the sound
limits must leave room for.

Left out, as in the program: the vision tower and its projector, the dense
warm-up stage of the indexer, the router's auxiliary loss, dropout.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "rope_theta", "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "vocab_size",
    "num_hidden_layers")
INDEX_KEYS = ("indexer_num_heads", "indexer_head_dim", "topk")


def dims(config):
    """The sizes a run uses: the configuration file's published keys and
    its `sa_config`'s, `builder_args` (the sequence length; a rehearsal's
    toy sizes) laid over them. `num_experts` is the number HELD; the
    router's width is `router_outputs` (the published `num_experts`)."""
    d = {k: config[k] for k in MODEL_KEYS}
    d.update({k: config["sa_config"][k] for k in INDEX_KEYS})
    d["indexer_topk"] = d.pop("topk")
    d["router_outputs"] = config["published"]["num_experts"]
    d["first_expert"] = 0
    d["selection"] = "index"
    d.update(config.get("builder_args", {}))
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


def rope(x, theta, dims_=None, first=0):
    """Rotate-half rotary embedding on the first `dims_` (default all) of
    the last axis; x is (S, heads, d), positions first..first+S-1."""
    s, _, dh = x.shape
    r = dh if dims_ is None else dims_
    if not r:
        return x
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = (first + jnp.arange(s)).astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xr = x[..., :r]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., r:]], -1)


def attention(x, blobs, d, store=lambda a: a, rows=64):
    """x (S, hidden) of one sequence, already normalised. -> (Attn(x)
    (S, hidden), L_I of this layer and sequence: the MEAN over its
    queries). The keys' side (k, v, kI) is made whole; the queries' side
    (q, qI, w, the scores, o and the out projection) a block of `rows`
    queries at a time, so that no array of all queries x all heads stands
    in memory but the result."""
    wq, wk, wv, wo, q_norm, k_norm, wqi, wki, ww, ln_w, ln_b = blobs
    s = x.shape[0]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    hi, di, topk = (d["indexer_num_heads"], d["indexer_head_dim"],
                    d["indexer_topk"])
    eps, theta, rot = d["rms_norm_eps"], d["rope_theta"], di // 2
    grp = h // hk                       # kv head j serves grp query heads
    k = (x @ store(wk).T).reshape(s, hk, dh)
    v = (x @ store(wv).T).reshape(s, hk, dh)
    k = store(rope(rms_norm(k, k_norm, eps), theta))
    xs = lax.stop_gradient(x)

    def index_key(xs, cast):
        return cast(rope(layer_norm(xs @ cast(wki).T, ln_w, ln_b,
                                    eps)[:, None, :], theta, rot))[:, 0, :]

    def index_scores(xsb, keys, lo, cast):
        qi = cast(rope((xsb @ cast(wqi).T).reshape(rows, hi, di), theta,
                       rot, lo))
        w = (xsb @ cast(ww).T) / math.sqrt(hi * di)
        return jnp.einsum("qj,jqk->qk", w, jax.nn.relu(jnp.einsum(
            "qjd,kd->jqk", qi, keys)))

    def low(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    ki = index_key(xs, store)
    ki_low = index_key(low(xs), low) if d["selection"] == "index_bf16" \
        else None
    rows = math.gcd(s, rows)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(lo):
        t = lo + jnp.arange(rows)
        seen = pos[None, :] <= t[:, None]
        xb = lax.dynamic_slice_in_dim(x, lo, rows, 0)
        xsb = lax.stop_gradient(xb)
        index = index_scores(xsb, ki, lo, store)
        if d["selection"] in ("index", "index_bf16"):
            pick = index if ki_low is None \
                else index_scores(low(xsb), ki_low, lo, low)
            held = jnp.where(seen, lax.stop_gradient(pick), -jnp.inf)
            kth = lax.top_k(held, min(topk, s))[0][:, -1]
            sel = seen & (held >= jnp.where(t < topk, -jnp.inf,
                                            kth)[:, None])
        elif d["selection"] == "window":
            sel = seen & (t[:, None] - pos[None, :] < topk)
        else:
            sel = seen
        q = (xb @ store(wq).T).reshape(rows, h, dh)
        q = store(rope(rms_norm(q, q_norm, eps), theta, None, lo))
        sc = jnp.einsum("qjgd,kjd->jgqk", q.reshape(rows, hk, grp, dh),
                        k) / math.sqrt(dh)
        mix = jax.nn.softmax(jnp.where(sel, sc, -jnp.inf), axis=-1)
        target = lax.stop_gradient(jnp.mean(mix, axis=(0, 1)))
        logq = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1)
        live = sel & (target > 0)
        kl = jnp.where(live, target * (
            jnp.log(jnp.where(live, target, 1.0))
            - jnp.where(live, logq, 0.0)), 0.0)
        o = jnp.einsum("jgqk,kjd->qjgd", mix, v).reshape(rows, h * dh)
        return store(o) @ store(wo).T, jnp.sum(kl)

    out, kl = lax.map(block, jnp.arange(0, s, rows))
    return out.reshape(s, -1), jnp.sum(kl) / s


def route(g, router, d):
    """-> (indices (n, k) into all the router's outputs, weights (n, k))."""
    p = jax.nn.softmax(g @ router.T, axis=-1)
    top, idx = lax.top_k(p, d["num_experts_per_tok"])
    if d["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top


def moe(g, blobs, d, store=lambda a: a):
    """g (n, hidden). The held experts are `first_expert` ..
    `first_expert + held - 1` of the router's outputs."""
    router, w1, w3, w2 = blobs
    idx, top = route(g, router, d)

    # the sum is the loop's carry and no input of the checkpointed part: a
    # carry that a checkpoint reads is kept once an expert, 268 MB each
    @jax.checkpoint
    def expert(e, gate, up, down):
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        out = (jax.nn.silu(g @ store(gate).T) * (g @ store(up).T)) \
            @ store(down).T
        return weight[:, None] * out

    def one(y, inp):
        return y + expert(*inp), None

    held = d["first_expert"] + jnp.arange(w1.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(g), (held, w1, w3, w2))
    return routed


def forward_loss(params, tokens, labels, d, quant=None):
    """Over the rows of `tokens` (rows, S): the SUM over the tokens of the
    cross-entropy, plus S x the layers' L_I of every row (so that dividing
    by rows x S gives the step's loss)."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)

    def block(x, p):
        ln1, attn, ln2, ff = p
        h = store(rms_norm(x, ln1[0], d["rms_norm_eps"]))
        op, kl = attention(h, attn, d, store)
        y = store(x + store(op))
        g = store(rms_norm(y, ln2[0], d["rms_norm_eps"]))
        return store(y + store(moe(g, ff, d, store))), kl

    def sequence(toks, labs):
        x = store(store(params["tok_embed"][0])[toks])
        index_loss = 0.0
        for i in range(d["num_hidden_layers"]):
            p = [params[f"block{i}/{n}"]
                 for n in ("ln1", "attn", "ln2", "moe")]
            x, kl = jax.checkpoint(block)(x, p)
            index_loss = index_loss + kl
        x = store(rms_norm(x, params["ln_f"][0], d["rms_norm_eps"]))
        head = store(params["lm_head"][0])
        rows = math.gcd(x.shape[0], 1024)

        @jax.checkpoint
        def picked(inp):            # the logits a block of tokens at a time
            xb, lb = inp
            logits = store(xb @ head.T)
            return jnp.sum(jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), lb[:, None], axis=-1))
        ce = -jnp.sum(lax.map(picked, (
            x.reshape(-1, rows, x.shape[1]), labs.reshape(-1, rows))))
        return ce + x.shape[0] * index_loss

    return sum(sequence(tokens[r], labels[r])
               for r in range(tokens.shape[0]))


# ------------------------------------------------- what the harness reads

def layer_specs(d):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order. Matrices are filled gaussian(0.02) and the embedding
    gaussian(1), as the program's builder fills them (the head is untied
    and the first mixer is attention, whose output all tokens share: at 1
    a token's own vector carries the residual stream and the routers see
    the tokens apart); norm weights 1, the index key's LayerNorm weight 1
    and bias 0, none of them decayed."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    one_, zero_, nodecay = ("constant", 1.0), ("constant", 0.0), (1.0, 0.0)
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    hi, di = d["indexer_num_heads"], d["indexer_head_dim"]
    held, f = d["num_experts"], d["moe_intermediate_size"]
    attn = [((h * dh, e), mat, keep), ((hk * dh, e), mat, keep),
            ((hk * dh, e), mat, keep), ((e, h * dh), mat, keep),
            ((dh,), one_, nodecay), ((dh,), one_, nodecay),
            ((hi * di, e), mat, keep), ((di, e), mat, keep),
            ((hi, e), mat, keep), ((di,), one_, nodecay),
            ((di,), zero_, nodecay)]
    ffn = [((d["router_outputs"], e), mat, keep), ((held, f, e), mat, keep),
           ((held, f, e), mat, keep), ((held, e, f), mat, keep)]
    specs = [("tok_embed", [((d["vocab_size"], e), ("gaussian", 1.0),
                             keep)])]
    for i in range(d["num_hidden_layers"]):
        specs += [(f"block{i}/ln1", [((e,), one_, nodecay)]),
                  (f"block{i}/attn", attn),
                  (f"block{i}/ln2", [((e,), one_, nodecay)]),
                  (f"block{i}/moe", ffn)]
    specs += [("ln_f", [((e,), one_, nodecay)]),
              ("lm_head", [((d["vocab_size"], e), mat, keep)])]
    return specs


class Reference:
    def __init__(self, config, batch):
        self.d = dims(config)
        self.batch, self.seq = batch, self.d["seq_len"]
        self.specs = layer_specs(self.d)
        self.inputs = [("data", (batch, self.seq), "int32"),
                       ("label", (batch, self.seq), "int32")]

    def make_step(self, solver, block_rows=None, quant=None, masters=None):
        # the device keeps every loaded program's temporaries reserved for
        # as long as its executable lives (the timed solver's step: 7.9 GB,
        # beside which this reference's first program could not be loaded);
        # dropping jax's caches unloads what ran before
        jax.clear_caches()
        n, rows = self.batch, block_rows or self.batch
        update = plain.make_update(
            solver, {name: [b[2] for b in blobs]
                     for name, blobs in self.specs}, masters)

        def block_grad(params, tokens, labels):
            return jax.value_and_grad(lambda p: forward_loss(
                p, tokens, labels, self.d, quant) / (n * self.seq))(params)

        # the gradient is summed into one set of buffers (donated), and the
        # update goes layer by layer: 465M parameters in float32 are 1.9 GB
        # a copy, and the harness keeps four of them besides
        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_block(acc, params, tokens, labels):
            loss, g = block_grad(params, tokens, labels)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss
        first_block = jax.jit(block_grad)

        # after the first step a layer's weights and moments are updated
        # in their own buffers (as reference/lfm2_moe.py: undonated, the
        # old and the new set of all layers stand side by side)
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
