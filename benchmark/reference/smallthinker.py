"""Plain float32 reference of SmallThinker (`model_name`
smallthinker_21b_instruct), as one chip's share of an expert-parallel group
holds it: nothing of the program, `jax.numpy` only, every layer by its
equation. No bias anywhere, RMSNorm weights filled with 1.

x in R^hidden per token, block l of a sequence:

  h   = RMSNorm_1(x)                          y = x / rms(x) * w
  r   = W_r h                                 router logits, FROM THE
                                              PRE-ATTENTION NORM
  y   = x + Attn_l(h)
  g   = RMSNorm_2(y)
  p   = softmax(r) over all the router's outputs; the top k, weights
        divided by their sum (= softmax over the k largest logits)
  out = y + sum over the chosen experts THAT THIS CHIP HOLDS of
        p_e W_down,e (relu(W_gate,e g) * W_up,e g): a loop over the held
        experts with a mask, nothing dropped; no shared expert
  Attn_l  q = W_q h (heads x d), k = W_k h, v = W_v h (kv_heads x d), each
        key-value head serving heads / kv_heads query heads;
        o = W_o softmax(q k^T / sqrt(d) + mask) v
        rope_layout[l] = 1: rotate-half rotary on all d dimensions,
        positions 0..S-1; 0: NO positional encoding
        sliding_window_layout[l] = 1: key j visible to query i iff
        i - window < j <= i (window keys, the query's own among them, as
        Hugging Face's sliding-window mask has it); 0: causal, j <= i
        A block of query rows at a time, so that 16,384 keys fit: a
        window layer's block reads only the window + rows keys that can be
        visible to it (a slice of the keys padded in front), masked by
        position like any other
  head    logits = W_head RMSNorm_f(x_L) over the held rows of the
        vocabulary, mean cross-entropy per token

Left out, as in the program: the router's auxiliary loss, dropout.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "rope_layout", "sliding_window_layout", "sliding_window_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_ffn_hidden_size", "norm_topk_prob", "vocab_size")


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `moe_num_primary_experts` is the number HELD; the router's width
    is `router_outputs` (the published `moe_num_primary_experts`). The two
    layouts are cut to the layers held: the first of them."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["router_outputs"] = config["published"]["moe_num_primary_experts"]
    d["first_expert"] = 0
    d.update(config.get("builder_args", {}))
    for key in ("rope_layout", "sliding_window_layout"):
        d[key] = list(d[key])[:d["num_hidden_layers"]]
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


def attention(x, blobs, d, rotary, window, store=lambda a: a, rows=128):
    """x (S, hidden) of one sequence, already normalised."""
    wq, wk, wv, wo = blobs
    s = x.shape[0]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    q = (x @ store(wq).T).reshape(s, h, dh)
    k = (x @ store(wk).T).reshape(s, hk, dh)
    v = (x @ store(wv).T).reshape(s, hk, dh)
    if rotary:
        q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    k = jnp.repeat(k, h // hk, axis=1)          # kv head j serves h/hk heads
    v = jnp.repeat(v, h // hk, axis=1)
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
        qb = lax.dynamic_slice_in_dim(q, lo, rows, 0)
        first = lo if front else 0              # in the padded keys
        kb = lax.dynamic_slice_in_dim(k, first, span, 0)
        vb = lax.dynamic_slice_in_dim(v, first, span, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, kb) / math.sqrt(dh)
        i = (lo + jnp.arange(rows))[:, None]
        j = (first - front + jnp.arange(span))[None, :]
        seen = (j <= i) & (j >= 0)
        if window:
            seen = seen & (i - j < window)
        mix = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", mix, vb)

    o = lax.map(block, jnp.arange(0, s, rows)).reshape(s, h * dh)
    return o @ store(wo).T


def route(h, router, d):
    """-> (indices (n, k) into all the router's outputs, weights (n, k))."""
    p = jax.nn.softmax(h @ router.T, axis=-1)
    top, idx = lax.top_k(p, d["moe_num_active_primary_experts"])
    if d["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top


def moe(g, h, blobs, d, store=lambda a: a):
    """g (n, hidden) feeds the experts, h (n, hidden) the router. The held
    experts are `first_expert` .. `first_expert + held - 1` of the
    router's outputs."""
    router, wg, wu, wd = blobs
    idx, top = route(h, router, d)

    @jax.checkpoint
    def one(y, inp):
        e, gate, up, down = inp
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        out = (jax.nn.relu(g @ store(gate).T) * (g @ store(up).T)) \
            @ store(down).T
        return y + weight[:, None] * out, None

    held = d["first_expert"] + jnp.arange(wg.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(g), (held, wg, wu, wd))
    return routed


def forward_loss(params, tokens, labels, d, quant=None):
    """SUM over the tokens of `tokens` (rows, S) of the cross-entropy."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)

    def block(i, x, p):
        ln1, attn, ln2, ffn = p
        h = store(rms_norm(x, ln1[0], d["rms_norm_eps"]))
        y = store(x + store(attention(
            h, attn, d, d["rope_layout"][i],
            d["sliding_window_size"] * d["sliding_window_layout"][i],
            store)))
        g = store(rms_norm(y, ln2[0], d["rms_norm_eps"]))
        return store(y + store(moe(g, h, ffn, d, store)))

    def sequence(toks, labs):
        x = store(store(params["tok_embed"][0])[toks])
        for i in range(d["num_hidden_layers"]):
            p = [params[f"block{i}/{n}"]
                 for n in ("ln1", "attn", "ln2", "moe")]
            x = jax.checkpoint(block, static_argnums=0)(i, x, p)
        x = store(rms_norm(x, params["ln_f"][0], d["rms_norm_eps"]))
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
    program's order. Matrices are filled gaussian(0.02), as the program's
    builder fills them; the embedding is filled gaussian(1) (what
    torch.nn.Embedding does unasked): at 0.02 the blocks' outputs, which
    every token shares, outweigh a token's own vector in the residual
    stream, the deeper routers see all but one input, and the share of the
    pairs that lands on the held experts swings between 1% and 25% with
    the seed and the step (PERF.md, PR 33)."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    one_, nodecay = ("constant", 1.0), (1.0, 0.0)
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    held, f = d["moe_num_primary_experts"], d["moe_ffn_hidden_size"]
    attn = [((h * dh, e), mat, keep), ((hk * dh, e), mat, keep),
            ((hk * dh, e), mat, keep), ((e, h * dh), mat, keep)]
    ffn = [((d["router_outputs"], e), mat, keep),
           ((held, f, e), mat, keep), ((held, f, e), mat, keep),
           ((held, e, f), mat, keep)]
    specs = [("tok_embed", [((d["vocab_size"], e),
                             ("gaussian", 1.0), keep)])]
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
        n, rows = self.batch, block_rows or self.batch
        update = plain.make_update(
            solver, {name: [b[2] for b in blobs]
                     for name, blobs in self.specs}, masters)

        def block_grad(params, tokens, labels):
            return jax.value_and_grad(lambda p: forward_loss(
                p, tokens, labels, self.d, quant) / (n * self.seq))(params)

        # the gradient is summed into one set of buffers (donated), and the
        # update goes layer by layer: 370M parameters in float32 are 1.5 GB
        # a copy, and the harness keeps four of them besides
        @functools.partial(jax.jit, donate_argnums=(0,))
        def add_block(acc, params, tokens, labels):
            loss, g = block_grad(params, tokens, labels)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss
        first_block = jax.jit(block_grad)

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
                g = grads[name] if keep else grads.pop(name)
                p, (taken_next, s) = update(
                    {name: params[name]},
                    None if keep else (taken, {name: slots[name]}),
                    {name: g})
                del g
                new_params[name], new_slots[name] = p[name], s[name]
            return new_params, (taken_next, new_slots), loss, given
        return step


def build(config, batch):
    return Reference(config, batch)
