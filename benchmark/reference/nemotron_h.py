"""Plain float32 reference of the Nemotron-H tower of
Nemotron-Labs-TwoTower-30B-A3B (`model_type` nemotron_h), trained as a
causal language model, as one chip's share of an expert-parallel group
holds it: nothing of the program, `jax.numpy` only, every layer by its
equation. No bias on any projection, plain RMSNorm y = x / rms(x) * w with
w filled with 1.

x in R^hidden per token; EVERY block is out = x + Mixer(RMSNorm(x)) with ONE
mixer, chosen by the block's letter in the pattern; h = RMSNorm(x):

  M — Mamba-2, H heads of P channels, a state of N, G groups (head i reads
      group i // (H / G)), K conv taps:
        [z | xBC | dt] = W_in h        widths H P | H P + 2 G N | H
        xBC = silu(conv(xBC) + b_c)    causal depthwise, zeros before 0
        x (H x P) | B | C (G x N) = xBC
        delta_t = softplus(dt_t + dt_bias);  a_t = exp(-delta_t exp(A_log))
        THE RECURRENCE, token by token (a `lax.scan` over the tokens of a
        segment inside a `lax.scan` over the segments, so that the backward
        pass holds one segment's states): H_{-1} = 0,
            H_t = a_t H_{t-1} + delta_t x_t B_t^T       (H is P x N a head)
            y_t = H_t C_t + D x_t
        g = y * silu(z);  y = w * g / rms(g) over each group's H P / G
        channels (the gate first, then the norm);  out = W_out y
      `carry` false (a control, never the model) starts every segment of
      `chunk_size` tokens from a zero state: a window of one chunk in the
      state-space layer's place.
  * — grouped-query attention, causal, NO positional encoding, no head norm:
        q = W_q h (heads x d), k = W_k h, v = W_v h (kv_heads x d), each
        key-value head serving heads / kv_heads query heads;
        o = W_o softmax(q k^T / sqrt(d)) v, a block of query rows at a time
  E — s = sigmoid(W_r h) over all the router's outputs; the k largest of
      s + b (b a buffer of zeros); their weights the UNBIASED s, divided by
      (their sum + 1e-20), times the scaling factor; FF = sum over the
      chosen experts THAT THIS CHIP HOLDS of w_e W_down,e relu(W_up,e h)^2
      (a loop over the held experts with a mask, nothing dropped) plus the
      shared expert W_sd relu(W_su h)^2, added with no gate
  head  logits = W_head RMSNorm_f(x_L) over the held rows of the
        vocabulary (untied), mean cross-entropy per token

Left out, as in the program: the second (denoiser) tower, adaLN,
bidirectional in-block attention, cross-tower conditioning, the
block-diffusion objective and its noise schedule, the bias's
load-balancing update, any auxiliary loss, dropout, packing and the
state's reset at a document's start.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
    "n_groups", "conv_kernel", "chunk_size", "time_step_min",
    "time_step_max", "num_attention_heads", "num_key_value_heads",
    "head_dim", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon",
    "vocab_size")
TOPK_EPS = 1e-20


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `n_routed_experts` is the number HELD; the router's width is
    `router_outputs` (the published count). `pattern` holds the letters of
    the blocks held, `whole_pattern` the model's (its length scales the
    residual branches' last matrices down)."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["pattern"] = config["hybrid_override_pattern"]
    d["whole_pattern"] = config["published"]["hybrid_override_pattern"]
    d["router_outputs"] = config["published"]["n_routed_experts"]
    d["first_expert"] = 0
    d["carry"] = True
    d.update(config.get("builder_args", {}))
    if not d["whole_pattern"].startswith(d["pattern"]):
        d["whole_pattern"] = d["pattern"]       # a toy's own model
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(u):
    return jnp.square(jnp.maximum(u, 0.0))


def recurrence(a, dx, b, c, segment, carry=True):
    """H_t = a_t H_{t-1} + dx_t B_t^T, y_t = H_t C_t, token by token from a
    zero state: a (S, H), dx (S, H, P) (delta x), b, c (S, G, N), head i
    reading group i // (H / G). -> y (S, H, P). The tokens go in segments
    of `segment` (the backward pass recomputes one at a time); without
    `carry` each starts from zero."""
    s, h, p = dx.shape
    g, n = b.shape[1:]
    pad = -s % segment
    if pad:     # tokens that leave the state alone, cut off again below
        a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=1.0)
        dx, b, c = [jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
                    for v in (dx, b, c)]

    def token(state, inp):
        a_t, dx_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(v, h // g, axis=0) for v in (b_t, c_t))
        state = a_t[:, None, None] * state \
            + dx_t[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def one_segment(state, inp):
        if not carry:
            state = jnp.zeros_like(state)
        return lax.scan(token, state, inp)

    _, y = lax.scan(one_segment, jnp.zeros((h, p, n), jnp.float32), tuple(
        v.reshape((-1, segment) + v.shape[1:]) for v in (a, dx, b, c)))
    return y.reshape(-1, h, p)[:s]


def mamba2(x, blobs, d, store=lambda a: a):
    """x (S, hidden) of one sequence, already normalised."""
    w_in, conv_w, conv_b, a_log, d_skip, dt_bias, norm_w, w_out = blobs
    s = x.shape[0]
    h, p, n, g = (d["mamba_num_heads"], d["mamba_head_dim"],
                  d["ssm_state_size"], d["n_groups"])
    k, inner = d["conv_kernel"], h * p
    zxbcdt = store(x @ store(w_in).T)
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * g * n]
    dt = zxbcdt[:, 2 * inner + 2 * g * n:]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = store(jax.nn.silu(conv_b + sum(
        padded[j:j + s] * conv_w[:, j] for j in range(k))))
    xs = xbc[:, :inner].reshape(s, h, p)
    b = xbc[:, inner:inner + g * n].reshape(s, g, n)
    c = xbc[:, inner + g * n:].reshape(s, g, n)
    delta = jax.nn.softplus(dt + dt_bias)                       # (S, H)
    y = recurrence(jnp.exp(-delta * jnp.exp(a_log)), delta[..., None] * xs,
                   b, c, d["chunk_size"], d["carry"])
    y = store(y + d_skip[:, None] * xs)
    gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    gated = gated * lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                              + d["layer_norm_epsilon"])
    return store(gated.reshape(s, inner) * norm_w) @ store(w_out).T


def attention(x, blobs, d, store=lambda a: a, rows=256):
    """x (S, hidden) of one sequence, already normalised; a block of `rows`
    queries at a time against all the keys."""
    wq, wk, wv, wo = blobs
    s = x.shape[0]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    k = store(x @ store(wk).T).reshape(s, hk, dh)
    v = store(x @ store(wv).T).reshape(s, hk, dh)
    rows = math.gcd(s, rows)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(lo):
        xb = lax.dynamic_slice_in_dim(x, lo, rows, 0)
        q = store(xb @ store(wq).T).reshape(rows, hk, h // hk, dh)
        sc = jnp.einsum("qjgd,kjd->jgqk", q, k) / math.sqrt(dh)
        seen = pos[None, :] <= (lo + jnp.arange(rows))[:, None]
        mix = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("jgqk,kjd->qjgd", mix, v).reshape(rows, h * dh)
        return store(o) @ store(wo).T

    return lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)


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
    `first_expert + held - 1` of the router's outputs; the shared expert
    sees every token."""
    router, w_up, w_down, ws_up, ws_down, bias = blobs
    idx, top = route(g, router, bias, d)

    # the sum is the loop's carry and no input of the checkpointed part
    @jax.checkpoint
    def expert(e, up, down):
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        return weight[:, None] * (relu2(g @ store(up).T) @ store(down).T)

    def one(y, inp):
        return y + expert(*inp), None

    held = d["first_expert"] + jnp.arange(w_up.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(g), (held, w_up, w_down))
    return routed + store(relu2(g @ store(ws_up).T)) @ store(ws_down).T


MIXERS = {"M": mamba2, "*": attention, "E": moe}


def forward_loss(params, tokens, labels, d, quant=None):
    """Over the rows of `tokens` (rows, S): the SUM over the tokens of the
    cross-entropy."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)

    def block(letter, x, p):
        ln, mixer = p
        h = store(rms_norm(x, ln[0], d["layer_norm_epsilon"]))
        return store(x + store(MIXERS[letter](h, mixer, d, store)))

    def sequence(toks, labs):
        x = store(store(params["tok_embed"][0])[toks])
        for i, letter in enumerate(d["pattern"]):
            p = [params[f"block{i}/{n}"] for n in ("ln", "mixer")]
            x = jax.checkpoint(functools.partial(block, letter))(x, p)
        x = store(rms_norm(x, params["ln_f"][0], d["layer_norm_epsilon"]))
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

def inverse_softplus(y):
    return y + math.log(-math.expm1(-y))


def layer_specs(d):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order. Matrices gaussian(0.02), each residual branch's last
    one (W_out, W_o, the experts' down projections) divided by the square
    root of the WHOLE model's depth; the embedding gaussian(1); the conv's
    taps and bias uniform(+-1/sqrt(taps)); A_log uniform(0, log 16);
    dt_bias uniform between the inverse softplus of the two time steps; D
    and the norms 1; the route's bias 0 with no rate and no decay."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    last = ("gaussian", 0.02 / math.sqrt(len(d["whole_pattern"])))
    one_, nodecay = ("constant", 1.0), (1.0, 0.0)
    h, p, n, g = (d["mamba_num_heads"], d["mamba_head_dim"],
                  d["ssm_state_size"], d["n_groups"])
    inner, k = h * p, d["conv_kernel"]
    conv_dim = inner + 2 * g * n
    taps = ("uniform", -1.0 / math.sqrt(k), 1.0 / math.sqrt(k))
    mixers = {
        "M": [((inner + conv_dim + h, e), mat, keep),
              ((conv_dim, k), taps, keep), ((conv_dim,), taps, nodecay),
              ((h,), ("uniform", 0.0, math.log(16.0)), nodecay),
              ((h,), one_, nodecay),
              ((h,), ("uniform", inverse_softplus(d["time_step_min"]),
                      inverse_softplus(d["time_step_max"])), nodecay),
              ((inner,), one_, nodecay), ((e, inner), last, keep)],
    }
    hq, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                  d["head_dim"])
    mixers["*"] = [((hq * dh, e), mat, keep), ((hk * dh, e), mat, keep),
                   ((hk * dh, e), mat, keep), ((e, hq * dh), last, keep)]
    held, f = d["n_routed_experts"], d["moe_intermediate_size"]
    fs = d["moe_shared_expert_intermediate_size"]
    mixers["E"] = [((d["router_outputs"], e), mat, keep),
                   ((held, f, e), mat, keep), ((held, e, f), last, keep),
                   ((fs, e), mat, keep), ((e, fs), last, keep),
                   ((d["router_outputs"],), ("constant", 0.0), (0.0, 0.0))]
    specs = [("tok_embed", [((d["vocab_size"], e), ("gaussian", 1.0),
                             keep)])]
    for i, letter in enumerate(d["pattern"]):
        specs += [(f"block{i}/ln", [((e,), one_, nodecay)]),
                  (f"block{i}/mixer", mixers[letter])]
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
        # update goes layer by layer: 528M parameters in float32 are 2.1 GB
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
