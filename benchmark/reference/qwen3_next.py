"""Plain float32 reference of Qwen3-Next (`model_type` qwen3_next), as one
chip's share of an expert-parallel group holds it: nothing of the program,
`jax.numpy` only, every layer by its published equation.

x in R^hidden per token, every projection without bias:

  block i   h = x + Mixer_i(RMSNorm(x));  out = h + MoE(RMSNorm(h));
            Mixer_i is gated attention when (i + 1) % full_attention_interval
            == 0, Gated DeltaNet otherwise
  RMSNorm   y = x / sqrt(mean(x^2) + eps) * (1 + w)      (zero-centred w)
  attention [q | gate] = W_q x per head, k = W_k x, v = W_v x; q, k <-
            RMSNorm over the head; rotary embedding (rotate-half) on the
            first `rotary` dimensions; each key-value head serves
            heads / kv_heads query heads; causal softmax(q k^T / sqrt(d)) v;
            o <- o * sigmoid(gate); y = W_o o
  DeltaNet  [q, k, v, z] = W_qkvz x, [b, a] = W_ba x; [q, k, v] <-
            SiLU(causal depthwise conv); beta = sigmoid(b); g = -exp(A_log)
            * softplus(a + dt_bias); q, k repeated to the value heads,
            L2-normalised, q scaled by d_k^-0.5; per head, S from zero:
            S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
            o_t = S^T q_t;  then o <- RMSNorm(o) * w * SiLU(z); y = W_out o.
            TOKEN BY TOKEN here: a `lax.scan` over the tokens, checkpointed
            per segment so that no state per token is stored
  MoE       p = softmax(W_r x) over all the router's outputs; the top k,
            weights divided by their sum; routed = sum over the chosen
            experts THAT THIS CHIP HOLDS of p_e W_down,e (SiLU(W_gate,e x) *
            W_up,e x): a loop over the held experts with a mask, nothing
            dropped; shared = sigmoid(w_s . x) W_down (SiLU(W_gate x) *
            W_up x); y = routed + shared
  head      logits over the held rows of the vocabulary, mean cross-entropy
            per token

Left out, as in the program: the multi-token-prediction module, the
router's auxiliary loss, dropout.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "full_attention_interval",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "rms_norm_eps",
    "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "vocab_size")


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `num_experts` is the number HELD; the router's width is
    `router_outputs` (the published `num_experts`)."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["router_outputs"] = config["published"]["num_experts"]
    d["first_expert"] = 0
    d.update(config.get("builder_args", {}))
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps, zero_centered=True):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def rope(x, rotary, theta):
    """Rotate-half rotary embedding on the first `rotary` of the last
    axis's dimensions; x is (S, heads, d), positions 0..S-1."""
    s = x.shape[0]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xr, rest = x[..., :rotary], x[..., rotary:]
    half = rotary // 2
    rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rot * sin, rest], -1)


def gated_attention(x, blobs, d, store=lambda a: a, rows=256):
    """x (S, hidden) of one sequence."""
    wq, wk, wv, wo, qn, kn = blobs
    s = x.shape[0]
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    qg = (x @ store(wq).T).reshape(s, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (x @ store(wk).T).reshape(s, hk, dh)
    v = (x @ store(wv).T).reshape(s, hk, dh)
    rotary = int(dh * d["partial_rotary_factor"])
    q = rope(rms_norm(q, qn, d["rms_norm_eps"]), rotary, d["rope_theta"])
    k = rope(rms_norm(k, kn, d["rms_norm_eps"]), rotary, d["rope_theta"])
    k = jnp.repeat(k, h // hk, axis=1)          # kv head j serves h/hk heads
    v = jnp.repeat(v, h // hk, axis=1)
    rows = math.gcd(s, rows)

    @jax.checkpoint
    def block(lo):
        qb = lax.dynamic_slice_in_dim(q, lo, rows, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        seen = (lo + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        mix = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", mix, v)

    o = lax.map(block, jnp.arange(0, s, rows)).reshape(s, h, dh)
    o = o * jax.nn.sigmoid(gate)
    return o.reshape(s, h * dh) @ store(wo).T


def delta_rule(q, k, v, beta, g, segment=64):
    """The recurrence, token by token. q, k (T, H, dk), v (T, H, dv), beta
    and g (T, H). -> o (T, H, dv). A scan over segments of a scan over
    tokens, the segment checkpointed: one state per segment is stored."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    seg = math.gcd(t, segment)

    def token(s, inp):
        qt, kt, vt, bt, gt = inp
        s = s * jnp.exp(gt)[:, None, None]                  # (H, dk, dv)
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    @jax.checkpoint
    def run(s, inp):
        return lax.scan(token, s, inp)

    parts = [a.reshape((t // seg, seg) + a.shape[1:])
             for a in (q, k, v, beta, g)]
    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32), tuple(parts))
    return o.reshape(t, h, dv)


def causal_conv(x, w):
    """Depthwise causal convolution: y_t = sum_j w[c, j] x_{t-K+1+j}."""
    k = w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(k))


def gated_delta_net(x, blobs, d, store=lambda a: a):
    """x (S, hidden) of one sequence."""
    w_qkvz, w_ba, conv, a_log, dt_bias, norm, w_out = blobs
    s = x.shape[0]
    hk, hv = d["linear_num_key_heads"], d["linear_num_value_heads"]
    dk, dv = d["linear_key_head_dim"], d["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = x @ store(w_qkvz).T
    qkv, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    ba = x @ store(w_ba).T
    b, a = ba[:, :hv], ba[:, hv:]
    qkv = jax.nn.silu(causal_conv(qkv, store(conv)))
    q = qkv[:, :kd].reshape(s, hk, dk)
    k = qkv[:, kd:2 * kd].reshape(s, hk, dk)
    v = qkv[:, 2 * kd:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

    def l2(u):
        return u * lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q), hv // hk, axis=1) * dk ** -0.5
    k = jnp.repeat(l2(k), hv // hk, axis=1)
    o = delta_rule(q, k, v, beta, g)
    o = rms_norm(o, norm, d["rms_norm_eps"], zero_centered=False)
    o = o * jax.nn.silu(z.reshape(s, hv, dv))
    return o.reshape(s, vd) @ store(w_out).T


def route(x, router, d):
    """-> (indices (n, k) into all the router's outputs, weights (n, k))."""
    p = jax.nn.softmax(x @ router.T, axis=-1)
    top, idx = lax.top_k(p, d["num_experts_per_tok"])
    if d["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top


def moe(x, blobs, d, store=lambda a: a):
    """x (n, hidden). The held experts are `first_expert` ..
    `first_expert + num_experts - 1` of the router's outputs."""
    router, wg, wu, wd, sg, su, sd, s_gate = blobs
    idx, top = route(x, router, d)

    @jax.checkpoint
    def one(y, inp):
        e, g, u, dn = inp
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1)     # the mask
        out = (jax.nn.silu(x @ store(g).T) * (x @ store(u).T)) @ store(dn).T
        return y + weight[:, None] * out, None

    held = d["first_expert"] + jnp.arange(wg.shape[0])
    routed, _ = lax.scan(one, jnp.zeros_like(x), (held, wg, wu, wd))
    shared = (jax.nn.silu(x @ store(sg).T) * (x @ store(su).T)) @ store(sd).T
    return routed + jax.nn.sigmoid(x @ s_gate.T) * shared


def is_attention(i, d):
    return (i + 1) % d["full_attention_interval"] == 0


def forward_loss(params, tokens, labels, d, quant=None):
    """SUM over the tokens of `tokens` (rows, S) of the cross-entropy."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)

    def block(i, x, p):
        mixer = gated_attention if is_attention(i, d) else gated_delta_net
        ln1, mix, ln2, ffn = p
        h = store(x + store(mixer(
            store(rms_norm(x, ln1[0], d["rms_norm_eps"])), mix, d, store)))
        return store(h + store(moe(
            store(rms_norm(h, ln2[0], d["rms_norm_eps"])), ffn, d, store)))

    def sequence(toks, labs):
        x = store(store(params["tok_embed"][0])[toks])
        for i in range(d["num_hidden_layers"]):
            p = [params[f"block{i}/{n}"]
                 for n in ("ln1", "mixer", "ln2", "moe")]
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

def layer_specs(d, std=0.02):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", std), (1.0, 1.0)
    zero, one_ = ("constant", 0.0), ("constant", 1.0)
    nodecay = (1.0, 0.0)
    h, hk, dh = (d["num_attention_heads"], d["num_key_value_heads"],
                 d["head_dim"])
    lk, lv = d["linear_num_key_heads"], d["linear_num_value_heads"]
    kd, vd = lk * d["linear_key_head_dim"], lv * d["linear_value_head_dim"]
    held, f = d["num_experts"], d["moe_intermediate_size"]
    fs = d["shared_expert_intermediate_size"]
    attn = [((h * 2 * dh, e), mat, keep), ((hk * dh, e), mat, keep),
            ((hk * dh, e), mat, keep), ((e, h * dh), mat, keep),
            ((dh,), zero, nodecay), ((dh,), zero, nodecay)]
    gdn = [((2 * kd + 2 * vd, e), mat, keep), ((2 * lv, e), mat, keep),
           ((2 * kd + vd, d["linear_conv_kernel_dim"]), mat, keep),
           ((lv,), ("uniform", 0.0, math.log(16.0)), nodecay),
           ((lv,), one_, nodecay),
           ((d["linear_value_head_dim"],), one_, nodecay),
           ((e, vd), mat, keep)]
    ffn = [((d["router_outputs"], e), mat, keep),
           ((held, f, e), mat, keep), ((held, f, e), mat, keep),
           ((held, e, f), mat, keep),
           ((fs, e), mat, keep), ((fs, e), mat, keep), ((e, fs), mat, keep),
           ((1, e), mat, keep)]
    specs = [("tok_embed", [((d["vocab_size"], e), mat, keep)])]
    for i in range(d["num_hidden_layers"]):
        specs += [(f"block{i}/ln1", [((e,), zero, nodecay)]),
                  (f"block{i}/mixer", attn if is_attention(i, d) else gdn),
                  (f"block{i}/ln2", [((e,), zero, nodecay)]),
                  (f"block{i}/moe", ffn)]
    specs += [("ln_f", [((e,), zero, nodecay)]),
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
        # update goes layer by layer: 424M parameters in float32 are 1.7 GB
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
