"""Plain float32 reference of GLM-4.7-Flash (`model_type` glm4_moe_lite), as
one chip's share of an expert-parallel group holds it: nothing of the
program, `jax.numpy` only, every layer by its equation. No bias anywhere,
plain RMSNorm y = x / rms(x) * w with w filled with 1.

x in R^hidden per token, block l of a sequence:

  y   = x + Attn(RMSNorm_1(x))
  out = y + FF_l(RMSNorm_2(y))
  Attn — multi-head latent attention, as a training step runs it (keys and
        values expanded from their latent; the absorbed form is decode's):
        c_q = RMSNorm_q(W_qa h)                       the query latent
        q   = W_qb c_q, H heads of [q_nope (Dn) | q_pe (Dr)]
        [c_kv (Rkv) | k_pe (Dr)] = W_kva h;  c_kv = RMSNorm_kv(c_kv)
        [k_nope (Dn) | v (Dv)] a head = W_kvb c_kv
        rotate-half rotary on q_pe of every head and on k_pe, all Dr
        dimensions, positions 0..S-1; k_pe is ONE vector a token, which
        every head's key takes: k = [k_nope | k_pe]
        o = softmax(q k^T / sqrt(Dn + Dr) + causal mask) v, a block of
        query rows at a time; out = W_o o over the H heads of Dv
  FF_l, l < first_k_dense_replace: W_2 (silu(W_1 g) * W_3 g)
  FF_l otherwise — the MoE: s = sigmoid(W_r g) over all the router's
        outputs; the k chosen are the largest of s + b, b the correction
        bias (a buffer: no gradient trains it); their weights the UNBIASED
        s_e divided by (their sum + 1e-20) (norm_topk_prob), times
        routed_scaling_factor; FF = sum over the chosen experts THAT THIS
        CHIP HOLDS of w_e W_2,e (silu(W_1,e g) * W_3,e g): a loop over the
        held experts with a mask, nothing dropped; plus one shared expert
        of the same form at n_shared_experts x the experts' width, of
        every token, added with no gate
  head  logits = W_head RMSNorm_f(x_L) over the held rows of the
        vocabulary (untied), mean cross-entropy per token
  the multi-token-prediction module (num_nextn_predict_layers 1),
        DeepSeek-V3's form: for position i
        h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(x_L,i)]
        with the MAIN model's table (t_{i+1} is the feed's label), one more
        block of the MoE kind over h', a final RMSNorm of its own, the MAIN
        model's head, cross-entropy against t_{i+2}: the labels moved one
        place left, the last position carrying no loss, the mean over the
        S - 1 that do; the step's loss is L + mtp_loss_weight L_mtp

Assumed (the configuration file lists the same): rotate-half rotary — the
interleaved pairs of DeepSeek's code are the same function under a fixed
permutation of the rotary rows of W_qb and W_kva; the order [embedding ;
hidden] of W_eh's input; mtp_loss_weight 0.3; the 1e-20. Left out, as in
the program: the bias's load-balancing update, any auxiliary loss,
dropout, packing.

`shared_rope_key` and `kv_latent_norm` false are CONTROLS
(benchmark/control_latent.py), never the model: k_pe set to zero (a key
without its shared rotary part), and c_kv used as W_kva gives it.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import plain

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rms_norm_eps", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor", "num_nextn_predict_layers", "vocab_size")
TOPK_EPS = 1e-20


def dims(config):
    """The sizes a run uses: the configuration file's published keys,
    `builder_args` (the sequence length; a rehearsal's toy sizes) laid over
    them. `n_routed_experts` is the number HELD; the router's width is
    `router_outputs` (the published count)."""
    d = {k: config[k] for k in MODEL_KEYS}
    d["router_outputs"] = config["published"]["n_routed_experts"]
    d["first_expert"] = 0
    d["mtp_loss_weight"] = config.get("mtp_loss_weight", 0.3)
    d["shared_rope_key"] = d["kv_latent_norm"] = True
    d.update(config.get("builder_args", {}))
    return d


# ------------------------------------------------------------------ layers

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta, first=0):
    """Rotate-half rotary embedding on the whole last axis; x is
    (S, heads, d), positions `first` .. `first` + S - 1."""
    s, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (first + jnp.arange(s)).astype(jnp.float32)[:, None] \
        * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + rot * sin


def attention(x, blobs, d, store=lambda a: a, rows=256):
    """x (S, hidden) of one sequence, already normalised; a block of `rows`
    queries at a time (their latent, heads and rotary made in the block)
    against all the keys."""
    w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o = blobs
    s = x.shape[0]
    h, dn, dr, dv, rkv = (d["num_attention_heads"], d["qk_nope_head_dim"],
                          d["qk_rope_head_dim"], d["v_head_dim"],
                          d["kv_lora_rank"])
    latent = store(x @ store(w_kva).T)
    c_kv, k_pe = latent[:, :rkv], latent[:, None, rkv:]     # (S, 1, Dr)
    if d.get("kv_latent_norm", True):
        c_kv = store(rms_norm(c_kv, kv_norm, d["rms_norm_eps"]))
    k_pe = rope(k_pe, d["rope_theta"]) if d.get("shared_rope_key", True) \
        else jnp.zeros_like(k_pe)
    kv = store(c_kv @ store(w_kvb).T).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (s, h, dr))],
                        -1)
    v = kv[..., dn:]
    rows = math.gcd(s, rows)

    @jax.checkpoint
    def block(lo):
        xb = lax.dynamic_slice_in_dim(x, lo, rows, 0)
        c_q = store(rms_norm(store(xb @ store(w_qa).T), q_norm,
                             d["rms_norm_eps"]))
        q = store(c_q @ store(w_qb).T).reshape(rows, h, dn + dr)
        qb = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], d["rope_theta"], first=lo)], -1)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dn + dr)
        seen = jnp.arange(s)[None, :] <= (lo + jnp.arange(rows))[:, None]
        mix = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", mix, v).reshape(rows, h * dv)
        return store(o) @ store(w_o).T

    return lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)


def gated_ff(g, w1, w3, w2, store=lambda a: a):
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
    `first_expert + held - 1` of the router's outputs; the shared expert
    sees every token."""
    router, w1, w3, w2, ws1, ws3, ws2, bias = blobs
    idx, top = route(g, router, bias, d)

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
    """Over the rows of `tokens` (rows, S): the SUM over the tokens of the
    cross-entropy, the prediction module's (where there is one) scaled so
    that the sum over S tokens stands for its mean over S - 1."""
    def store(a):
        return a if quant is None else plain.fake_quant(a, quant)
    eps = d["rms_norm_eps"]

    def block(dense, x, p):
        ln1, attn, ln2, *ff = p
        h = store(rms_norm(x, ln1[0], eps))
        y = store(x + store(attention(h, attn, d, store)))
        g = store(rms_norm(y, ln2[0], eps))
        out = gated_ff(g, *[b[0] for b in ff], store) if dense \
            else moe(g, ff[0], d, store)
        return store(y + store(out))

    def run_block(prefix, dense, x):
        p = [params[prefix + n]
             for n in ("ln1", "attn", "ln2") + (FF_DENSE if dense
                                                else ("moe",))]
        return jax.checkpoint(functools.partial(block, dense))(x, p)

    def sequence(toks, labs):
        table = store(params["tok_embed"][0])
        head = store(params["lm_head"][0])
        x = store(table[toks])
        for i in range(d["num_hidden_layers"]):
            x = run_block(f"block{i}/", i < d["first_k_dense_replace"], x)
        rows = math.gcd(x.shape[0], 1024)

        @jax.checkpoint
        def picked(inp):            # the logits a block of tokens at a time
            xb, lb, on = inp
            logits = store(xb @ head.T)
            return jnp.sum(on * jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), lb[:, None],
                axis=-1)[:, 0])

        def cross_entropy(x, norm, targets, on):
            x = store(rms_norm(x, params[norm][0], eps))
            return -jnp.sum(lax.map(picked, (
                x.reshape(-1, rows, x.shape[1]), targets.reshape(-1, rows),
                on.reshape(-1, rows))))

        every = jnp.ones(labs.shape, jnp.float32)
        total = cross_entropy(x, "ln_f", labs, every)
        s = labs.shape[0]
        for k in range(1, d["num_nextn_predict_layers"] + 1):
            m = f"mtp{k}_"
            # this depth reads the depth before's labels as its tokens and
            # is scored against them moved one place; the places moved in
            # from past the end carry no loss
            both = jnp.concatenate(
                [store(rms_norm(store(table[labs]), params[m + "ln_e"][0],
                                eps)),
                 store(rms_norm(x, params[m + "ln_h"][0], eps))], -1)
            x = run_block(f"block_mtp{k}/", False,
                          store(both @ store(params[m + "proj"][0]).T))
            labs = jnp.concatenate([labs[1:], labs[:1] * 0])
            on = (jnp.arange(s) < s - k).astype(jnp.float32)
            total = total + d["mtp_loss_weight"] * s / (s - k) \
                * cross_entropy(x, m + "ln_f", labs, on)
        return total

    return sum(sequence(tokens[r], labels[r])
               for r in range(tokens.shape[0]))


# ------------------------------------------------- what the harness reads

def layer_specs(d):
    """[(layer, [(shape, filler, (lr_mult, decay_mult))])] in the
    program's order. Matrices gaussian(0.02); the embedding gaussian(1)
    (the head is untied and the first mixer is attention, whose output all
    tokens share: at 1 a token's own vector carries the residual stream
    from the first step); norm weights 1 without decay; the route's bias 0
    with no rate and no decay. A prediction module owns W_eh, its three
    norms and its block: the table and the head are the main model's and
    stand once."""
    e = d["hidden_size"]
    mat, keep = ("gaussian", 0.02), (1.0, 1.0)
    one_, nodecay = ("constant", 1.0), (1.0, 0.0)
    h, dn, dr, dv = (d["num_attention_heads"], d["qk_nope_head_dim"],
                     d["qk_rope_head_dim"], d["v_head_dim"])
    rq, rkv = d["q_lora_rank"], d["kv_lora_rank"]
    attn = [((rq, e), mat, keep), ((rq,), one_, nodecay),
            ((h * (dn + dr), rq), mat, keep), ((rkv + dr, e), mat, keep),
            ((rkv,), one_, nodecay), ((h * (dn + dv), rkv), mat, keep),
            ((e, h * dv), mat, keep)]
    held, f, i_ = (d["n_routed_experts"], d["moe_intermediate_size"],
                   d["intermediate_size"])
    fs = d["n_shared_experts"] * f
    ffn = [((d["router_outputs"], e), mat, keep),
           ((held, f, e), mat, keep), ((held, f, e), mat, keep),
           ((held, e, f), mat, keep), ((fs, e), mat, keep),
           ((fs, e), mat, keep), ((e, fs), mat, keep),
           ((d["router_outputs"],), ("constant", 0.0), (0.0, 0.0))]
    norm = [((e,), one_, nodecay)]

    def block(prefix, dense):
        specs = [(prefix + "ln1", norm), (prefix + "attn", attn),
                 (prefix + "ln2", norm)]
        if dense:
            return specs + [(prefix + "ff_gate", [((i_, e), mat, keep)]),
                            (prefix + "ff_up", [((i_, e), mat, keep)]),
                            (prefix + "ff_down", [((e, i_), mat, keep)])]
        return specs + [(prefix + "moe", ffn)]

    specs = [("tok_embed", [((d["vocab_size"], e), ("gaussian", 1.0),
                             keep)])]
    for i in range(d["num_hidden_layers"]):
        specs += block(f"block{i}/", i < d["first_k_dense_replace"])
    specs += [("ln_f", norm),
              ("lm_head", [((d["vocab_size"], e), mat, keep)])]
    for k in range(1, d["num_nextn_predict_layers"] + 1):
        m = f"mtp{k}_"
        specs += [(m + "ln_e", norm), (m + "ln_h", norm),
                  (m + "proj", [((e, 2 * e), mat, keep)]),
                  *block(f"block_mtp{k}/", False), (m + "ln_f", norm)]
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
        # update goes layer by layer: 591M parameters in float32 are 2.4 GB
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

        # Adam's moments wait on the HOST between the steps and come to the
        # device a layer at a time: while the second and third gradients
        # are computed the device holds the harness's w0 and first gradient
        # (4.7 GB), the weights and their new gradient (4.7 GB) and this
        # program's temporaries (2.7 GB); the moments' 4.7 GB beside them
        # is 16.9 GB and more, and the program was refused (PERF.md, PR 46)
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
                        {name: jax.device_put(slots.pop(name))},
                        {name: grads.pop(name)})
                new_params[name] = p[name]
                new_slots[name] = jax.device_get(s[name])
            return new_params, (taken_next, new_slots), loss, given
        return step


def build(config, batch):
    return Reference(config, batch)
