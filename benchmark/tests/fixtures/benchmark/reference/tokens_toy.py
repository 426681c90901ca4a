"""Plain float32 reference of the fixture's token model, a file of its own
with `build`: nothing of `plain`'s layer list, only its rounding and its
solver update. Token embedding with bias; one causal multi-head
self-attention layer (fused qkv projection, scores over sqrt(head size),
output projection) added to its input; a head over the vocabulary; the
mean over all tokens of the cross-entropy."""

import math

import jax
import jax.numpy as jnp

from . import plain


def forward_loss(params, tokens, labels, heads, quant):
    """SUM over the block's tokens of the cross-entropy."""
    def store(x):
        return x if quant is None else plain.fake_quant(x, quant)
    emb, emb_b = params["tok_embed"]
    wqkv, bqkv, wo, bo = params["attn"]
    head, head_b = params["lm_head"]
    x = store(store(emb)[tokens] + emb_b)                   # (B, S, E)
    b, s, e = x.shape
    qkv = (x @ store(wqkv).T + bqkv).reshape(b, s, 3, heads, e // heads)
    q, k, v = [jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3)]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(e // heads)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    mix = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.moveaxis(jnp.einsum("bhqk,bhkd->bhqd", mix, v), 2, 1)
    attn = store(o.reshape(b, s, e) @ store(wo).T + bo)
    logits = store(store(x + attn) @ store(head).T + head_b)
    picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                 labels[..., None], axis=-1)
    return -jnp.sum(picked)


class Reference:
    def __init__(self, config, batch):
        a = config["builder_args"]
        v, s, e = a["vocab_size"], a["seq_len"], a["d_model"]
        self.heads, self.batch, self.seq = a["num_heads"], batch, s
        one, zero = (1.0, 1.0), ("constant", 0.0)
        self.specs = [
            ("tok_embed", [((v, e), ("uniform", -0.1, 0.1), one),
                           ((e,), zero, one)]),
            ("attn", [((3 * e, e), ("xavier",), one), ((3 * e,), zero, one),
                      ((e, e), ("xavier",), one), ((e,), zero, one)]),
            ("lm_head", [((v, e), ("gaussian", 0.05), one),
                         ((v,), zero, (2.0, 0.0))]),
        ]
        self.inputs = [("data", (batch, s), "int32"),
                       ("label", (batch, s), "int32")]

    def make_step(self, solver, block_rows=None, quant=None, masters=None):
        n, rows = self.batch, block_rows or self.batch
        update = plain.make_update(
            solver, {name: [b[2] for b in blobs]
                     for name, blobs in self.specs}, masters)

        @jax.jit
        def block_grad(params, tokens, labels):
            return jax.value_and_grad(lambda p: forward_loss(
                p, tokens, labels, self.heads, quant) / (n * self.seq))(
                    params)

        def step(params, history, data, labels, key):
            loss, grads = 0.0, None
            for lo in range(0, n, rows):
                l, g = block_grad(params, data[lo:lo + rows],
                                  labels[lo:lo + rows])
                loss = loss + l
                grads = g if grads is None else \
                    jax.tree_util.tree_map(jnp.add, grads, g)
            params, history = update(params, history, grads)
            return params, history, loss, grads
        return step


def build(config, batch):
    return Reference(config, batch)
