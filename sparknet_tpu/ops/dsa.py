"""Attention over a key set that a learned index picks: the plain form.

The lightning indexer of DeepSeek-V3.2's sparse attention, as a layer of
its own mathematics beside ops/attention.py (core jax only: the pallas
form is ops/pallas_dsa.py, imported in the branch that calls it). With
qI (B, HI, S, DI) the index queries, kI (B, S, DI) the ONE index key a
token and w (B, HI, S) float32 the index heads' weights of a query:

  I[t, s]  = sum_j w[t, j] * relu(qI[t, j] . kI[s])            s <= t
  S_t      = the keys s <= t with I[t, s] >= the `topk`-th largest of
             I[t, :t+1]; every key s <= t while t < topk. Ties AT the
             threshold are all kept (a set of more than `topk`), so that
             the set is a function of the scores alone and not of an order
  o[t, h]  = softmax over s in S_t of (q[t, h] . k[s] / sqrt(D)) v[s]
  L_I      = mean_t KL(p_t || softmax_{s in S_t} I[t, s]),
             p_t[s] = stop_gradient(mean over the heads of the main
             softmax's probabilities), which sums to 1 over S_t

The selection has no gradient, so nothing of the main loss reaches qI, kI
or w through `o`, and `p_t` is a constant of L_I, so nothing of L_I reaches
q, k or v: the two losses train disjoint blobs (the layer hands the indexer
stop_gradient of its input besides).
"""

import jax
import jax.numpy as jnp


def index_scores(qI, kI, w):
    """I (B, S, S) float32, [t, s], every pair (the caller masks)."""
    x = jnp.einsum("bjtd,bsd->bjts", qI.astype(jnp.float32),
                   kI.astype(jnp.float32))
    return jnp.sum(w.astype(jnp.float32)[..., None] * jax.nn.relu(x), axis=1)


def threshold(scores, topk):
    """(B, S) float32: the `topk`-th largest of a query's causal scores,
    -inf while the query has no more than `topk` keys."""
    s = scores.shape[-1]
    if topk >= s:
        return jnp.full(scores.shape[:-1], -jnp.inf, jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    kth = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)[0][..., -1]
    return jnp.where(jnp.arange(s) < topk, -jnp.inf, kth)


def selected(scores, topk):
    """The key sets as a mask (B, S, S), [t, s]."""
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    return causal & (scores >= threshold(scores, topk)[..., None])


def sparse_attention_plain(q, k, v, qI, kI, w, topk, mask=None):
    """-> (o (B, H, S, D), L_I ()), every score matrix whole: small sizes,
    the CPU, and what the kernels are tested against. q (B, H, S, D), k and
    v (B, Hkv, S, D). `mask` (B, S, S) stands in for the selection (the
    tests' window and dense forms)."""
    b, h, s, d = q.shape
    grp = h // k.shape[1]
    scores = index_scores(qI, kI, w)
    sel = selected(jax.lax.stop_gradient(scores), topk) if mask is None \
        else mask
    kr, vr = (jnp.repeat(a, grp, axis=1) for a in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) / (d ** 0.5),
                    kr.astype(jnp.float32))
    p = jax.nn.softmax(jnp.where(sel[:, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    target = jax.lax.stop_gradient(jnp.mean(p, axis=1))         # (B, S, S)
    logq = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    live = sel & (target > 0)
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - jnp.where(live, logq, 0.0)), 0.0)
    return o.astype(q.dtype), jnp.sum(kl) / (b * s)


def selection_stats(qI, kI, w, topk, rows=512):
    """[share of the selected keys among a query's last `topk` positions
    (what a sliding window of `topk` would have caught), mean keys a
    query], float32 (2,), a block of `rows` queries at a time. A
    diagnostic: its scores are XLA's, so a key at the threshold may fall
    the other way than in the kernels."""
    b, hi, s, di = qI.shape
    rows = min(rows, s)
    while s % rows:
        rows -= 1
    pos = jnp.arange(s)

    def block(lo):
        t = lo + jnp.arange(rows)
        sc = index_scores(jax.lax.dynamic_slice_in_dim(qI, lo, rows, 2), kI,
                          jax.lax.dynamic_slice_in_dim(w, lo, rows, 2))
        seen = pos[None, :] <= t[:, None]
        sc = jnp.where(seen, sc, -jnp.inf)
        kth = jax.lax.top_k(sc, min(topk, s))[0][..., -1]
        thr = jnp.where(t < topk, -jnp.inf, kth)
        sel = seen & (sc >= thr[..., None])
        near = t[:, None] - pos[None, :] < topk
        return jnp.stack([jnp.sum(sel & near), jnp.sum(sel)]).astype(
            jnp.float32)
    inside, total = jnp.sum(jax.lax.map(block, jnp.arange(0, s, rows)), 0)
    return jax.lax.stop_gradient(jnp.stack([inside / total,
                                            total / (b * s)]))
