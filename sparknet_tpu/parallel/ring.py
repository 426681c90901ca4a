"""Long-context sequence/context parallelism.

The reference is a 2015 CNN framework — no attention, no sequence axis
(SURVEY.md section 5, "long-context: absent entirely"). sparknet_tpu treats
long context as first-class: sequences shard across a "seq" mesh axis and
attention runs without ever materializing the full sequence on one chip.

Two interchangeable strategies (jax-native; see PAPERS.md for the source
techniques — Ring Attention with blockwise transformers, and
DeepSpeed-Ulysses all-to-all):

  ring_attention     K/V blocks rotate around the ring via ppermute while a
                     numerically-stable running softmax (the flash-attention
                     recurrence m/l/o) accumulates per Q block. Comm is
                     point-to-point neighbor traffic — rides ICI perfectly —
                     and overlaps with each block's compute.
  ulysses_attention  two all_to_alls reshard (seq-sharded, heads-full) ->
                     (seq-full, heads-sharded) around a plain attention; best
                     when num_heads % axis_size == 0 and the sequence fits
                     once resharded.

Both are exact (bitwise-modulo-reduction-order) equivalents of full
attention, verified against the dense reference in tests/test_parallel.py.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from .compat import shard_map


def _stable_block_update(o, m, l, s, v):
    """One flash-attention accumulation step.
    o: (..., Sq, D) running unnormalized output
    m: (..., Sq)    running max
    l: (..., Sq)    running denominator
    s: (..., Sq, Sk) raw scores for this K/V block
    v: (..., Sk, D)
    """
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # renormalize history; exp(-inf - -inf) guarded to 0
    alpha = jnp.exp(jnp.where(m == -jnp.inf, -jnp.inf, m - m_new))
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Exact attention over a sequence sharded on ``axis_name``.

    q, k, v: (B, H, S_local, D) — the local sequence shard. Must be called
    inside shard_map/pmap providing ``axis_name``. Returns (B, H, S_local, D).
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[-2]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qf = (q * scale).astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my * s_local + jnp.arange(s_local)

    def body(carry, step):
        o, m, l, k_cur, v_cur = carry
        # whose block do we currently hold? blocks rotate +1 each step,
        # so at step t we hold the block originally on rank (my - t) mod n
        src = (my - step) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        o, m, l = _stable_block_update(o, m, l, s, v_cur.astype(jnp.float32))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k, v), jnp.arange(n, dtype=jnp.int32))
    # fully-masked rows (can't happen with causal self-attn, but be safe)
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """All-to-all sequence parallelism (Ulysses): reshard so each device
    holds ALL positions for H/n heads, run plain attention, reshard back.

    q, k, v: (B, H, S_local, D); requires H % axis_size == 0."""
    n = jax.lax.psum(1, axis_name)
    h = q.shape[1]

    def seq_to_head(x):
        # (B, H, S/n, D) -> (B, H/n, S, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def head_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    out = dense_attention(qh, kh, vh, causal=causal, scale=scale)
    del h, n
    return head_to_seq(out)


def dense_attention(q, k, v, causal=False, scale=None, window=0):
    """Plain full attention (B, H, S, D) — the single-device reference.
    `window` (with causal): query i sees keys i - window < j <= i."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", (q * scale).astype(jnp.float32),
                   k.astype(jnp.float32))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        ahead = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        mask = (ahead >= 0) & (ahead < window) if window else ahead >= 0
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ring_attention_comm_bytes(block_shape, n, itemsize=4):
    """Per-chip bytes one ring_attention forward moves over the seq axis:
    the K and V blocks (each ``block_shape``, the local shard) are
    ppermuted ``n`` times around the ring (the final rotation returns
    blocks home; XLA may elide it, so this is a slight upper bound).
    Used by the obs comms meter — the traffic itself runs inside the
    compiled step and can't be counted from the host."""
    total = 1
    for d in block_shape:
        total *= int(d)
    return int(2 * int(n) * total * itemsize)


def sequence_sharded_apply(fn, mesh, seq_axis="seq", batch_args=(),
                           seq_dim=1):
    """Wrap ``fn(*arrays)`` so its array args are sharded along ``seq_dim``
    over ``seq_axis`` and fn runs under shard_map with the seq axis
    published in the parallelism context (ops.attention picks it up)."""
    from . import context

    spec = [None] * (seq_dim + 1)
    spec[seq_dim] = seq_axis
    sp = P(*spec)

    @functools.wraps(fn)
    def wrapped(*args):
        with context.axis_context(seq=seq_axis):
            inner = shard_map(fn, mesh=mesh,
                              in_specs=tuple(sp for _ in args),
                              out_specs=sp, check_vma=False)
            return inner(*args)

    return wrapped
