"""Pallas flash-attention kernel for TPU — forward AND blockwise backward.

The per-chip complement to parallel.ring: ring attention distributes the
sequence across chips; THIS kernel computes each chip's local attention
without ever materializing the (S, S) score matrix — the flash recurrence
(running max m, denominator l, unnormalized accumulator acc) over K/V
blocks streamed through VMEM, with the MXU doing the two matmuls per block.
K/V arrive in (block_k, D) tiles via a third, sequential grid dimension, so
VMEM usage is O(block) regardless of S.

Training memory is O(block) in VMEM: the forward additionally emits the
per-row logsumexp (LSE), and the backward re-derives each probability
block as P = exp(S - LSE) inside two pallas kernels — dQ with K/V streamed
innermost, dK/dV with Q/dO streamed innermost (the FlashAttention-2
recurrences):

    delta_i = rowsum(dO_i * O_i)        (once a row, outside the kernels)
    P_ij    = exp((scale * Q_i) K_j^T - LSE_i)
    dV_j   += P_ij^T dO_i
    dS_ij   = P_ij * (dO_i V_j^T - delta_i)
    dQ_i   += scale * dS_ij K_j
    dK_j   += dS_ij^T (scale * Q_i)

**A score tile is held transposed**, (block_k, block_q): keys down the
sublanes, queries along the lanes (`_scores`). What belongs to a query —
the running maximum and sum, the logsumexp, delta — is then a (1, block_q)
row: the forward's maximum and sum reduce over sublanes (elementwise over
the tile's vector registers and one short reduction at the end), where a
(block_q, block_k) tile reduces every register across its 128 lanes; the
backward's three products of a tile need no transposed operand; and the
statistics live in HBM as one float32 a query a head, (B*H, S), not
replicated over 128 lanes. The accumulators are transposed with the tile
(O^T and dQ^T, (D, block_q)) and turned once a query block, when the block
is written; V for the forward and K for dQ are handed over transposed,
(D, S), by XLA. (Measured, PR 37, PERF.md section 6: with (block_q,
block_k) tiles the forward cost 2.2 us a 512 x 512 tile whatever the head
size, and the cross-lane sum alone 0.8 us of it.)

In HBM the backward reads q, k, v and two results of the forward: the
output O (the size of q) and the LSE (4 bytes a row a head). Those two go
through `graph/remat.py:keep`: under `--remat full` or `dots` a block
recomputes q, k and v in the backward pass and KEEPS O and the LSE, so the
forward kernel runs once and not again for its own backward. That is
memory plain `jax.checkpoint` did not hold: at 2 x 16,384 tokens and 28
heads of 128 in bfloat16 235 MB + 3.7 MB a layer, live from the layer's
forward to its backward; one `remat.kept` record each in the ring of
obs/trace.py a trace of the forward rule. delta needs the backward's dO:
it is one fused reduction over dO * O where the backward starts.

**What a tile is charged.** The causal mask makes part of each grid dead
and part of it uniform, and a kernel sees which from its block indices:
- a DEAD step (a key block wholly ahead of the query block; under a
  window also the steps of a short band past its diagonal block) runs
  nothing and fetches nothing: its index maps return the block of the
  nearest live step, and a repeated index is not fetched again
  (`_block_maps`). It still costs a grid step, about 0.35 us;
- an INTERIOR tile (`_interior`: its last key not ahead of its first
  query and, under a window, its first key inside the last query's
  window) runs the body without a mask: no iota, compare or select;
- an EDGE tile (the diagonal's, and the ones the window's trailing edge
  crosses: `edge_blocks`, 56 of a head's 252 live blocks at S 16,384 under
  a window of 4,096, 32 of 528 without, 16 of 136 at S 8,192) runs the
  same body with the mask.
`scale` multiplies q's block (once a query block in the forward and dQ),
never a score tile, so forward and backward round their scores alike.
A live 512 x 512 tile costs 1.34 us in the forward, 1.49 in dQ and 1.71
in dK/dV at head 128 on a v5e (its products alone 0.68, 1.02, 1.36 at the
bfloat16 peak; PERF.md section 7 (s3) has the other heads); the mask of an
edge tile is 2-4% of the forward's tile and nothing measurable in the
backward's.

A sliding window (`window` > 0, a trace-time constant, with `causal`): key
j is visible to query i iff i - window < j <= i. The window form's grids
are the BAND's, not the square's: a query block's key axis runs over the
`_key_band` of key blocks that hold a visible key (its first block from the
block index, at most (window + block_q - 2) // block_k + 2 of them), and a
key block's query axis likewise, so the blocks wholly left of the window
are never fetched or visited. Those calls are named `flash_swa_fwd`,
`flash_swa_dq`, `flash_swa_dkv`. With window 0, or a window that covers the
sequence, the kernels are traced as the causal form, without a window
term.

Interpret mode engages on the CPU backend only (the tests); any other
backend compiles the kernel or raises. On a TPU a query block is the lane
dimension of a tile: 128 must divide it unless it is the whole sequence
(`_fit_block` prefers such divisors).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..graph.remat import keep
from .backend import _should_interpret

NEG_INF = -1e30
LANES = 128


def _key_band(qi, bq, bk, window, xp=jnp):
    """(first, last) key block with a key visible to query block `qi`
    under the causal mask: from the first row's oldest key (key 0 without
    a window) to the last row's own. `xp` is numpy for the static extents,
    jax.numpy inside a kernel or an index map."""
    first = xp.maximum(qi * bq - (window - 1), 0) // bk if window else 0 * qi
    return first, (qi * bq + bq - 1) // bk


def _query_band(ki, bq, bk, window, nq, xp=jnp):
    """(first, last) query block that sees a key of key block `ki`: from
    its first key's own row to the last key's youngest reader (the last
    row without a window)."""
    last = xp.minimum((ki * bk + bk + window - 2) // bq, nq - 1) if window \
        else 0 * ki + nq - 1
    return (ki * bk) // bq, last


def band_blocks(s, window, block_q=512, block_k=512):
    """(key blocks the window form visits, key blocks of the causal half),
    summed over the query blocks of one head at sequence length `s`: what
    `attn.path` reports. Without a window the two are equal."""
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, s)
    first, last = _key_band(np.arange(s // bq), bq, bk,
                            _window_of(window, True, s), np)
    return int(np.sum(last - first + 1)), int(np.sum(last + 1))


def _band_extent(s, window, bq, bk):
    """Static extents of the window form's inner grid axes: the most key
    blocks any query block visits, the most query blocks any key block."""
    nq, nk = s // bq, s // bk
    kf, kl = _key_band(np.arange(nq), bq, bk, window, np)
    qf, ql = _query_band(np.arange(nk), bq, bk, window, nq, np)
    return int(np.max(kl - kf)) + 1, int(np.max(ql - qf)) + 1


def edge_blocks(s, window, block_q=512, block_k=512):
    """Of `band_blocks`' live blocks of one head, those the kernels mask:
    the blocks that are not `_interior` (the diagonal's, and with a window
    the blocks its trailing edge crosses). `attn.path` reports it as
    `masked_blocks`."""
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, s)
    window = _window_of(window, True, s)
    qi, kj = np.arange(s // bq)[:, None], np.arange(s // bk)[None, :]
    first, last = _key_band(qi, bq, bk, window, np)
    live = (kj >= first) & (kj <= last)
    return int(np.sum(live & ~_interior(qi, kj, bq, bk, window, np)))


def _interior(qi, kj, bq, bk, window, xp=jnp):
    """Whether every key of key block kj is visible to every query of query
    block qi, from the block indices alone: its last key is not ahead of
    the first query and, under a window, its first key is inside the last
    query's. Such a tile needs no mask."""
    inside = kj * bk + bk - 1 <= qi * bq
    if window:
        inside = xp.logical_and(inside, qi * bq + bq - 1 - kj * bk < window)
    return inside


def _scores(k_ref, qs, qi, kj, window, masked):
    """The (bk, bq) score tile of key block kj against the scaled query
    block `qs`, TRANSPOSED: keys down the sublanes, queries along the
    lanes, so a query's statistics (its maximum, sum, logsumexp, delta)
    are (1, bq) rows and reduce over sublanes. An edge tile (`masked`)
    hides the keys ahead of the query and, under a window, those `window`
    or more back."""
    bk, bq = k_ref.shape[1], qs.shape[0]
    sc = jax.lax.dot_general(k_ref[0].astype(jnp.float32), qs,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if masked:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        seen = q_pos >= k_pos
        if window:
            seen &= q_pos - k_pos < window
        sc = jnp.where(seen, sc, NEG_INF)
    return sc


def _live_tiles(step, causal, live, inside):
    """Run `step(masked)` where the tile is live: without the mask where
    it is `inside` the visible region, with it on an edge tile. Without a
    causal mask every tile is live and inside."""
    if not causal:
        return step(False)

    @pl.when(live)
    def _():
        pl.when(inside)(lambda: step(False))
        pl.when(jnp.logical_not(inside))(lambda: step(True))


def _key_step(qi, ki, bq, bk, causal, window):
    """(key block, live, interior) of step ki of query block qi's key
    axis. Under a window the axis is the band's: step ki is key block
    first + ki, and the steps past the diagonal block (short bands at the
    start of the sequence) are dead; in the causal form the steps above
    the diagonal are. Both fetch nothing: the index maps hold the last live
    block there."""
    if not causal:
        return ki, None, None
    first, last = _key_band(qi, bq, bk, window)
    kj = first + ki
    return kj, kj <= last, _interior(qi, kj, bq, bk, window)


def _fa_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
               qs_ref, *, causal, scale, window=0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # scaled once a query block, not once a key step
        qs_ref[:] = q_ref[0].astype(jnp.float32) * scale

    kj, live, inside = _key_step(qi, ki, bq, bk, causal, window)

    def step(masked):
        sc = _scores(k_ref, qs_ref[:], qi, kj, window, masked)  # (bk, bq)
        m_prev = m_ref[:]                                       # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
        # O^T += V^T P^T, (d, bq)
        pv = jax.lax.dot_general(vt_ref[0].astype(jnp.float32), p,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    _live_tiles(step, causal, live, inside)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).T.astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _fit_block(block, s):
    """Largest divisor of the sequence <= the requested block, preferring
    lane-aligned (multiple-of-128) divisors, then sublane-aligned
    (multiple-of-8) ones: a query block is the lane dimension of a score
    tile, and on a TPU a block narrower than the sequence must be whole
    lane rows. The grid's K/V dimension is sequential, so a collapsed
    block size pays dispatch latency per tile (the 20x in
    flash_attention's docstring)."""
    block = min(block, s)
    if s % block == 0:
        return block
    divisors = [d for d in range(block, 0, -1) if s % d == 0]
    for align in (LANES, 8):
        for d in divisors:
            if d % align == 0:
                return d
    if divisors[0] < 8 and s > 64:
        # e.g. prime S: the only divisors are 1/S — a 1-row block means
        # S^2 sequential kernel dispatches (near-hang), worse than failing
        raise ValueError(
            f"sequence {s} has no usable flash block divisor "
            f"<= {block}; pad the sequence to a multiple of 128")
    return divisors[0]


def _group(q, k):
    """Query heads per key-value head: q is (B, H, S, D), k and v
    (B, Hkv, S, D) with H a multiple of Hkv; query head h of a batch row
    reads key-value head h // (H / Hkv), so with heads flattened
    batch-major, row bh of q reads row bh // group of k and v."""
    h, hkv = q.shape[1], k.shape[1]
    if h % hkv:
        raise ValueError(f"flash attention: {h} query heads over {hkv} "
                         "key-value heads")
    return h // hkv


def _window_of(window, causal, s):
    """The window the kernels are traced with: 0 (the causal form, no
    window term) when none is asked or it covers the sequence."""
    if window and not causal:
        raise ValueError("flash attention: a window needs causal=True")
    return window if 0 < window < s else 0


def _block_maps(grp, bq, bk, causal, window):
    """Index maps of the (query head, query block, key step) grids: `rows`
    a query block's (1, bq, d) block, `cols` a (1, bk, d) block of K or V,
    `cols_t` the same block of a transposed (d, S) operand, `stats` a
    query block's (1, 1, bq) row of a per-query statistic. Query head bh
    reads key-value head bh // grp in place, and a dead step (`_key_step`)
    holds the index of the last live block, so nothing is fetched for
    it."""
    def rows(bh, i, j):
        return (bh, i, 0)

    def stats(bh, i, j):
        return (bh, 0, i)

    def cols(bh, i, j):
        if causal:
            first, last = _key_band(i, bq, bk, window)
            j = jnp.minimum(first + j, last)
        return (bh // grp, j, 0)

    def cols_t(bh, i, j):
        head, j, _ = cols(bh, i, j)
        return (head, 0, j)
    return rows, cols, cols_t, stats


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=0):
    """-> (o (B, H, S, D), the logsumexp (B*H, S) float32)."""
    b, h, s, d = q.shape
    grp = _group(q, k)
    block_q = _fit_block(block_q, s)
    block_k = _fit_block(block_k, s)
    window = _window_of(window, causal, s)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h // grp, s, d)
    vt = jnp.swapaxes(v.reshape(b * h // grp, s, d), 1, 2)
    rows, cols, cols_t, stats = _block_maps(grp, block_q, block_k, causal,
                                            window)
    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, scale=scale,
                          window=window),
        grid=(b * h, s // block_q,
              _band_extent(s, window, block_q, block_k)[0]),
        in_specs=[pl.BlockSpec((1, block_q, d), rows),
                  pl.BlockSpec((1, block_k, d), cols),
                  pl.BlockSpec((1, d, block_k), cols_t)],
        out_specs=[pl.BlockSpec((1, block_q, d), rows),
                   pl.BlockSpec((1, 1, block_q), stats)],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((d, block_q), jnp.float32),       # O^T unnormalized
            pltpu.VMEM((1, block_q), jnp.float32),       # m
            pltpu.VMEM((1, block_q), jnp.float32),       # l
            pltpu.VMEM((block_q, d), jnp.float32),       # q * scale
        ],
        interpret=interpret,
        name="flash_swa_fwd" if window else "flash_fwd",
    )(qf, kf, vt)
    return out.reshape(b, h, s, d), lse[:, 0]


def _dscores(k_ref, v_ref, do_ref, lse_ref, delta_ref, qs, qi, kj, window,
             masked):
    """dS^T and P^T, (bk, bq), of one tile: P = exp(S - LSE) re-derived,
    dS = P * (dO V^T - delta)."""
    p = jnp.exp(_scores(k_ref, qs, qi, kj, window, masked) - lse_ref[0])
    dp = jax.lax.dot_general(v_ref[0].astype(jnp.float32),
                             do_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p * (dp - delta_ref[0]), p


def _dq_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, qs_ref, *, causal, scale, window=0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # the forward's scores: q scaled, once a query block
        qs_ref[:] = q_ref[0].astype(jnp.float32) * scale

    kj, live, inside = _key_step(qi, ki, bq, bk, causal, window)

    def step(masked):
        ds, _ = _dscores(k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         qs_ref[:], qi, kj, window, masked)
        # dQ^T += K^T dS^T, (d, bq)
        acc_ref[:] += jax.lax.dot_general(
            kt_ref[0].astype(jnp.float32), ds, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _live_tiles(step, causal, live, inside)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (acc_ref[:] * scale).T.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, causal, scale, nq, window=0,
                band=0):
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    ki = pl.program_id(1)       # note: grid is (kv head, j, group x i) here
    t = pl.program_id(2)        # the group's query heads one after another,
    nt = pl.num_programs(2)     # each over the `band` query blocks that may
    qi = t % band               # see ki (all nq without a window, those
    live = inside = None        # above the diagonal dead)
    if causal:
        first, last = _query_band(ki, bq, bk, window, nq)
        if window:
            qi += first
        live = jnp.logical_and(qi >= first, qi <= last)
        inside = _interior(qi, ki, bq, bk, window)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(masked):
        # scaled as the forward scales it: dK = dS^T (scale Q)
        qs = q_ref[0].astype(jnp.float32) * scale
        ds, p = _dscores(k_ref, v_ref, do_ref, lse_ref, delta_ref, qs, qi,
                         ki, window, masked)
        dv_acc[:] += jax.lax.dot_general(
            p, do_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds, qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _live_tiles(step, causal, live, inside)

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                    interpret, window=0):
    """`lse` as `_flash_forward` returns it, (B*H, S)."""
    b, h, s, d = q.shape
    grp = _group(q, k)
    hkv = h // grp
    block_q = _fit_block(block_q, s)
    block_k = _fit_block(block_k, s)
    window = _window_of(window, causal, s)
    nq = s // block_q
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    dof = g.reshape(b * h, s, d)
    # delta_i = rowsum(dO_i * O_i), once a row for both kernels, a row of
    # the sequence a head as the logsumexp is: O is no operand of theirs
    delta = jnp.sum(dof.astype(jnp.float32)
                    * o.reshape(b * h, s, d).astype(jnp.float32), axis=-1)
    lse, delta = lse[:, None, :], delta[:, None, :]         # (B*H, 1, S)
    key_steps, band = _band_extent(s, window, block_q, block_k)
    rows, cols, cols_t, stats = _block_maps(grp, block_q, block_k, causal,
                                            window)
    q_spec = pl.BlockSpec((1, block_q, d), rows)
    k_spec = pl.BlockSpec((1, block_k, d), cols)
    stat_spec = pl.BlockSpec((1, 1, block_q), stats)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          window=window),
        grid=(b * h, nq, key_steps),                # K/V innermost
        in_specs=[q_spec, k_spec, pl.BlockSpec((1, d, block_k), cols_t),
                  k_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32),   # dQ^T
                        pltpu.VMEM((block_q, d), jnp.float32)],  # q * scale
        interpret=interpret,
        name="flash_swa_dq" if window else "flash_dq",
    )(qf, kf, jnp.swapaxes(kf, 1, 2), vf, dof, lse, delta)

    # second kernel iterates (kv head, j, t): Q/dO stream innermost, the
    # group's query heads one after another, so a shared key-value head's
    # gradient is summed in the kernel. t = head in group x band + step
    # over the band of query blocks that may see key block j (all nq
    # without a window); a dead step holds the index of the nearest live
    # block, so nothing is fetched for it
    def rows_t(bk, j, t):
        i = t % band
        if causal:
            first, last = _query_band(j, block_q, block_k, window, nq)
            i = jnp.clip(i + first if window else i, first, last)
        return (bk * grp + t // band, i, 0)

    def stats_t(bk, j, t):
        head, i, _ = rows_t(bk, j, t)
        return (head, 0, i)

    qT_spec = pl.BlockSpec((1, block_q, d), rows_t)
    kT_spec = pl.BlockSpec((1, block_k, d), lambda bk, j, t: (bk, j, 0))
    statT_spec = pl.BlockSpec((1, 1, block_q), stats_t)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, nq=nq,
                          window=window, band=band),
        grid=(b * hkv, s // block_k, grp * band),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, statT_spec,
                  statT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[jax.ShapeDtypeStruct((b * hkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * hkv, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_swa_dkv" if window else "flash_dkv",
    )(qf, kf, vf, dof, lse, delta)
    return (dq.reshape(b, h, s, d), dk.reshape(b, hkv, s, d),
            dv.reshape(b, hkv, s, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, window=0, layer=None):
    """Flash attention (B, H, S, D) -> (B, H, S, D); exact, O(block) VMEM
    in both forward and backward. scale defaults to 1/sqrt(D). k and v may
    have fewer heads, (B, Hkv, S, D) with H a multiple of Hkv: query head
    h reads key-value head h // (H / Hkv) in place, and the backward sums
    a shared head's gradient over its group inside the kernel. `window`
    (with `causal`): query i sees keys i - window < j <= i, and the blocks
    outside that band are not visited. `layer` is the caller's name in the
    `remat.kept` records of what the backward keeps (the header).

    Default blocks are 512x512: the grid's K/V dimension is sequential,
    so small blocks are dispatch-latency-bound — at S=32k, 512x512 runs
    the train-grad step 20x faster than 128x128 on a v5e (149 ms vs
    3.1 s) while still using O(block^2) VMEM (~1 MB of scores). Blocks
    clamp to S for short sequences."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                            _should_interpret(), window)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k, window, layer):
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              _should_interpret(), window)
    out, lse = keep(out, layer, "o"), keep(lse, layer, "lse")
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, block_k, window, layer, res, g):
    q, k, v, o, lse = res
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_backward(q, k, v, o, lse, g, causal, scale, block_q,
                           block_k, _should_interpret(), window)


flash_attention.defvjp(_fwd, _bwd)
