"""Pallas kernels of attention over an index-picked key set (ops/dsa.py has
the mathematics and the plain form): masked dense tiles, the mask made in
the kernel from the indexer's tile.

A per-query key set is data, so no block map can skip by it; gathering a
query's 2,048 keys for 4 key-value heads of 128 is 4 MB a query. So the
core runs the causal half's tiles, and every kernel that needs the set
RECOMPUTES the indexer's (block_k, block_q) tile beside the main products
— 16 products of depth 64 against 32 heads x 2 of depth 128, an eighth —
and compares it with the query's threshold: `I[t, s] >= thr[t]`. For that
one tile to serve all the query heads a grid step holds ALL of them: the
grids are (batch, query block, key block) and the heads are a loop inside
the step (the kernels of ops/pallas_attention.py put a head on the grid).
The set is never stored: the threshold is 4 bytes a query.

Four kernels, by their names in a device trace:

- `dsa_index_select` (scope `dsa_select`): a block of queries against all
  their keys: the index scores by tiles into a VMEM scratch as sortable
  integers, their maximum a query taken as they are written, and each
  chunk of them once more as 32 BIT PLANES (a word holds one bit of 32
  keys). Then the `topk`-th largest of every query, a bit of the answer
  a walk from the top: the live keys with the bit set are one `and` and
  one population count a word, so 32 walks read the planes once where 32
  counting passes over the keys read them 32 times (`jax.lax.top_k` at
  k = 2,048 is a sort on a TPU). Last the logsumexp of the selected
  scores (L_I's softmax), one pass over the keys. Out: `thr` and
  `lse_i`, (B, 1, S) float32.
- `flash_sparse_fwd` (scope `attn_core`): the flash recurrence over the
  selected keys; out o and the heads' logsumexp.
- `dsa_kl` (scope `dsa_kl`): L_I's value a query, from the heads'
  probabilities summed in the tile (their scores once more).
- `flash_sparse_dq` (scope `attn_core`): the whole backward, a tile's
  index tile, scores and probabilities made once for all six gradients:
  the FlashAttention-2 backward of the main loss (dq, dk, dv) AND, since
  the tile holds every head's probabilities anyway, L_I's: dI =
  softmax(I) - mean_h P_h on the set, then dw, d qI, d kI from the x_j the
  index tile left in VMEM. The two cotangents do not mix: dq, dk, dv read
  dO alone, the indexer's three read L_I's alone. The grid is dq's
  (batch, query block, key block): 32 query heads' dq are 8 MB a block and
  stay in VMEM over a row of tiles, the 4 key-value heads' dk and dv and
  d kI are 2.25 MB a block and go through HBM, float32, by the kernel's
  own copies (`_bwd_kernel` says why not the pipeline's), each hidden
  behind a neighbouring tile's products. Every sum keeps the order two
  kernels gave it (dq over key blocks, dk, dv, d kI over query blocks and
  heads, ascending). With as many key-value heads as query heads the
  other orientation (key block outermost, dq through HBM) moves less.

A score tile is held transposed, (block_k, block_q), as in
ops/pallas_attention.py: a query's statistics are (1, block_q) rows.
The backward reads the forward's `thr`: `graph/remat.py:keep` holds it
(with `lse_i`, o and the logsumexp) across a block's replay, so the index
scores' selection runs once a step.

The indexer's tile must come out bit for bit alike in all four kernels (a
key at the threshold is in the set everywhere or nowhere): one helper,
`_index_tile`, the same operations in the same order, the products of
depth 64 whole.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..graph.remat import keep
from .backend import _should_interpret
from .pallas_attention import NEG_INF, _fit_block

INT_MIN = np.int32(-2 ** 31)
VMEM_LIMIT = 100 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # (m, d) x (n, d) -> (m, n)
_NN = (((1,), (0,)), ((), ()))      # (m, k) x (k, n) -> (m, n)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _params(outer="parallel"):
    """Every grid here is (batch, an outer block, the streamed block); a
    step holds all the heads of its block, more than the default limit."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", outer, "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _index_parts(ki_ref, qi_ref, j):
    """x_j^T (bk, bq) = kI . qI_j of index head j, float32."""
    return _dot(ki_ref[0].astype(jnp.float32),
                qi_ref[0, j].astype(jnp.float32), _NT)


def _index_tile(ki_ref, qi_ref, w_ref, parts_ref=None):
    """I^T (bk, bq) = sum_j w_j relu(x_j), in the one order every kernel
    shares; the x_j are left in `parts_ref` (HI, bk, bq) where one is
    given."""
    acc = None
    for j in range(qi_ref.shape[1]):
        x = _index_parts(ki_ref, qi_ref, j)
        if parts_ref is not None:
            parts_ref[j] = x
        term = w_ref[0, j] * jnp.maximum(x, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _seen(qi, kj, bq, bk):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    return q_pos >= k_pos


def _set_tile(ki_ref, qi_ref, w_ref, thr_ref, qi, kj, parts_ref=None):
    """(the key set's tile (bk, bq) bool, I^T of the tile)."""
    scores = _index_tile(ki_ref, qi_ref, w_ref, parts_ref)
    bk, bq = scores.shape
    return (scores >= thr_ref[0]) & _seen(qi, kj, bq, bk), scores


def _sortable(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 made +0.0
    first, so that equal floats are equal integers)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key), jnp.float32)


# ---------------------------------------------------------------- selection

SLABS = 32      # a chunk's rows are cut into as many slabs as a key has bits
# what the `dsa.select` record says of the search (ops/attention.py): walks a
# query block makes (32 of a bit plane and its live words, a 32nd of the
# keys' size each, and the sum's one of the keys), answer bits a walk
SELECT_PASSES, BITS_A_PASS = SLABS + 1, 1


def _plane_rows(bk):
    """Rows of a chunk's bit plane: a 32nd of the chunk, the chunk first
    filled up to 32 slabs of whole (8, 128) registers."""
    return -(-bk // (8 * SLABS)) * 8


def _bit_planes(keys):
    """A chunk's sortable integers (bk, bq) as 32 planes (bk / 32, bq):
    bit 31 - b of plane i is bit 31 - i of slab b's key, plane 0 inverted
    (the sign bit is SET in the lower half of the signed order). The
    32 x 32 bit matrix of the slabs' words is transposed in five rounds of
    masked swaps (Hacker's Delight 7-3), 15 operations a key where a
    counting pass over the keys costs 3; the shift may be arithmetic,
    every mask clears what it drags in. A chunk that is not 32 whole slabs
    is filled up with INT_MIN, which no walk counts."""
    bk, bq = keys.shape
    m = _plane_rows(bk)
    if m * SLABS > bk:
        keys = jnp.concatenate(
            [keys, jnp.full((m * SLABS - bk, bq), INT_MIN, jnp.int32)])
    x = [keys[b * m:(b + 1) * m] for b in range(SLABS)]
    for j, mask in ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
                    (2, 0x33333333), (1, 0x55555555)):
        for k in range(SLABS):
            if not k & j:
                swap = (x[k] ^ (x[k + j] >> j)) & jnp.int32(mask)
                x[k] = x[k] ^ swap
                x[k + j] = x[k + j] ^ (swap << j)
    return [~x[0]] + x[1:]


def _select_kernel(qi_ref, ki_ref, w_ref, thr_ref, lse_ref, keys_ref,
                   top_ref, planes_ref, alive_ref, *, topk, stride, search):
    r, c, nc = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = qi_ref.shape[2], ki_ref.shape[1]
    m = _plane_rows(bk)
    last = (r * bq + bq - 1) // bk          # the last chunk with a seen key

    @pl.when(c <= last)
    def _scores():
        keys = jnp.where(_seen(r, c, bq, bk),
                         _sortable(_index_tile(ki_ref, qi_ref, w_ref)),
                         INT_MIN)
        keys_ref[pl.ds(pl.multiple_of(c * bk, bk), bk), :] = keys
        # the scores' maximum (the softmax's shift) rides with the write
        part = jnp.max(keys.reshape(bk // 8, 8, bq), axis=0)
        top_ref[...] = jnp.maximum(
            part, jnp.where(c == 0, INT_MIN + 1, top_ref[...]))
        if search:
            for i, plane in enumerate(_bit_planes(keys)):
                planes_ref[i, c] = plane

    @pl.when(c == nc - 1)
    def _select():
        pos = r * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        top = _unsortable(jnp.max(top_ref[...], axis=0, keepdims=True))
        if not search:      # scripts/bench_dsa.py: the scores' write alone
            thr_ref[0] = jnp.full((1, bq), -jnp.inf, jnp.float32)
            lse_ref[0] = top
            return
        steps = last // stride + 1          # `stride` chunks a step

        def walk(i, alive_of):
            """The live keys with bit 31 - i set, counted a query: every
            step's live words are made by `alive_of`, stored, and counted
            under plane i. 32 keys a word and one population count: a walk
            reads a 32nd of what a pass over the keys reads."""
            def step(t, acc):
                at = pl.ds(pl.multiple_of(t * stride, stride), stride)
                alive = alive_of(t, at)
                alive_ref[at] = alive
                return acc + jax.lax.population_count(
                    alive & planes_ref[i, at])
            acc = jax.lax.fori_loop(
                0, steps, step, jnp.zeros((stride, m, bq), jnp.int32))
            return jnp.sum(jnp.sum(acc, axis=0), axis=0, keepdims=True)

        def keep(i, count, kth, rank):
            """The answer's bits from the top: bit 31 - i is set where at
            least `rank` live keys have it; where not, those keys lie above
            the answer and leave the rank. -> kth, rank and what the next
            walk turns plane i by (0: the keys with the bit stay live,
            -1: those without)."""
            take = count >= rank
            return (jnp.where(take, kth | jnp.left_shift(
                        jnp.int32(1), 31 - i), kth),
                    jnp.where(take, rank, rank - count),
                    jnp.where(take, 0, -1))

        def seen_chunks(t, at):             # a row's end: chunks never written
            chunk = t * stride + jax.lax.broadcasted_iota(
                jnp.int32, (stride, m, bq), 0)
            return jnp.where(chunk <= last, -1, 0)

        def bit(i, carry):
            kth, rank, turn = carry
            return keep(i, walk(i, lambda t, at: alive_ref[at] & (
                planes_ref[i - 1, at] ^ turn)), kth, rank)

        zero = jnp.zeros((1, bq), jnp.int32)
        kth = jax.lax.fori_loop(
            1, 32, bit, keep(0, walk(0, seen_chunks), zero, zero + topk))[0]
        kth = kth ^ INT_MIN                 # the planes' order is unsigned
        # a query with no more than topk keys takes them all
        cut = jnp.where(pos < topk, INT_MIN + 1, kth)

        def chunk(i, acc):
            blk = keys_ref[pl.ds(pl.multiple_of(i * bk, bk), bk), :]
            return acc + jnp.sum(jnp.where(
                blk >= cut, jnp.exp(_unsortable(blk) - top), 0.0),
                axis=0, keepdims=True)
        total = jax.lax.fori_loop(0, last + 1, chunk,
                                  jnp.zeros((1, bq), jnp.float32))
        thr_ref[0] = jnp.where(pos < topk, -jnp.inf, _unsortable(kth))
        lse_ref[0] = top + jnp.log(total)


def _select(qi, ki, w, topk, block_q, block_k, interpret, search=True):
    """-> thr, lse_i, each (B, 1, S) float32. `search=False` stops after
    the scores' write (scripts/bench_dsa.py's split of the kernel's time)."""
    b, hi, s, di = qi.shape
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, s)
    nk, m = s // bk, _plane_rows(bk)
    stride = math.gcd(4, nk)

    def chunks(bb, r, c):
        return (bb, jnp.minimum(c, (r * bq + bq - 1) // bk), 0)
    row = pl.BlockSpec((1, 1, bq), lambda bb, r, c: (bb, 0, r))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, stride=stride,
                          search=search),
        grid=(b, s // bq, nk),
        in_specs=[pl.BlockSpec((1, hi, bq, di),
                               lambda bb, r, c: (bb, 0, r, 0)),
                  pl.BlockSpec((1, bk, di), chunks),
                  pl.BlockSpec((1, hi, 1, bq),
                               lambda bb, r, c: (bb, 0, 0, r))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((b, 1, s), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((s, bq), jnp.int32),
                        pltpu.VMEM((8, bq), jnp.int32),
                        pltpu.VMEM((SLABS, nk, m, bq), jnp.int32),
                        pltpu.VMEM((nk, m, bq), jnp.int32)],
        compiler_params=_params(), interpret=interpret,
        name="dsa_index_select",
    )(qi, ki, w)


# ------------------------------------------------------------------ forward

def _masked_scores(k_ref, q_ref, h, grp, scale, sel):
    """scores^T (bk, bq) of head h on the set, -1e30 off it."""
    qs = q_ref[0, h].astype(jnp.float32) * scale
    sc = _dot(k_ref[0, h // grp].astype(jnp.float32), qs, _NT)
    return jnp.where(sel, sc, NEG_INF)


def _fwd_kernel(q_ref, k_ref, vt_ref, qi_ref, ki_ref, w_ref, thr_ref, o_ref,
                lse_ref, acc_ref, m_ref, l_ref, *, scale, grp):
    qi, kj, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    heads, bq, bk = q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kj <= (qi * bq + bq - 1) // bk)
    def _live():
        sel, _ = _set_tile(ki_ref, qi_ref, w_ref, thr_ref, qi, kj)

        def head(h, carry):
            sc = _masked_scores(k_ref, q_ref, h, grp, scale, sel)
            m_prev = m_ref[h]                                   # (1, bq)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + _dot(
                vt_ref[0, h // grp].astype(jnp.float32), p, _NN)
            m_ref[h] = m_new
            return carry
        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(kj == nk - 1)
    def _finish():
        def head(h, carry):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).T.astype(o_ref.dtype)
            lse_ref[0, h] = m_ref[h] + jnp.log(l)
            return carry
        jax.lax.fori_loop(0, heads, head, 0)


def _specs(b, h, hkv, hi, s, d, di, bq, bk):
    """Block specs of the (batch, query block, key step) grids; a dead step
    above the diagonal holds the last live key block (nothing fetched)."""
    def kcol(j, i):
        return jnp.minimum(j, (i * bq + bq - 1) // bk)
    return {
        "q": pl.BlockSpec((1, h, bq, d), lambda bb, i, j: (bb, 0, i, 0)),
        "k": pl.BlockSpec((1, hkv, bk, d),
                          lambda bb, i, j: (bb, 0, kcol(j, i), 0)),
        "kt": pl.BlockSpec((1, hkv, d, bk),
                           lambda bb, i, j: (bb, 0, 0, kcol(j, i))),
        "stat": pl.BlockSpec((1, h, 1, bq), lambda bb, i, j: (bb, 0, 0, i)),
        "qi": pl.BlockSpec((1, hi, bq, di), lambda bb, i, j: (bb, 0, i, 0)),
        "ki": pl.BlockSpec((1, bk, di), lambda bb, i, j: (bb, kcol(j, i), 0)),
        "kit": pl.BlockSpec((1, di, bk),
                            lambda bb, i, j: (bb, 0, kcol(j, i))),
        "w": pl.BlockSpec((1, hi, 1, bq), lambda bb, i, j: (bb, 0, 0, i)),
        "row": pl.BlockSpec((1, 1, bq), lambda bb, i, j: (bb, 0, i)),
    }


def _forward(q, k, v, qi, ki, w, thr, scale, bq, bk, interpret):
    """-> o (B, H, S, D), the heads' logsumexp (B, H, 1, S) float32."""
    b, h, s, d = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    sp = _specs(b, h, hkv, hi, s, d, di, bq, bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, grp=h // hkv),
        grid=(b, s // bq, s // bk),
        in_specs=[sp["q"], sp["k"], sp["kt"], sp["qi"], sp["ki"], sp["w"],
                  sp["row"]],
        out_specs=[sp["q"], sp["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h, d, bq), jnp.float32),
                        pltpu.VMEM((h, 1, bq), jnp.float32),
                        pltpu.VMEM((h, 1, bq), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="flash_sparse_fwd",
    )(q, k, jnp.swapaxes(v, 2, 3), qi, ki, w, thr)


# ---------------------------------------------------------------- L_I value

def _heads_probs(k_ref, q_ref, lse_ref, grp, scale, sel):
    """sum over the heads of P_h^T = exp(S_h - LSE_h) on the set, (bk, bq)."""
    def head(h, total):
        sc = _masked_scores(k_ref, q_ref, h, grp, scale, sel)
        return total + jnp.exp(sc - lse_ref[0, h])
    return jax.lax.fori_loop(0, q_ref.shape[1], head,
                             jnp.zeros(sel.shape, jnp.float32))


def _kl_kernel(q_ref, k_ref, qi_ref, ki_ref, w_ref, thr_ref, lse_ref,
               lsei_ref, kl_ref, acc_ref, *, scale, grp):
    qi, kj, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    heads, bq, bk = q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kj <= (qi * bq + bq - 1) // bk)
    def _live():
        sel, scores = _set_tile(ki_ref, qi_ref, w_ref, thr_ref, qi, kj)
        target = _heads_probs(k_ref, q_ref, lse_ref, grp, scale, sel) \
            * (1.0 / heads)
        live = target > 0
        term = target * (jnp.log(jnp.where(live, target, 1.0))
                         - (scores - lsei_ref[0]))
        acc_ref[:] += jnp.sum(jnp.where(live, term, 0.0), axis=0,
                              keepdims=True)

    @pl.when(kj == nk - 1)
    def _finish():
        kl_ref[0] = acc_ref[:]


def _kl_rows(q, k, qi, ki, w, thr, lse, lse_i, scale, bq, bk, interpret):
    """-> KL(p_t || softmax_{S_t} I[t]) a query, (B, 1, S) float32."""
    b, h, s, d = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    sp = _specs(b, h, hkv, hi, s, d, di, bq, bk)
    return pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, grp=h // hkv),
        grid=(b, s // bq, s // bk),
        in_specs=[sp["q"], sp["k"], sp["qi"], sp["ki"], sp["w"], sp["row"],
                  sp["stat"], sp["row"]],
        out_specs=sp["row"],
        out_shape=jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="dsa_kl",
    )(q, k, qi, ki, w, thr, lse, lse_i)


# ----------------------------------------------------------------- backward

def _index_grad(scores, total, heads, sel, lsei_ref):
    """dI^T (bk, bq) of L_I's SUM over the queries: softmax(I) - mean_h P_h
    on the set."""
    return jnp.where(sel, jnp.exp(scores - lsei_ref[0])
                     - total * (1.0 / heads), 0.0)


def _bwd_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,
                qi_ref, ki_ref, kit_ref, w_ref, thr_ref, lsei_ref, dq_ref,
                dqi_ref, dw_ref, dk_hbm, dv_hbm, dki_hbm, acc_ref, acci_ref,
                accw_ref, dk_buf, dv_buf, dki_buf, parts_ref, slot_ref, sems,
                *, scale, grp):
    """One (query block, key block) tile of the whole backward. The query
    side (dq, d qI, d w) accumulates in VMEM over a row of tiles, as the
    pipeline's blocks; the key side (dk, dv, d kI, float32 in HBM) is the
    kernel's own to move: a key block's sums so far come into one of two
    VMEM slots while the tile before is computed, take this tile's parts
    and go back. The pipeline cannot carry them: a block whose index stays
    between two grid steps is neither written nor fetched again, and dead
    steps and a row's end repeat indices."""
    b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    heads, bq, bk = q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]
    n_index, width = qi_ref.shape[1], qi_ref.shape[3]
    last = (qi * bq + bq - 1) // bk         # the row's last live key block

    def moves(block, slot, way):
        """The three copies of key block `block` between HBM and `slot`:
        way 0 reads, way 1 writes."""
        rows = pl.ds(pl.multiple_of(block * bk, bk), bk)
        pairs = ((dk_hbm.at[b, :, rows, :], dk_buf.at[slot]),
                 (dv_hbm.at[b, :, rows, :], dv_buf.at[slot]),
                 (dki_hbm.at[b, rows, :], dki_buf.at[slot]))
        return [pltpu.make_async_copy(*(pair if way == 0 else pair[::-1]),
                                      sems.at[way, n])
                for n, pair in enumerate(pairs)]

    def start(block, slot, way):
        for copy in moves(block, slot, way):
            copy.start()

    def wait(slot, way):
        for copy in moves(kj, slot, way):   # a wait reads the shapes alone
            copy.wait()

    def clear(slot):
        dk_buf[slot] = jnp.zeros(dk_buf.shape[1:], jnp.float32)
        dv_buf[slot] = jnp.zeros(dv_buf.shape[1:], jnp.float32)
        dki_buf[slot] = jnp.zeros(dki_buf.shape[1:], jnp.float32)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        acci_ref[:] = jnp.zeros_like(acci_ref)
        accw_ref[:] = jnp.zeros_like(accw_ref)

    @pl.when(kj <= last)
    def _live():
        # the live tiles of a batch entry in grid order: this one's place
        first = (qi == 0) & (kj == 0)

        @pl.when(first)
        def _first():
            slot_ref[0] = 0
            clear(0)
        ends_row = kj == last
        final = ends_row & (qi == nq - 1)
        # a key block that the next (the previous) live tile names as well
        # stays in its slot: no copy at all
        stays = ends_row & (last == 0) & jnp.logical_not(final)
        stayed = (kj == 0) & (qi > 0) & ((qi * bq - 1) // bk == 0)
        nxt_q = jnp.where(ends_row, qi + 1, qi)
        nxt_k = jnp.where(ends_row, 0, kj + 1)
        slot = slot_ref[0]
        other = 1 - slot

        sel, scores = _set_tile(ki_ref, qi_ref, w_ref, thr_ref, qi, kj,
                                parts_ref)

        # a key block's first tile starts from zero, any later one from
        # the sums the tile before asked for
        @pl.when((qi != (kj * bk) // bq) & jnp.logical_not(stayed))
        def _arrived():
            wait(slot, 0)

        def heads_of(g, kf, ktf, vf):
            """The query heads of key-value head `g`, its blocks cast
            once."""
            def head(h, total):
                qs = q_ref[0, h].astype(jnp.float32) * scale
                sc = jnp.where(sel, _dot(kf, qs, _NT), NEG_INF)
                p = jnp.exp(sc - lse_ref[0, h])
                do = do_ref[0, h].astype(jnp.float32)
                dv_buf[slot, g] += _dot(p, do, _NN)
                ds = p * (_dot(vf, do, _NT) - delta_ref[0, h])
                acc_ref[h] += _dot(ktf, ds, _NN)    # dQ^T += K^T dS^T
                dk_buf[slot, g] += _dot(ds, qs, _NN)
                return total + p
            return head
        total = jnp.zeros(sel.shape, jnp.float32)
        for g in range(heads // grp):
            total = jax.lax.fori_loop(
                g * grp, (g + 1) * grp,
                heads_of(g, k_ref[0, g].astype(jnp.float32),
                         kt_ref[0, g].astype(jnp.float32),
                         v_ref[0, g].astype(jnp.float32)), total)

        # the other slot: the tile before wrote from it (long done by now),
        # the next tile's key block comes into it behind the index's part
        @pl.when(jnp.logical_not(first) & jnp.logical_not(stayed))
        def _written():
            wait(other, 1)

        @pl.when(jnp.logical_not(final) & jnp.logical_not(stays))
        def _next():
            fresh = nxt_q == (nxt_k * bk) // bq

            @pl.when(fresh)
            def _zero():
                clear(other)

            @pl.when(jnp.logical_not(fresh))
            def _fetch():
                start(nxt_k, other, 0)

        di = _index_grad(scores, total, heads, sel, lsei_ref)
        kit = kit_ref[0].astype(jnp.float32)
        for j in range(n_index):
            x = parts_ref[j]
            accw_ref[j] += jnp.sum(di * jnp.maximum(x, 0.0), axis=0,
                                   keepdims=True)
            dx = jnp.where(x > 0, di * w_ref[0, j], 0.0)
            acci_ref[j] += _dot(kit, dx, _NN)                  # (di, bq)
            dki_buf[slot, :, :width] += _dot(
                dx, qi_ref[0, j].astype(jnp.float32), _NN)

        @pl.when(jnp.logical_not(stays))
        def _send():
            start(kj, slot, 1)
            slot_ref[0] = other

        @pl.when(final)
        def _drain():
            wait(slot, 1)

    @pl.when(kj == nk - 1)
    def _finish():
        def head(h, carry):
            dq_ref[0, h] = (acc_ref[h] * scale).T.astype(dq_ref.dtype)
            return carry
        jax.lax.fori_loop(0, heads, head, 0)
        for j in range(n_index):
            dqi_ref[0, j] = acci_ref[j].T
            dw_ref[0, j] = accw_ref[j]


def _backward(q, k, v, qi, ki, w, thr, lse_i, o, lse, g, scale, bq, bk,
              interpret):
    """-> dq, dk, dv of the main loss (cotangent `g` of o) and d qI, d kI,
    d w of L_I's SUM over the queries, float32 (the caller scales them)."""
    b, h, s, d = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                 # (B, H, 1, S)
    sp = _specs(b, h, hkv, hi, s, d, di, bq, bk)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # a copy cannot cut an array in HBM inside a row of 128 lanes: d kI's
    # rows of `di` travel at the width they have in VMEM anyway
    lanes = -(-di // 128) * 128
    dq, dqi, dw, dk, dv, dki = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, grp=h // hkv),
        grid=(b, s // bq, s // bk),
        in_specs=[sp["q"], sp["k"], sp["kt"], sp["k"], sp["q"], sp["stat"],
                  sp["stat"], sp["qi"], sp["ki"], sp["kit"], sp["w"],
                  sp["row"], sp["row"]],
        out_specs=[sp["q"], sp["qi"], sp["w"], hbm, hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, s, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h, d, bq), jnp.float32),
                        pltpu.VMEM((hi, di, bq), jnp.float32),
                        pltpu.VMEM((hi, 1, bq), jnp.float32),
                        pltpu.VMEM((2, hkv, bk, d), jnp.float32),
                        pltpu.VMEM((2, hkv, bk, d), jnp.float32),
                        pltpu.VMEM((2, bk, lanes), jnp.float32),
                        pltpu.VMEM((hi, bk, bq), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2, 3))],
        # dk, dv, d kI add up over the query blocks: only the batch's
        # entries are each other's strangers
        compiler_params=_params("arbitrary"), interpret=interpret,
        name="flash_sparse_dq",
    )(q, k, jnp.swapaxes(k, 2, 3), v, g, lse, delta, qi, ki,
      jnp.swapaxes(ki, 1, 2), w, thr, lse_i)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), dqi, dki[..., :di],
            dw)


# ------------------------------------------------------------ the function

def blocks(s, block_q=512, block_k=512, select_q=128, select_k=1024):
    """The tiles a sequence of `s` is cut into: (query block, key block) of
    the three core kernels and of `dsa_kl`, then of `dsa_index_select`."""
    return (_fit_block(block_q, s), _fit_block(block_k, s),
            _fit_block(select_q, s), _fit_block(select_k, s))


def causal_tiles(s, block_q=512, block_k=512):
    """Key blocks a core kernel visits, summed over the query blocks: the
    causal half's, every one of them masked by the set."""
    bq, bk = blocks(s, block_q, block_k)[:2]
    return int(np.sum((np.arange(s // bq) * bq + bq - 1) // bk + 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def sparse_attention(q, k, v, qi, ki, w, topk, layer=None):
    """-> (o (B, H, S, D), L_I ()) of ops/dsa.py: q (B, H, S, D); k, v
    (B, Hkv, S, D); qi (B, HI, S, DI); ki (B, S, DI); w (B, HI, S)
    float32. `layer` names the caller in the `remat.kept` records."""
    return _fwd(q, k, v, qi, ki, w, topk, layer)[0]


def _fwd(q, k, v, qi, ki, w, topk, layer):
    b, _, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    bq, bk, sq, sk = blocks(s)
    interpret = _should_interpret()
    w4 = w.astype(jnp.float32)[:, :, None, :]
    with jax.named_scope("dsa_select"):
        thr, lse_i = _select(qi, ki, w4, topk, sq, sk, interpret)
        thr, lse_i = keep(thr, layer, "thr"), keep(lse_i, layer, "lse_i")
    with jax.named_scope("attn_core"):
        o, lse = _forward(q, k, v, qi, ki, w4, thr, scale, bq, bk, interpret)
        o, lse = keep(o, layer, "o"), keep(lse, layer, "lse")
    with jax.named_scope("dsa_kl"):
        kl = jnp.sum(_kl_rows(q, k, qi, ki, w4, thr, lse, lse_i, scale, bq,
                              bk, interpret)) / (b * s)
    return (o, kl), (q, k, v, qi, ki, w, thr, lse_i, o, lse)


def _bwd(topk, layer, res, cts):
    q, k, v, qi, ki, w, thr, lse_i, o, lse = res
    g, g_kl = cts
    b, _, s, d = q.shape
    bq, bk = blocks(s)[:2]
    with jax.named_scope("attn_core"):
        dq, dk, dv, dqi, dki, dw = _backward(
            q, k, v, qi, ki, w.astype(jnp.float32)[:, :, None, :], thr,
            lse_i, o, lse, g, 1.0 / (d ** 0.5), bq, bk, _should_interpret())
        share = g_kl.astype(jnp.float32) / (b * s)
        return (dq, dk, dv, (dqi * share).astype(qi.dtype),
                (dki * share).astype(ki.dtype),
                (dw[:, :, 0, :] * share).astype(w.dtype))


sparse_attention.defvjp(_fwd, _bwd)
