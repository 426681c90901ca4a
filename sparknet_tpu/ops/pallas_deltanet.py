"""The gated delta rule's chunk body as pallas TPU kernels, forward and
backward (the equations in ops/deltanet.py's docstring, unchanged).

A grid cell is a pair of KEY heads, each with the `r` value heads that
share it (two independent chains for the scheduler to interleave); the
chunks of C tokens run in order along a sequential grid axis, the state S
(Dk x Dv a value head, float32) in a VMEM scratch across it. So a chunk's
triangular system, its products and the state never leave VMEM between
chunks; q, k, v, beta, G are read once and o is written once. Per value
head and chunk, from q, k (C x Dk), v (C x Dv), beta, G = cumsum(g) (C)
and the state S0:

    D_ij = exp(G_i - G_j) (j <= i),  A = strict_lower(beta_i D_ij k_i.k_j)
    T = (I + A)^-1
    U = T (beta V - (beta exp(G) K) S0)
    O = (exp(G) Q) S0 + (D * Q K^T) U
    S_end = exp(G_end) S0 + (exp(G_end - G) K)^T U

q and k enter RAW, in whatever float type the caller has (bfloat16 from
the conv), and are widened, L2-normalised (q also scaled by Dk^-0.5)
inside; the backward kernel returns the gradients of the raw q and k in
their type, so no float32 copy of q or k ever lies in HBM. Every product
is a float32 `dot_general` at `Precision.HIGHEST` with float32
accumulation; decays, exponentials and the state are float32.

The r heads' C x C matrices (D, A, T, D * Q K^T) lie SIDE BY SIDE in one
(C, r C) array — with r = 2 and C = 64 exactly the lane width, so their
elementwise work fills whole registers, and one product against a
block-diagonal right-hand side serves all of them. T is built that way
(`_unit_lower_inverses`): the diagonal blocks of 16 by the exact Neumann
product (I - A)(I + A^2)(I + A^4)(I + A^8), then blocks of 32 and of 64
from [[T1, 0], [-T2 A21 T1, T2]], every block of a level in one product
of as many ROWS as a block has. The MXU's time goes by rows pushed: this
takes 192 of them for two heads where the Neumann product over the whole
chunk, a head at a time, took 1,280.

Backward: the forward stores each chunk's T (C x r C: 134 MB a layer at
2 x 8,192 tokens and 32 heads of 128) and the state at the start of every
GROUP of 8 chunks (67 MB; all chunks' states would be 537 MB, and the cell
has no such room). A grid step of the backward kernel is one group, the
groups in reverse: it first runs the group's chunks forward from the
stored state, keeping each chunk's state, U and decay matrices in VMEM,
then walks them in reverse with dS in a VMEM scratch and makes the
gradients of q, k, v, beta and G. With dR = T^T dU the triangular system's
gradient is dA = -dR U^T. Products that share a right-hand side are one
product of stacked rows, products that are summed one longer contraction:
a product costs about the same 0.14 us whatever its rows (chip runs, PR
30), so their number is what is kept small. The forward's four results
(o, the state after, the groups' states, T) go through
`graph/remat.py:keep`: a block under `--remat full` or `dots` keeps what
its backward reads of them (268 + 67 + 134 MB a layer at that shape, o in
float32) and the forward kernel runs once, not again for its own backward;
one `remat.kept` record each in the ring of obs/trace.py.

Per-token scalars (beta, G and their gradients) travel as ROWS, a
(chunks, r C) table a key head that stays in VMEM across the chunk axis; a
chunk's row is turned into columns, and a column of row sums back into a
row, by a masked reduction against the identity.

Interpret mode engages on the CPU backend only (the tests); any other
backend compiles the kernels or raises.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..graph.remat import keep
from .backend import _should_interpret
from .deltanet import L2_EPS

_F32 = jnp.float32
GROUP = 8           # chunks between two stored states
KEY_HEADS = 2       # key heads a grid step works on, where they pair up
_BASE = 16          # the diagonal blocks the Neumann product inverts


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _nn(a, b):      # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):      # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):      # a^T @ b
    return _dot(a, b, ((0,), (0,)))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _tile_rows(x, times):
    return jnp.concatenate([x] * times, axis=0) if times > 1 else x


def _unit_lower_inverses(a):
    """(I + a_h)^-1 for the strictly lower-triangular (C, C) matrices a_h
    that lie side by side in a (C, L): the inverses, side by side.

    At block size m the diagonal blocks' inverses lie in an (m, L) array
    x, block b in lanes [m b, m b + m), which are its columns in `a` too.
    First m = `base`, by the Neumann product on all blocks at once
    (x @ blockdiag(y) multiplies block by block). Then m doubles: with T1,
    T2 a pair's inverses and A21 the block of `a` below T1 and left of T2,
    the pair's inverse is [[T1, 0], [-T2 A21 T1, T2]]; T2 A21 T1 for every
    pair is two products of m rows, the right-hand sides `a` and x
    themselves under a mask."""
    c, lanes = a.shape
    base = min(_BASE, c)
    row, col = _iota((lanes, lanes), 0), _iota((lanes, lanes), 1)
    a_rows = _tile_rows(a, lanes // c)          # row r holds a's row r % c

    def lane_block(m):
        return _iota((m, lanes), 1) // m

    def blockdiag(x, m, keep):
        return jnp.where(keep, _tile_rows(x, lanes // m), 0.0)

    m = base
    same = row // m == col // m
    # the diagonal blocks of `a`: block b's rows are rows (b % (c/m)) m ...
    diag = jnp.zeros((m, lanes), _F32)
    for p in range(c // m):
        diag = jnp.where(lane_block(m) % (c // m) == p,
                         a[p * m:(p + 1) * m], diag)
    eye = (_iota((m, lanes), 1) % m == _iota((m, lanes), 0)).astype(_F32)
    x, power, span = eye - diag, diag, 2
    while span < m:
        power = _nn(power, blockdiag(power, m, same))
        x = x + _nn(x, blockdiag(power, m, same))
        span *= 2
    while m < c:
        rb, cb = row // m, col // m
        odd = lane_block(m) % 2 == 1
        x_odd = jnp.where(odd, x, 0.0)
        below = jnp.where((rb % 2 == 1) & (cb == rb - 1), a_rows, 0.0)
        first = blockdiag(x, m, (rb == cb) & (rb % 2 == 0))
        x = jnp.concatenate([jnp.where(odd, 0.0, x),
                             x_odd - _nn(_nn(x_odd, below), first)], axis=0)
        m *= 2
    return x


def _unit(x):
    """Rows widened and L2-normalised, and the factor that did it (C, 1):
    ops/deltanet.py's `l2_normalize`."""
    x = x.astype(_F32)
    rnorm = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
    return x * rnorm, rnorm


def _unit_grad(unit, rnorm, d_unit):
    """The gradient of x from the gradient of x * rsqrt(sum x^2 + L2_EPS)."""
    return rnorm * (d_unit - unit * jnp.sum(unit * d_unit, axis=1,
                                            keepdims=True))


def _columns(row, r):
    """A (1, r C) row of r heads' per-token scalars -> each head's (C, 1)
    column, and the (C, r C) array whose head h lanes all hold head h's
    column."""
    chunk = row.shape[1] // r
    shape = (chunk, row.shape[1])
    eye = _iota(shape, 1) % chunk == _iota(shape, 0)
    head = _iota(shape, 1) // chunk
    cols = [jnp.sum(jnp.where(eye & (head == j), row, 0.0), axis=1,
                    keepdims=True) for j in range(r)]
    spread = jnp.broadcast_to(cols[0], shape)
    for j in range(1, r):
        spread = jnp.where(head == j, cols[j], spread)
    return cols, spread


def _chunk_terms(q, k, b_row, g_row, r, tinv=None):
    """What depends on no state, for the r heads side by side (C, r C):
    the decay matrix D, D * K K^T (A without beta), D * Q K^T and T; and
    each head's beta and G as columns. q (scaled) and k normalised."""
    chunk = q.shape[0]
    shape = (chunk, r * chunk)
    k_rep = _tile_rows(k, r)
    lower = _iota(shape, 0) >= _iota(shape, 1) % chunk
    strict = _iota(shape, 0) > _iota(shape, 1) % chunk
    b_cols, b_spread = _columns(b_row, r)
    g_cols, g_spread = _columns(g_row, r)
    # exp(G_i - G_j) for j <= i: every exponent is <= 0
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, g_spread - g_row,
                                               0.0)), 0.0)
    # one right-hand side, so one product: rows of q k^T, then of k k^T
    qk_kk = _nt(jnp.concatenate([q, k], axis=0), k_rep)
    dkk = decay * qk_kk[chunk:]
    if tinv is None:
        tinv = _unit_lower_inverses(jnp.where(strict, b_spread * dkk, 0.0))
    return decay, dkk, decay * qk_kk[:chunk], tinv, b_cols, g_cols


def _fwd_kernel(q_ref, k_ref, v_ref, beta_ref, g_ref, s0_ref,
                o_ref, s_end_ref, *rest, kh, r, dk, dv, group, scale):
    # with what the backward reads (the state a group of chunks starts
    # from, each chunk's T), or, where nothing is differentiated, without
    starts_ref, tinv_ref, s_scr, m_scr = \
        rest if len(rest) == 4 else (None, None) + rest
    c = pl.program_id(2)
    chunk = q_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    if starts_ref is not None:
        @pl.when(c % group == 0)
        def _keep():
            starts_ref[0, :, 0] = s_scr[...]

    for h in range(kh):     # independent: the scheduler interleaves them
        q = _unit(q_ref[0, :, h * dk:(h + 1) * dk])[0] * scale
        k = _unit(k_ref[0, :, h * dk:(h + 1) * dk])[0]
        _, _, m_scr[h, 0], m_scr[h, 1], b_cols, g_cols = _chunk_terms(
            q, k, beta_ref[0, h, pl.ds(c, 1), :],
            g_ref[0, h, pl.ds(c, 1), :], r)
        if tinv_ref is not None:
            tinv_ref[0, h, 0] = m_scr[h, 1]
        for j in range(r):
            b_col, g_col, g_end = b_cols[j], g_cols[j], g_cols[j][-1:, :]
            p = m_scr[h, 0, :, j * chunk:(j + 1) * chunk]
            tinv = m_scr[h, 1, :, j * chunk:(j + 1) * chunk]
            hj = h * r + j
            s = s_scr[hj]
            v = v_ref[0, :, hj * dv:(hj + 1) * dv].astype(_F32)
            e_col = jnp.exp(g_col)
            on_s = _nn(jnp.concatenate([k * (b_col * e_col), q * e_col],
                                       axis=0), s)
            u = _nn(tinv, b_col * v - on_s[:chunk])
            o_ref[0, :, hj * dv:(hj + 1) * dv] = on_s[chunk:] + _nn(p, u)
            s_scr[hj] = s * jnp.exp(g_end) \
                + _tn(k * jnp.exp(g_end - g_col), u)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        s_end_ref[0] = s_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, beta_ref, g_ref, starts_ref, tinv_ref,
                do_ref, ds_end_ref,
                dq_ref, dk_ref, dv_ref, dbeta_ref, dg_ref, ds0_ref,
                ds_scr, s_scr, u_scr, m_scr, *, kh, r, dk, dv, chunk, group,
                scale):
    step = pl.program_id(2)
    n = pl.num_programs(2)
    first = (n - 1 - step) * group      # the groups in reverse

    @pl.when(step == 0)
    def _init():
        ds_scr[...] = ds_end_ref[0]

    def tokens(i):
        return pl.ds(pl.multiple_of(i * chunk, chunk), chunk)

    def part(j, width=chunk):
        return slice(j * width, (j + 1) * width)

    # the group's chunks forward from the stored state: each chunk's first
    # state, U and side-by-side matrices stay in VMEM for the walk back
    s_scr[0] = starts_ref[0, :, 0]

    def forward(i, _):
        for h in range(kh):
            q = _unit(q_ref[0, tokens(i), part(h, dk)])[0] * scale
            k = _unit(k_ref[0, tokens(i), part(h, dk)])[0]
            (m_scr[i, h, 0], m_scr[i, h, 1], m_scr[i, h, 2], m_scr[i, h, 3],
             b_cols, g_cols) = _chunk_terms(
                q, k, beta_ref[0, h, pl.ds(first + i, 1), :],
                g_ref[0, h, pl.ds(first + i, 1), :], r, tinv_ref[0, h, i])
            for j in range(r):
                b_col, g_col, g_end = b_cols[j], g_cols[j], g_cols[j][-1:, :]
                hj = h * r + j
                s = s_scr[i, hj]
                v = v_ref[0, tokens(i), part(hj, dv)].astype(_F32)
                u = _nn(m_scr[i, h, 3, :, part(j)],
                        b_col * v - _nn(k * (b_col * jnp.exp(g_col)), s))
                u_scr[i, hj] = u
                s_scr[i + 1, hj] = s * jnp.exp(g_end) \
                    + _tn(k * jnp.exp(g_end - g_col), u)
        return 0

    lax.fori_loop(0, group, forward, 0)

    shape = (chunk, chunk)
    lower = _iota(shape, 0) >= _iota(shape, 1)
    strict = _iota(shape, 0) > _iota(shape, 1)
    eye = _iota(shape, 0) == _iota(shape, 1)
    last = _iota((1, chunk), 1) == chunk - 1

    def to_row(col):        # (C, 1) -> (1, C)
        return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)

    def to_col(row):        # (1, C) -> (C, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def backward(back, _):
        i = group - 1 - back
        c = first + i
        for h in range(kh):
            q_unit, q_rnorm = _unit(q_ref[0, tokens(i), part(h, dk)])
            k, k_rnorm = _unit(k_ref[0, tokens(i), part(h, dk)])
            q = q_unit * scale
            dq = jnp.zeros_like(q)
            dk_ = jnp.zeros_like(k)
            dqk = jnp.zeros(shape, _F32)    # summed over the r heads that
            dkk = jnp.zeros(shape, _F32)    # share q and k
            for j in range(r):
                hj = h * r + j
                decay = m_scr[i, h, 0, :, part(j)]
                kk = m_scr[i, h, 1, :, part(j)]         # D * K K^T
                p = m_scr[i, h, 2, :, part(j)]          # D * Q K^T
                tinv = m_scr[i, h, 3, :, part(j)]
                b_col = to_col(beta_ref[0, h, pl.ds(c, 1), part(j)])
                g_col = to_col(g_ref[0, h, pl.ds(c, 1), part(j)])
                g_end = g_col[-1:, :]
                s, u, ds_end = s_scr[i, hj], u_scr[i, hj], ds_scr[hj]
                v = v_ref[0, tokens(i), part(hj, dv)].astype(_F32)
                do = do_ref[0, tokens(i), part(hj, dv)].astype(_F32)
                e_col, e_end = jnp.exp(g_col), jnp.exp(g_end)
                kbg = k * (b_col * e_col)
                qg = q * e_col
                kd = k * jnp.exp(g_end - g_col)

                du = _tn(p, do) + _nn(kd, ds_end)           # (C, Dv)
                dkd = _nt(u, ds_end)                        # (C, Dk)
                dr = _tn(tinv, du)                          # (C, Dv)
                # products that share a right-hand side, or that are
                # summed, as one: rows stacked, or the contraction
                do_dr = jnp.concatenate([do, dr], axis=0)
                on_u = _nt(do_dr, u)
                dp = jnp.where(lower, on_u[:chunk], 0.0)    # (C, C)
                da = jnp.where(strict, -on_u[chunk:], 0.0)  # (C, C)
                on_s = _nt(do_dr, s)
                dqg, dkbg = on_s[:chunk], -on_s[chunk:]     # (C, Dk)
                ds_scr[hj] = e_end * ds_end + _tn(
                    jnp.concatenate([qg, -kbg], axis=0), do_dr)

                dkk += da * (b_col * decay)
                dqk += dp * decay
                e = da * (b_col * kk) + dp * p      # dD * D
                kd_dot = jnp.sum(dkd * kd, axis=1, keepdims=True)
                dg_col = (jnp.sum(e, axis=1, keepdims=True)
                          + jnp.sum(dqg * qg, axis=1, keepdims=True)
                          + jnp.sum(dkbg * kbg, axis=1, keepdims=True)
                          - kd_dot)
                dg_end = e_end * jnp.sum(ds_end * s, keepdims=True) \
                    + jnp.sum(kd_dot, keepdims=True)        # (1, 1)
                db_col = (jnp.sum(da * kk, axis=1, keepdims=True)
                          + e_col * jnp.sum(dkbg * k, axis=1, keepdims=True)
                          + jnp.sum(dr * v, axis=1, keepdims=True))
                dg_ref[0, h, pl.ds(c, 1), part(j)] = (
                    to_row(dg_col) - jnp.sum(e, axis=0, keepdims=True)
                    + jnp.where(last, dg_end, 0.0))
                dbeta_ref[0, h, pl.ds(c, 1), part(j)] = to_row(db_col)
                dv_ref[0, tokens(i), part(hj, dv)] = \
                    (b_col * dr).astype(dv_ref.dtype)
                dq += e_col * dqg
                dk_ += (b_col * e_col) * dkbg \
                    + jnp.exp(g_end - g_col) * dkd
            both = jnp.concatenate([dqk, dkk], axis=0)
            on_k = _nn(both, k)
            dq = dq + on_k[:chunk]
            dk_ = dk_ + on_k[chunk:] \
                + _tn(both, jnp.concatenate([q, k], axis=0))
            dq_ref[0, tokens(i), part(h, dk)] = _unit_grad(
                q_unit, q_rnorm, scale * dq).astype(dq_ref.dtype)
            dk_ref[0, tokens(i), part(h, dk)] = _unit_grad(
                k, k_rnorm, dk_).astype(dk_ref.dtype)
        return 0

    lax.fori_loop(0, group, backward, 0)

    @pl.when(step == n - 1)
    def _finish():
        ds0_ref[0] = ds_scr[...]


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _heads(q, dk, hv):
    """Key heads, value heads a key head, and key heads a grid step."""
    hk = q.shape[2] // dk
    return hk, hv // hk, KEY_HEADS if hk % KEY_HEADS == 0 else 1


def _forward(q, k, v, beta, g, s0, chunk, group, interpret, residuals=True):
    """q, k (B, T, Hk Dk), v (B, T, Hv Dv), the row tables beta and G (B,
    Hk, N, r C), s0 (B, Hv, Dk, Dv) -> o (B, T, Hv Dv), the state after
    and, with `residuals`, each group's first state (B, Hv, N / group, Dk,
    Dv) and each chunk's T (B, Hk, N, C, r C)."""
    b, t, _ = q.shape
    hv, dk, dv = s0.shape[1:]
    n = t // chunk
    hk, r, kh = _heads(q, dk, hv)
    qk = pl.BlockSpec((1, chunk, kh * dk), lambda i, h, c: (i, c, h))
    vs = pl.BlockSpec((1, chunk, kh * r * dv), lambda i, h, c: (i, c, h))
    rows = pl.BlockSpec((1, kh, n, r * chunk), lambda i, h, c: (i, h, 0, 0))
    state = pl.BlockSpec((1, kh * r, dk, dv), lambda i, h, c: (i, h, 0, 0))
    starts = pl.BlockSpec((1, kh * r, 1, dk, dv),
                          lambda i, h, c: (i, h, c // group, 0, 0))
    tinv = pl.BlockSpec((1, kh, 1, chunk, r * chunk),
                        lambda i, h, c: (i, h, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, kh=kh, r=r, dk=dk, dv=dv, group=group,
                          scale=dk ** -0.5),
        grid=(b, hk // kh, n),
        in_specs=[qk, qk, vs, rows, rows, state],
        out_specs=[vs, state] + [starts, tinv] * residuals,
        out_shape=[jax.ShapeDtypeStruct((b, t, hv * dv), _F32),
                   jax.ShapeDtypeStruct(s0.shape, _F32)] + [
                   jax.ShapeDtypeStruct((b, hv, n // group, dk, dv), _F32),
                   jax.ShapeDtypeStruct((b, hk, n, chunk, r * chunk), _F32)
                   ] * residuals,
        scratch_shapes=[pltpu.VMEM((kh * r, dk, dv), _F32),
                        pltpu.VMEM((kh, 2, chunk, r * chunk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(q, k, v, beta, g, s0)


def _backward(q, k, v, beta, g, starts, tinv, do, ds_end, chunk, group,
              interpret):
    """`_forward`'s inputs and residuals, the cotangents of o and of the
    state after -> the gradients of q, k, v, beta, G and s0."""
    b, t, _ = q.shape
    hv, ng, dk, dv = starts.shape[1:]
    n = t // chunk
    hk, r, kh = _heads(q, dk, hv)
    span = group * chunk

    def back(c):
        return ng - 1 - c
    qk = pl.BlockSpec((1, span, kh * dk), lambda i, h, c: (i, back(c), h))
    vs = pl.BlockSpec((1, span, kh * r * dv),
                      lambda i, h, c: (i, back(c), h))
    rows = pl.BlockSpec((1, kh, n, r * chunk), lambda i, h, c: (i, h, 0, 0))
    state = pl.BlockSpec((1, kh * r, dk, dv), lambda i, h, c: (i, h, 0, 0))
    start = pl.BlockSpec((1, kh * r, 1, dk, dv),
                         lambda i, h, c: (i, h, back(c), 0, 0))
    tinvs = pl.BlockSpec((1, kh, group, chunk, r * chunk),
                         lambda i, h, c: (i, h, back(c), 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, kh=kh, r=r, dk=dk, dv=dv, chunk=chunk,
                          group=group, scale=dk ** -0.5),
        grid=(b, hk // kh, ng),
        in_specs=[qk, qk, vs, rows, rows, start, tinvs, vs, state],
        out_specs=[qk, qk, vs, rows, rows, state],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(beta.shape, _F32),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(ds_end.shape, _F32)],
        scratch_shapes=[
            pltpu.VMEM((kh * r, dk, dv), _F32),                 # dS
            pltpu.VMEM((group + 1, kh * r, dk, dv), _F32),      # states
            pltpu.VMEM((group, kh * r, chunk, dv), _F32),       # U
            pltpu.VMEM((group, kh, 4, chunk, r * chunk), _F32)],
        # a group's blocks twice over and its states: 17 MB at the LM's
        # shape, over the 16 MB a kernel gets unasked
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS,
            vmem_limit_bytes=40 * 1024 * 1024),
        interpret=interpret,
        name="gdn_chunk_bwd",
    )(q, k, v, beta, g, starts, tinv, do, ds_end)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _chunks(q, k, v, beta, g, s0, chunk, group, layer):
    return tuple(_forward(q, k, v, beta, g, s0, chunk, group,
                          _should_interpret(), residuals=False))


def _chunks_fwd(q, k, v, beta, g, s0, chunk, group, layer):
    # what the backward pass reads of the forward kernel's results goes
    # through graph/remat.py:keep, so a block under remat keeps them and
    # the kernel does not run again for its own backward
    results = _forward(q, k, v, beta, g, s0, chunk, group,
                       _should_interpret())
    o, s_end, starts, tinv = [
        keep(a, layer, name)
        for a, name in zip(results, ("o", "s_end", "starts", "tinv"))]
    return (o, s_end), (q, k, v, beta, g, starts, tinv)


def _chunks_bwd(chunk, group, layer, res, cot):
    q, k, v, beta, g, starts, tinv = res
    do, ds_end = cot
    return _backward(q, k, v, beta, g, starts, tinv, do, ds_end, chunk,
                     group, _should_interpret())


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def chunk_rule(q, k, v, beta, g, state=None, chunk=64, layer=None):
    """The gated delta rule through the kernel pair. q, k (B, T, Hk, Dk)
    RAW: the kernels normalise them as ops/deltanet.py's `l2_normalize`
    does and scale q by Dk^-0.5; v (B, T, Hv, Dv) with Hv a multiple of Hk (value head j
    reads key head j // (Hv / Hk)), beta and g (B, T, Hv), `state` (B, Hv,
    Dk, Dv) or None for zeros -> (o (B, T, Hv, Dv) float32, the state
    after). Dk and Dv are multiples of the lane width. T is padded to whole
    groups of chunks with tokens that leave the state alone. `layer` is
    the caller's name in the `remat.kept` records (the header)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    group = min(GROUP, -(-t // chunk))
    pad = -t % (group * chunk)
    if pad:
        q, k, v, beta, g = [
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, beta, g)]
    n = (t + pad) // chunk

    def rows(a):    # (B, T, Hv) -> (B, Hk, N, r, C): a key head's heads
        a = a.astype(_F32).reshape(b, n, chunk, hk, r)
        return jnp.transpose(a, (0, 3, 1, 4, 2))
    if state is None:
        state = jnp.zeros((b, hv, dk, dv), _F32)
    table = (b, hk, n, r * chunk)       # ... side by side
    o, s_end = _chunks(q.reshape(b, t + pad, hk * dk),
                       k.reshape(b, t + pad, hk * dk),
                       v.reshape(b, t + pad, hv * dv),
                       rows(beta).reshape(table),
                       jnp.cumsum(rows(g), axis=-1).reshape(table),
                       state.astype(_F32), chunk, group, layer)
    return o.reshape(b, t + pad, hv, dv)[:, :t], s_end
