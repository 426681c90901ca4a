"""The Mamba-2 chunk body as pallas TPU kernels, forward and backward (the
equations and the precisions in ops/mamba2.py's docstring, unchanged).

A grid step is one chunk of one GROUP of a batch row: the r heads of P
channels that share a B and a C, side by side as the conv leaves them, a
(Q, r P) block of x. The chunks of Q tokens run in order along a
sequential grid axis, the group's state in a VMEM scratch across it,
TRANSPOSED: (N, r P) float32, a head's P columns under its own lanes. So a
chunk's r decay masks, its one C B^T tile and the state never leave VMEM;
x, B, C are read once and y is written once. Per chunk, with delta and l
(the running sum of delta A inside the chunk) a head, dx = delta x, H0 the
state before:

    Y  = ((C B^T) * L_j) dx_j + exp(l_j) (C H0_j^T)      L_j[t, s] =
    H1 = exp(l_end) H0 + B^T (exp(l_end - l) dx)         exp(l_t - l_s), s <= t

Everything but C B^T goes a LANE TILE at a time (128 lanes: two heads at P
= 64). The products with a mask take the tile's masks side by side (Q, 2 Q)
against its dx stacked under a head mask (2 Q, 128), so a result is a whole
lane tile and nothing is shifted between lanes; the product that reads the
state, C (Q, N) against the tile's (N, 128) of it, is at the highest
precision; the chunk's own state is B^T against the decay-weighted dx. x,
B, C, the masks (C B^T) * L and exp(l_end - l) dx are operands in x's
type, rounded once; everything else is float32.

Per-token scalars (delta, l, their gradients) travel as ROWS, a (chunks,
rows, Q) table a group with the heads' rows padded to whole sublane tiles;
a chunk's rows become columns, and columns of sums rows, through one
(Q, Q) transpose. A column is spread over its head's P lanes by selects.

Backward: the forward stores the state every chunk STARTS from (N x r P a
group a chunk, float32: 268 MB a layer at 2 x 8,192 tokens and 64 heads of
64 x 128) and the last one. The backward kernel walks the chunks in
reverse along the same sequential axis with dH in a VMEM scratch and makes
dx, dB, dC (summed over the group's heads), d delta and dl; the in-chunk
running sum, A and softplus are XLA's, outside. It does not read y: a
chunk's Y is made again in VMEM, because every decay's gradient is then a
sum over a head's channels. With dM = dY dx^T the masks give l the row
sums of dM * M less its column sums, which are sum_p dY Y_in and sum_p dx
ddx_in (ddx_in = M^T dY), and with dxw = B dH1^T the chunk's own state
gives it -sum_p xw dxw and, at the chunk's last token, sum(dH1 * S):

    d delta = sum_p ddx x          dl = sum_p (dY Y - dx ddx_in - xw dxw)
    dl_end += sum(dH1 * (H1 - exp(l_end) H0) + exp(l_end) dH1 * H0)

(H1 the state the NEXT chunk starts from, kept from the step before).
Each pair of shares cancels over a chunk, so each is taken of the same
rounded operands the products saw (taken of unlike ones, A's gradient was
20% off in bfloat16). One product against a 0/1 matrix sums a tile's heads'
channels at the highest precision, straight into columns. A product at the
highest precision whose one side is bfloat16 as it comes (C, the 0/1
matrix) runs as three bfloat16 passes over the other side's three parts:
the same sum as the library's six (0.75 ms a layer less in the backward:
chip run, PR 43). The forward's three results (y, the chunks' first states,
the last state) go through `graph/remat.py:keep`: a block under `--remat
full` or `dots` keeps them, its replayed gate and norm read the kept y, and
the forward kernel runs once a step; one `remat.kept` record each in the
ring of obs/trace.py.

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

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
LANES = 128
_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b^T


def _rows(r):
    """A group's rows in a table of per-token scalars: whole sublane tiles."""
    return -(-r // 8) * 8


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=_F32)


def _split(v):
    """A float32 array as three bfloat16 arrays that add up to it (to a
    bit of 2^-24: what a product at the highest precision is made of)."""
    bf16 = jnp.bfloat16
    hi = v.astype(bf16)
    rest = v - hi.astype(_F32)
    mid = rest.astype(bf16)
    return hi, mid, (rest - mid.astype(_F32)).astype(bf16)


def _dot_hi(a, b, dims):
    """a . b at the highest precision. That is six bfloat16 passes over
    the three parts of either side; where one side IS bfloat16 (C as the
    conv leaves it, a 0/1 matrix) its parts are itself and two zeros, and
    the three passes over the other side's parts are the same sum."""
    if a.dtype == jnp.bfloat16:
        return sum(_dot(a, part, dims) for part in _split(b))
    if b.dtype == jnp.bfloat16:
        return sum(_dot(part, b, dims) for part in _split(a))
    return _dot(a, b, dims, _HI)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _to_cols(*tables):
    """Row tables (rows, Q) -> (Q, Q): table i's row j as the column in
    lane i rows + j."""
    q = tables[0].shape[1]
    rows = sum(t.shape[0] for t in tables)
    return jnp.concatenate(tables + (jnp.zeros((q - rows, q), _F32),),
                           axis=0).T


def _spread(cols, first, heads, p):
    """(Q, 128): the columns of a lane tile's heads (lane first + j of
    `cols` for head j), each over the lanes of its head."""
    q = cols.shape[0]
    tile = jnp.broadcast_to(
        cols[:, first + heads[0]:first + heads[0] + 1], (q, LANES))
    for k, j in enumerate(heads[1:], 1):
        tile = jnp.where(_iota((q, LANES), 1) // p == k,
                         cols[:, first + j:first + j + 1], tile)
    return tile


def _lane_tiles(r, p):
    """[(the lanes of a tile, its heads)] over a group's r p lanes."""
    return [(slice(t * LANES, (t + 1) * LANES),
             range(t * LANES // p, ((t + 1) * LANES - 1) // p + 1))
            for t in range(r * p // LANES)]


def _side_by_side(tiles, axis):
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=axis)


def _stack_heads(tile, heads, p):
    """A lane tile (Q, 128) of several heads -> (heads Q, 128): a copy a
    head, one under another, each with the other heads' lanes zeroed."""
    if len(heads) == 1:
        return tile
    head = _iota(tile.shape, 1) // p
    return jnp.concatenate([jnp.where(head == k, tile, jnp.zeros_like(tile))
                            for k in range(len(heads))], axis=0)


def _pick_heads(stack, heads, p):
    """(heads Q, 128) -> (Q, 128): head k's lanes from the k-th block of
    rows."""
    q = stack.shape[0] // len(heads)
    head = _iota((q, LANES), 1) // p
    tile = stack[:q]
    for k in range(1, len(heads)):
        tile = jnp.where(head == k, stack[k * q:(k + 1) * q], tile)
    return tile


def _chunk_terms(dt_ref, l_ref):
    """What a chunk's lane tiles share: the per-token scalars as columns
    (delta in lane j, l in lane first_l + j) and the heads' decay masks."""
    dt_rows, l_rows = dt_ref[0, 0, 0], l_ref[0, 0, 0]
    q = l_rows.shape[1]
    cols = _to_cols(dt_rows, l_rows)
    first_l = dt_rows.shape[0]
    lower = _iota((q, q), 0) >= _iota((q, q), 1)
    upper = _iota((q, q), 0) <= _iota((q, q), 1)

    def decay(j, transposed=False):
        """L_j (or its transpose): exp(l_t - l_s) where s <= t, else 0; no
        exponent above 0 is taken."""
        col, row = cols[:, first_l + j:first_l + j + 1], l_rows[j:j + 1]
        diff, live = (row - col, upper) if transposed else (col - row, lower)
        return jnp.where(live, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    return cols, first_l, decay


def _tile_terms(x_ref, cols, first_l, lanes, heads, p):
    """x, delta, l, delta x and exp(l_end - l) of one lane tile (Q, 128):
    everything elementwise goes a lane tile at a time, its temporaries a
    quarter of a group's (Q, r P) (the time is the same: chip run, PR 43)."""
    x = x_ref[0, :, lanes]
    q = x.shape[0]
    dt = _spread(cols, 0, heads, p)
    l = _spread(cols, first_l, heads, p)
    # exp(l_end - l_s): every exponent is <= 0 (l falls along a chunk)
    return x, dt, l, dt * x.astype(_F32), jnp.exp(l[q - 1:q] - l)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, y_ref, last_ref, *rest,
                r, p):
    # with the state every chunk starts from (what the backward reads), or,
    # where nothing is differentiated, without
    starts_ref, h_scr = rest if len(rest) == 2 else (None,) + rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    bm, cm = b_ref[0], c_ref[0]
    cols, first_l, decay = _chunk_terms(dt_ref, l_ref)
    cd = bm.dtype
    q = bm.shape[0]
    cb = _dot(cm, bm, _NT)                          # one C B^T a group
    bmt = bm.astype(_F32).T.astype(cd)
    for lanes, heads in _lane_tiles(r, p):
        h0 = h_scr[:, lanes]                        # (N, 128)
        if starts_ref is not None:
            starts_ref[0, 0, 0, :, lanes] = h0
        _, _, l, dx, w = _tile_terms(x_ref, cols, first_l, lanes, heads, p)
        masks = [(cb * decay(j)).astype(cd) for j in heads]
        # the carried state is read at the highest precision
        y_ref[0, :, lanes] = jnp.exp(l) * _dot_hi(cm, h0, _NN) + _dot(
            _side_by_side(masks, 1), _stack_heads(dx.astype(cd), heads, p),
            _NN)
        h_scr[:, lanes] = jnp.exp(l[q - 1:q]) * h0 \
            + _dot(bmt, (dx * w).astype(cd), _NN)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        last_ref[0, 0] = h_scr[...]


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, starts_ref, last_ref,
                dy_ref, dlast_ref, dx_ref, db_ref, dc_ref, ddt_ref, dl_ref,
                dh_scr, h1_scr, *, r, p):
    step = pl.program_id(2)                         # the chunks in reverse

    @pl.when(step == 0)
    def _init():
        dh_scr[...] = dlast_ref[0, 0]
        h1_scr[...] = last_ref[0, 0]

    bm, cm = b_ref[0], c_ref[0]
    cols, first_l, decay = _chunk_terms(dt_ref, l_ref)
    cd = bm.dtype
    q, n = bm.shape
    rows = dt_ref.shape[3]
    cmt = cm.astype(_F32).T.astype(cd)
    cb, cbt = _dot(cm, bm, _NT), _dot(bm, cm, _NT)
    dcb = jnp.zeros((q, q), _F32)
    dcm, dbm = jnp.zeros((q, n), _F32), jnp.zeros((q, n), _F32)
    sums = jnp.zeros((2 * q + 8, LANES), _F32)
    for lanes, heads in _lane_tiles(r, p):
        h0, h1, dh1 = starts_ref[0, 0, 0, :, lanes], h1_scr[:, lanes], \
            dh_scr[:, lanes]
        x, dt, l, dx, w = _tile_terms(x_ref, cols, first_l, lanes, heads, p)
        dy = dy_ref[0, :, lanes]                    # (Q, 128) float32
        dyb, dxb, xw = dy.astype(cd), dx.astype(cd), (dx * w).astype(cd)
        e, e_end = jnp.exp(l), jnp.exp(l[q - 1:q])
        dh1b = dh1.astype(cd)

        # through the state: Y += e * (C H0), H1 = e_end H0 + B^T xw
        d_t = e * dy
        dcm += _dot(d_t, h0, _NT, _HI)
        dbm += _dot(xw, dh1b, _NT)
        dh_scr[:, lanes] = e_end * dh1 + _dot_hi(cmt, d_t, _NN)
        h1_scr[:, lanes] = h0
        dxw = _dot(bm, dh1b, _NN)
        # what l gets, before it is summed over a head's channels. Its
        # shares cancel over a chunk (the row sums of dM * M against its
        # column sums, sum_s dxw xw against dl_end's sum(dH1 * S)), so each
        # pair is taken of the SAME rounded operands: sum_p dY Y_in and
        # sum_p dx ddx_in below
        to_l = dy * (e * _dot_hi(cm, h0, _NN)) - xw.astype(_F32) * dxw
        to_end = dh1b.astype(_F32) * (h1 - e_end * h0) + e_end * dh1 * h0

        # inside the chunk
        decays = [decay(j) for j in heads]
        dx_stack = _stack_heads(dxb, heads, p)
        y_in = _dot(_side_by_side([(cb * d).astype(cd) for d in decays], 1),
                    dx_stack, _NN)
        dmask = _dot(dyb, dx_stack, _NT)            # (Q, heads Q)
        for k, d in enumerate(decays):
            dcb += dmask[:, k * q:(k + 1) * q] * d
        ddx_in = _pick_heads(_dot(_side_by_side(
            [(cbt * decay(j, True)).astype(cd) for j in heads], 0), dyb, _NN),
            heads, p)
        to_l = to_l + dyb.astype(_F32) * y_in - dxb.astype(_F32) * ddx_in
        ddx = ddx_in + dxw * w
        dx_ref[0, :, lanes] = (ddx * dt).astype(dx_ref.dtype)

        # a head's sums over its channels by one product, straight into
        # columns: lane j of `sums` is head j's
        sel = (heads[0] + _iota((LANES, LANES), 0) // p
               == _iota((LANES, LANES), 1)).astype(cd)
        end = jnp.broadcast_to(jnp.sum(to_end, axis=0, keepdims=True),
                               (8, LANES))
        sums += _dot_hi(jnp.concatenate([to_l, ddx * x.astype(_F32), end],
                                        axis=0), sel, _NN)
    dc_ref[0] = (dcm + _dot(dcb.astype(cd), bm, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (dbm + _dot(dcb.T.astype(cd), cm, _NN)).astype(db_ref.dtype)
    last_row = _iota((q, LANES), 0) == q - 1
    dl = sums[:q] + jnp.where(last_row, sums[2 * q:2 * q + 1], 0.0)
    ddt_ref[0, 0, 0] = sums[q:2 * q].T[:rows]
    dl_ref[0, 0, 0] = dl.T[:rows]


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _specs(x, b, dt, r, p, back=False):
    """The block specs of a chunk's x-shaped, B-shaped, table-shaped and
    state-shaped arrays, and of a group's one state; the chunks in order,
    or in reverse."""
    n = b.shape[2] // (x.shape[2] // (r * p))
    nc, rows, q = dt.shape[2:]

    def at(c):
        return nc - 1 - c if back else c
    return (pl.BlockSpec((1, q, r * p), lambda i, g, c: (i, at(c), g)),
            pl.BlockSpec((1, q, n), lambda i, g, c: (i, at(c), g)),
            pl.BlockSpec((1, 1, 1, rows, q),
                         lambda i, g, c: (i, g, at(c), 0, 0)),
            pl.BlockSpec((1, 1, 1, n, r * p),
                         lambda i, g, c: (i, g, at(c), 0, 0)),
            pl.BlockSpec((1, 1, n, r * p), lambda i, g, c: (i, g, 0, 0)))


def _forward(x, b, c, dt, l, r, p, interpret, residuals=True):
    """x (B, S, H P), b and c (B, S, G N), the row tables delta and l (B,
    G, chunks, rows, Q) -> y (B, S, H P) float32, the last state (B, G, N,
    r P) and, with `residuals`, the state every chunk starts from (B, G,
    chunks, N, r P)."""
    bsz, s, hp = x.shape
    g, nc = dt.shape[1:3]
    n = b.shape[2] // g
    xs, bs, rows, starts, state = _specs(x, b, dt, r, p)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, p=p),
        grid=(bsz, g, nc),
        in_specs=[xs, bs, bs, rows, rows],
        out_specs=[xs, state] + [starts] * residuals,
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hp), _F32),
                   jax.ShapeDtypeStruct((bsz, g, n, r * p), _F32)] + [
                   jax.ShapeDtypeStruct((bsz, g, nc, n, r * p), _F32)
                   ] * residuals,
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, b, c, dt, l)


def _backward(x, b, c, dt, l, starts, last, dy, dlast, r, p, interpret):
    """`_forward`'s inputs and results, the cotangents of y and of the
    last state -> the gradients of x, b, c and of the two row tables."""
    bsz, g, nc, n, _ = starts.shape
    xs, bs, rows, start, state = _specs(x, b, dt, r, p, back=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, r=r, p=p),
        grid=(bsz, g, nc),
        in_specs=[xs, bs, bs, rows, rows, start, state, xs, state],
        out_specs=[xs, bs, bs, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(l.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32),       # dH
                        pltpu.VMEM((n, r * p), _F32)],      # the next H0
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, b, c, dt, l, starts, last, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chunks(x, b, c, dt, l, r, p, layer):
    return tuple(_forward(x, b, c, dt, l, r, p, _should_interpret(),
                          residuals=False))


def _chunks_fwd(x, b, c, dt, l, r, p, layer):
    # what the backward pass and a block's replay read of the forward
    # kernel's results goes through graph/remat.py:keep, so a block under
    # remat keeps them and the kernel does not run again
    y, last, starts = [
        keep(a, layer, name) for a, name in zip(
            _forward(x, b, c, dt, l, r, p, _should_interpret()),
            ("y", "last", "starts"))]
    return (y, last), (x, b, c, dt, l, starts, last)


def _chunks_bwd(r, p, layer, res, cot):
    return tuple(_backward(*res, *cot, r, p, _should_interpret()))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def chunk_scan(x, dt, a, b, c, chunk=128, layer=None):
    """ops/mamba2.py's `ssd_chunked` through the kernel pair, for the
    shapes `Mamba2._why_xla` passes: x (B, S, H, P), dt (B, S, H) (delta), a (H,)
    (A < 0), b, c (B, S, G, N) -> (y (B, S, H, P) float32, the last state
    (B, H, P, N) float32, the mean share of a state that survives a
    chunk). S is padded to whole chunks with tokens of delta 0. `layer` is
    the caller's name in the `remat.kept` records."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    pad = -s % chunk
    dt = dt.astype(_F32)
    if pad:
        x, dt, b, c = [jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c)]
    nc = (s + pad) // chunk

    def rows(v):    # (B, S, H) -> (B, G, chunks, rows, Q): a group's heads
        v = jnp.transpose(v.reshape(bsz, nc, chunk, g, r), (0, 3, 1, 4, 2))
        return jnp.pad(v, [(0, 0)] * 3 + [(0, _rows(r) - r), (0, 0)])
    l = jnp.cumsum(rows(dt * a.astype(_F32)), axis=-1)
    y, last = _chunks(x.reshape(bsz, s + pad, h * p),
                      b.reshape(bsz, s + pad, g * n),
                      c.reshape(bsz, s + pad, g * n), rows(dt), l, r, p,
                      layer)
    last = jnp.transpose(last.reshape(bsz, g, n, r, p), (0, 1, 3, 4, 2))
    return (y.reshape(bsz, s + pad, h, p)[:, :s],
            last.reshape(bsz, h, p, n),
            jnp.mean(jnp.exp(l[:, :, :, :r, -1])))
