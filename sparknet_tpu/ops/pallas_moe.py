"""The held experts' grouped products (ops/moe.py) as TPU kernels: the
grouped matrix products of jax's own megablox (`jax.experimental.pallas.
ops.tpu.megablox.gmm`: `gmm` and `tgmm`), called as they are.

A window's rows lie sorted by expert, each expert's rows one contiguous
group, `sizes[e]` rows long; the rows after the last group belong to
nobody. `gmm` walks the row tiles that hold a row of some group (a grid
whose second extent is a traced value: as many tiles as the routing fills,
a tile that two groups share visited once for each) and multiplies each
by its group's matrix; `tgmm` accumulates each group's lhs^T rhs in
float32 inside the kernel and writes it once (zeros for a group of no
row: it visits the empty groups too). What this module adds is the
block sizes (the row tile is the layer's `tile_rows`, the other two extents
whole where the blocks fit the 16 MB of VMEM a kernel gets unasked) and a
name a call: upstream's are jitted functions called `gmm` and `tgmm`, and
the un-jitted function under a `jax.named_scope` makes the device trace say
`moe_gmm_fwd`, `moe_gmm_bwd`, `moe_gmm_dw`.

The rows after the last group are not written by `gmm` (whatever the
buffer held stays there: the caller masks them) and not read by `tgmm`.

And the one dense pass of the combine (PR 39): `segment_add`, a window's
rows token by token in, each token's sum on its first row out, in one
read and one write (`moe_segment_add` in a device trace). XLA's form of
the same shifted adds copies the window once a shift, because a slice at a
row offset that is no multiple of 8 is off the tiling (step 0 of PR 39:
1.7 ms a shift of 30,720 rows of 2,560).

Imported in the branch of ops/moe.py that calls it, never at the top of a
module every process imports (PR 29: 1.4 s of `import
jax.experimental.pallas` in every cell's set-up).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from .backend import _should_interpret

# elements of the largest block: 2 MB of bfloat16 weights a buffer for
# `gmm`, 2 MB of float32 accumulator and output for `tgmm`
_GMM_BLOCK, _TGMM_BLOCK = 1 << 20, 1 << 19


def _blocks(k, n, most):
    """(tk, tn): k and n whole, the larger halved while the block holds
    more than `most` elements. A width with no half that is a multiple of
    the lane width (2,688 = 21 x 128, 1,920 = 15 x 128: PR 42; the widths
    before it all halve down to their blocks) takes the pair of divisors
    in whole lane rows with the most elements that fit: (2688, 384) for
    2,688 x 1,920, where a cut to one lane row made 21 steps of (128,
    1920)."""
    whole = k, n
    while k * n > most:
        if (k if k >= n else n) % 256:
            return _fitting_divisors(*whole, most)
        if k >= n:
            k //= 2
        else:
            n //= 2
    return k, n


def _fitting_divisors(k, n, most):
    """The divisors (tk, tn) of k and n, multiples of the lane width each,
    with the largest product that `most` allows; of equal products the
    squarer pair."""
    def lane_divisors(x):
        return [d for d in range(128, x + 1, 128) if x % d == 0]
    return max(((tk, tn) for tk in lane_divisors(k) for tn in lane_divisors(n)
                if tk * tn <= most or (tk, tn) == (128, 128)),
               key=lambda b: (b[0] * b[1], min(b)))


# the VMEM a kernel gets unasked on a v5e
_VMEM = 16 << 20


def fits(tile, embed, hidden, itemsize):
    """Whether every grouped product of an expert of `embed` x `hidden`
    fits VMEM at a row tile of `tile`, operands of `itemsize` bytes: both
    operand blocks in two buffers each; `gmm`'s float32 output block in two
    and its accumulator, `tgmm`'s likewise (it adds onto no running total
    since PR 48; with one it held two buffers more, which was the larger
    count at four of the six cells' widths and decided the verdict at
    none: PERF.md section 6, PR 48). Against compiles for the described chip
    at the six cells' widths, tiles 128 to 512, both types (PR 47) this
    says no wherever the compiler does, and no to one expert it takes
    (2,048 x 512 in bfloat16 at 512)."""
    def gmm_bytes(k, n):
        tk, tn = _blocks(k, n, _GMM_BLOCK)
        return 2 * itemsize * (tile * tk + tk * tn) + 3 * 4 * tile * tn

    def tgmm_bytes(k, n):
        tk, tn = _blocks(k, n, _TGMM_BLOCK)
        return 2 * itemsize * tile * (tk + tn) + 3 * 4 * tk * tn
    return max(gmm_bytes(embed, hidden), gmm_bytes(hidden, embed),
               tgmm_bytes(embed, hidden), tgmm_bytes(hidden, embed)) < _VMEM


def grouped_dot(sizes, tile, lhs, rhs, transpose_rhs, name):
    """lhs (m, k) against rhs (groups, k, n), or (groups, n, k) with
    `transpose_rhs`: row r of group e times rhs[e] -> (m, n) float32, in
    row tiles of `tile`."""
    return _gmm(lhs, rhs, sizes, tile, transpose_rhs, name,
                _should_interpret())


def grouped_dot_t(sizes, tile, lhs, rhs, name):
    """(lhs's rows of group e)^T (rhs's rows of group e) for every group
    e: lhs (m, k), rhs (m, n) -> (groups, k, n) float32, written once;
    zeros for a group of no row."""
    return _tgmm(lhs, rhs, sizes, tile, name, _should_interpret())


# jitted under names of their own: a window's calls of one shape (gate and
# up, forward and recomputed, every layer) trace upstream's python once


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, sizes, tile, transpose_rhs, name, interpret):
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with jax.named_scope(name):
        return gmm.__wrapped__(
            lhs, rhs, sizes, jnp.float32, (tile, *_blocks(k, n, _GMM_BLOCK)),
            transpose_rhs=transpose_rhs, interpret=interpret)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _tgmm(lhs, rhs, sizes, tile, name, interpret):
    k, n = lhs.shape[1], rhs.shape[1]
    with jax.named_scope(name):
        return tgmm.__wrapped__(
            lhs.T, rhs, sizes, jnp.float32,
            (tile, *_blocks(k, n, _TGMM_BLOCK)), interpret=interpret)


# -- the combine's segment add ------------------------------------------------

_SEGMENT_ROWS, _SEGMENT_LANES = 512, 1024


def _halo(segment):
    """Rows of the next block that a block's last rows reach into: a power
    of two, whole sublane tiles."""
    return max(8, 1 << (segment - 2).bit_length()) if segment > 1 else 8


def segment_block(window, segment):
    """Rows a block of `segment_add`, or 0 where none fits (a window of
    few, odd tiles under a long segment: the caller keeps XLA's form).
    Half of _SEGMENT_ROWS at most under a segment of more than 8 rows:
    512 rows of 1,024 lanes and their shifted copies miss VMEM from 9 on
    (18 MB; segments of 9, 10, 12, 16, 17 and 32 compiled for the described
    chip, PR 47; at 8, and at 256 rows up to 32, they fit)."""
    block = math.gcd(window, _SEGMENT_ROWS // (1 if segment <= 8 else 2))
    return block if block % 8 == 0 and block >= _halo(segment) else 0


def _segment_add_kernel(tokens, segment, weighted, *refs):
    if weighted:
        tok_ref, tokh_ref, wt_ref, wth_ref, z_ref, zh_ref, out_ref = refs
    else:
        tok_ref, tokh_ref, z_ref, zh_ref, out_ref = refs
    rows = z_ref.shape[0]
    tok = jnp.concatenate([tok_ref[...], tokh_ref[...]], axis=0)
    z = jnp.concatenate([z_ref[...], zh_ref[...]], axis=0)
    if weighted:
        z = z * jnp.concatenate([wt_ref[...], wth_ref[...]], axis=0)
    # the rows of no pair hold whatever the buffer held: they add nothing
    z = jnp.where(tok < tokens, z, 0.0)
    total, mine = z[:rows], tok[:rows]
    for d in range(1, segment):
        total = total + jnp.where(tok[d:d + rows] == mine, z[d:d + rows],
                                  0.0)
    out_ref[...] = total


def segment_add(z, weight, tok, tokens, segment, block):
    """z (window, E) float32, its rows sorted by their token `tok`
    (window,) int32 (`tokens` on the rows of no pair, which come last),
    times `weight` (window,) a row where given -> (window + block, E)
    float32: row i holds the sum of rows i, i + 1, .. of i's token, `segment`
    at most, added in ascending order, so a token's first row holds the
    token's sum; the last `block` rows are zeros (the row to gather for a
    token with no row here). `block` from `segment_block`."""
    return _segment_add(z, weight, tok, tokens, segment, block,
                        _should_interpret())


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _segment_add(z, weight, tok, tokens, segment, block, interpret):
    window, embed = z.shape
    halo = _halo(segment)
    lanes = next((c for c in range(_SEGMENT_LANES, 0, -128)
                  if embed % c == 0), embed)
    blocks = window // block
    per = block // halo                      # halo blocks a row block

    def rows(i, j):
        return jnp.minimum(i, blocks - 1), j

    def rows_after(i, j):
        return jnp.minimum((i + 1) * per, window // halo - 1), j

    def column(i, j):
        return i, 0

    def column_after(i, j):
        return (i + 1) * per, 0

    def padded(v, fill):
        # a block of its own for the zero rows, and the halo after it
        return jnp.pad(v, (0, 2 * block),
                       constant_values=fill).reshape(-1, 1)
    columns = [padded(tok, tokens)] * 2
    specs = [pl.BlockSpec((block, 1), column),
             pl.BlockSpec((halo, 1), column_after)]
    if weight is not None:
        columns += [padded(weight, 0.0)] * 2
        specs += specs
    with jax.named_scope("moe_segment_add"):
        return pl.pallas_call(
            functools.partial(_segment_add_kernel, tokens, segment,
                              weight is not None),
            grid=(blocks + 1, embed // lanes),
            in_specs=specs + [pl.BlockSpec((block, lanes), rows),
                              pl.BlockSpec((halo, lanes), rows_after)],
            out_specs=pl.BlockSpec((block, lanes), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((window + block, embed),
                                           jnp.float32),
            interpret=interpret, name="moe_segment_add",
        )(*columns, z, z)
