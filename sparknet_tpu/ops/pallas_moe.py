"""The held experts' grouped products (ops/moe.py) as TPU kernels: the
grouped matrix products of jax's own megablox (`jax.experimental.pallas.
ops.tpu.megablox.gmm`: `gmm` and `tgmm`), called as they are.

A window's rows lie sorted by expert, each expert's rows one contiguous
group, `sizes[e]` rows long; the rows after the last group belong to
nobody. `gmm` walks the row tiles that hold a row of some group (a grid
whose second extent is a traced value: as many tiles as the routing fills,
a tile that two groups share visited once for each) and multiplies each
by its group's matrix; `tgmm` accumulates each group's lhs^T rhs in
float32 inside the kernel and writes it once. What this module adds is the
block sizes (the row tile is the layer's `tile_rows`, the other two extents
whole where the blocks fit the 16 MB of VMEM a kernel gets unasked) and a
name a call: upstream's are jitted functions called `gmm` and `tgmm`, and
the un-jitted function under a `jax.named_scope` makes the device trace say
`moe_gmm_fwd`, `moe_gmm_bwd`, `moe_gmm_dw`.

The rows after the last group are not written by `gmm` (whatever the
buffer held stays there: the caller masks them) and not read by `tgmm`.

Imported in the branch of ops/moe.py that calls it, never at the top of a
module every process imports (PR 29: 1.4 s of `import
jax.experimental.pallas` in every cell's set-up).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from .pallas_lrn import _should_interpret

# elements of the largest block: 2 MB of bfloat16 weights a buffer for
# `gmm`, 2 MB of float32 accumulator, output and running total for `tgmm`
_GMM_BLOCK, _TGMM_BLOCK = 1 << 20, 1 << 19


def _blocks(k, n, most):
    """(tk, tn): k and n whole, the larger halved (or, where it has no
    half that is a multiple of the lane width, cut to one lane width)
    while the block holds more than `most` elements."""
    while k * n > most:
        if k >= n:
            k = k // 2 if k % 256 == 0 else 128
        else:
            n = n // 2 if n % 256 == 0 else 128
    return k, n


def grouped_dot(sizes, tile, lhs, rhs, transpose_rhs, name):
    """lhs (m, k) against rhs (groups, k, n), or (groups, n, k) with
    `transpose_rhs`: row r of group e times rhs[e] -> (m, n) float32, in
    row tiles of `tile`."""
    return _gmm(lhs, rhs, sizes, tile, transpose_rhs, name,
                _should_interpret())


def grouped_dot_t(sizes, tile, lhs, rhs, total, name):
    """total[e] + (lhs's rows of group e)^T (rhs's rows of group e):
    lhs (m, k), rhs (m, n), total (groups, k, n) float32, updated in
    place."""
    return _tgmm(lhs, rhs, sizes, total, tile, name, _should_interpret())


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


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _tgmm(lhs, rhs, sizes, total, tile, name, interpret):
    k, n = lhs.shape[1], rhs.shape[1]
    with jax.named_scope(name):
        return tgmm.__wrapped__(
            lhs.T, rhs, sizes, jnp.float32,
            (tile, *_blocks(k, n, _TGMM_BLOCK)), existing_out=total,
            interpret=interpret)
