"""Fused conv-epilogue pallas kernels: bias+ReLU and bias+ReLU+LRN.

The XLA lowering of a Convolution layer's tail is bias-add + ReLU fused
into the conv output's epilogue, followed (in the GoogLeNet conv2 tower
and stock AlexNet variants) by a separate ACROSS_CHANNELS LRN that costs
several more HBM round-trips of the full activation (ops/lrn.py; the
pallas_lrn.py module header has the trace evidence). Running the LRN as
its own pallas kernel was a measured LOSS on v5e (PERF.md round-3): it
broke the bias+ReLU epilogue fusion and added a materialization
boundary. These kernels close that gap the other way — the entire
epilogue (bias add, ReLU, and optionally the channel-window LRN) runs in
ONE read and one write of the raw conv output, so the pallas boundary no
longer costs an extra pass:

    bias_relu:      out = max(x + b, 0)
    bias_relu_lrn:  y = max(x + b, 0)
                    out = y * (k + alpha/size * sum_{window} y^2)^-beta

Backward reuses the structure of pallas_lrn: the residual is the RAW
conv output x plus the (C,) bias — both already live — and the bwd pass
recomputes y = relu(x+b) instead of saving a second activation. For
bias_relu the backward is pure elementwise (dx = g * (y > 0)) and stays
in XLA where it fuses with its neighbors; only the LRN variant needs the
pallas backward, which it borrows from pallas_lrn._call_bwd applied to
y. dbias = sum(dx) over (N, H, W) is an XLA reduce outside the kernel.

Layout matches pallas_lrn: NCHW flattened to (N, C, H*W), spatial tiled
in 512-lane blocks, channels on the sublane axis. The bias rides in as a
(1, C, 128) broadcast so its block is a legal TPU tile at any C.

Selection lives in graph/compiler.py (SPARKNET_EPILOGUE gate); this
module only provides the fused ops.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import _should_interpret
from .pallas_lrn import SPATIAL_BLOCK, _window_sum, \
    _call_bwd as _lrn_call_bwd


def _bias_tile(b, dtype):
    """(C,) bias -> (1, C, 128) broadcast: a legal TPU tile whose block
    index map pins every grid step to the same lanes."""
    c = b.shape[0]
    return jnp.broadcast_to(b.astype(dtype).reshape(1, c, 1), (1, c, 128))


def _bias_relu_kernel(x_ref, b_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0][:, :1].astype(jnp.float32)        # (C, 1) column
    out_ref[0] = jnp.maximum(x + b, 0.0).astype(out_ref.dtype)


def _bias_relu_lrn_kernel(size, alpha, beta, k, x_ref, b_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0][:, :1].astype(jnp.float32)
    y = jnp.maximum(x + b, 0.0)
    half = (size - 1) // 2
    scale = k + (alpha / size) * _window_sum(y * y, size, half)
    out_ref[0] = (y * scale ** (-beta)).astype(out_ref.dtype)


def _call_epilogue(kernel, name, x, b, interpret):
    n, c, h, w = x.shape
    xf = x.reshape(n, c, h * w)
    bt = _bias_tile(b, x.dtype)
    grid = (n, pl.cdiv(h * w, SPATIAL_BLOCK))
    spec = pl.BlockSpec((1, c, SPATIAL_BLOCK), lambda i, j: (i, 0, j))
    bspec = pl.BlockSpec((1, c, 128), lambda i, j: (0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, bspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
        name=name,      # what a device trace and the HLO call it
    )(xf, bt)
    return out.reshape(n, c, h, w)


# -- bias + ReLU -----------------------------------------------------------
@jax.custom_vjp
def bias_relu(x, b):
    """max(x + b[None,:,None,None], 0) on NCHW, one fused pass."""
    return _call_epilogue(_bias_relu_kernel, "bias_relu", x, b,
                          _should_interpret())


def _br_fwd(x, b):
    return bias_relu(x, b), (x, b)


def _br_bwd(res, g):
    x, b = res
    # recompute the mask from the cheap elementwise fwd; stays in XLA
    # where it fuses with whatever consumes dx
    y = x + b.astype(x.dtype)[None, :, None, None]
    dx = jnp.where(y > 0, g, jnp.zeros_like(g))
    db = jnp.sum(dx.astype(jnp.float32), axis=(0, 2, 3)).astype(b.dtype)
    return dx, db


bias_relu.defvjp(_br_fwd, _br_bwd)


# -- bias + ReLU + cross-channel LRN ---------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def bias_relu_lrn(x, b, size, alpha, beta, k):
    """lrn_across(max(x + b, 0)) on NCHW in ONE fused read/write."""
    return _call_epilogue(
        functools.partial(_bias_relu_lrn_kernel, size, alpha, beta, k),
        "bias_relu_lrn", x, b, _should_interpret())


def _brl_fwd(x, b, size, alpha, beta, k):
    return bias_relu_lrn(x, b, size, alpha, beta, k), (x, b)


def _brl_bwd(size, alpha, beta, k, res, g):
    x, b = res
    y = jnp.maximum(x + b.astype(x.dtype)[None, :, None, None], 0)
    # d(lrn)/dy via the existing fused LRN backward kernel, then the ReLU
    # mask; both read y, which XLA materializes once
    dy = _lrn_call_bwd(y, g, size, alpha, beta, k, _should_interpret())
    dx = jnp.where(y > 0, dy, jnp.zeros_like(dy))
    db = jnp.sum(dx.astype(jnp.float32), axis=(0, 2, 3)).astype(b.dtype)
    return dx, db


bias_relu_lrn.defvjp(_brl_fwd, _brl_bwd)
