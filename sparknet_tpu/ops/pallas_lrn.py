"""Fused cross-channel LRN as pallas TPU kernels, forward and backward.

The XLA lowering of ACROSS_CHANNELS LRN (ops/lrn.py; reference
lrn_layer.cpp:108-151 / lrn_layer.cu) is a chain of elementwise ops
around a channel-window reduce_window: zero MXU FLOPs, several HBM
round-trips of the full activation. The trace work in PERF.md shows both
flagship CNNs paying it as pure VPU/HBM wall time between the big
matmuls. These kernels do each pass in ONE read and one write of the
activation: the channel-window sum runs over a (C, spatial-tile) VMEM
block as `size` shifted adds along the non-lane axis.

Forward (lrn_layer.cpp:108-133):
    scale = k + alpha/size * sum_{window} x^2,  out = x * scale^-beta
Backward (lrn_layer.cpp:180-204, the cuda CrossChannelBackward):
    dx = g * scale^-beta
       - (2*alpha*beta/size) * x * sum_{mirrored window} g*x*scale^(-beta-1)

The mirrored window: position i contributes to outputs j with
j - half <= i <= j + (size-1-half), so the backward gathers over
offsets [-(size-1-half), +half] — the forward window reversed.

Layout: callers pass NCHW; spatial dims are flattened to one minor axis
and tiled in 512-lane blocks, channels ride the sublane axis where the
shifted adds are cheap register moves. Block padding at the spatial edge
is benign (garbage lanes compute garbage scale and are masked on write).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import _should_interpret

SPATIAL_BLOCK = 512


def _window_sum(t, size, lo):
    """sum over window offsets [-lo, size-1-lo] along axis 0, zero-padded."""
    c = t.shape[0]
    tp = jnp.pad(t, ((lo, size - 1 - lo), (0, 0)))
    out = tp[0:c]
    for d in range(1, size):
        out = out + tp[d:d + c]
    return out


def _fwd_kernel(size, alpha, beta, k, x_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)
    half = (size - 1) // 2
    scale = k + (alpha / size) * _window_sum(x * x, size, half)
    out_ref[0] = (x * scale ** (-beta)).astype(out_ref.dtype)


def _bwd_kernel(size, alpha, beta, k, x_ref, g_ref, dx_ref):
    # scale is recomputed from x (a few VPU adds) rather than saved by the
    # forward: writing an f32 scale tensor would 1.5x the forward's HBM
    # traffic and hold a full f32 activation as a residual
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    half = (size - 1) // 2
    scale = k + (alpha / size) * _window_sum(x * x, size, half)
    t = g * x * scale ** (-beta - 1.0)
    acc = _window_sum(t, size, size - 1 - half)     # mirrored window
    dx = g * scale ** (-beta) - (2.0 * alpha * beta / size) * x * acc
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _call_fwd(x, size, alpha, beta, k, interpret):
    n, c, h, w = x.shape
    xf = x.reshape(n, c, h * w)
    grid = (n, pl.cdiv(h * w, SPATIAL_BLOCK))
    spec = pl.BlockSpec((1, c, SPATIAL_BLOCK), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, size, alpha, beta, k),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
        name="lrn_fwd",
    )(xf)
    return out.reshape(n, c, h, w)


def _call_bwd(x, g, size, alpha, beta, k, interpret):
    n, c, h, w = x.shape
    xf = x.reshape(n, c, h * w)
    gf = g.reshape(n, c, h * w)
    grid = (n, pl.cdiv(h * w, SPATIAL_BLOCK))
    spec = pl.BlockSpec((1, c, SPATIAL_BLOCK), lambda i, j: (i, 0, j))
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, size, alpha, beta, k),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xf.shape, g.dtype),
        interpret=interpret,
        name="lrn_bwd",
    )(xf, gf)
    return dx.reshape(n, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn_across(x, size, alpha, beta, k):
    """Cross-channel LRN on NCHW, fused fwd; exact Caffe semantics."""
    return _call_fwd(x, size, alpha, beta, k, _should_interpret())


def _lrn_fwd(x, size, alpha, beta, k):
    return (_call_fwd(x, size, alpha, beta, k, _should_interpret()), (x,))


def _lrn_bwd(size, alpha, beta, k, res, g):
    (x,) = res
    dx = _call_bwd(x, g, size, alpha, beta, k, _should_interpret())
    return (dx,)


lrn_across.defvjp(_lrn_fwd, _lrn_bwd)
