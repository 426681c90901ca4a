"""Pooling layers with exact Caffe geometry, lowered to XLA reduce_window.

Semantics match reference pooling_layer.cpp:
  * ceil-mode output sizing:  out = ceil((in + 2p - k)/s) + 1, then if padded
    and (out-1)*s >= in + p, out is decremented (pooling_layer.cpp:92-107).
  * MAX ignores padding entirely (window clipped to the real image,
    pooling_layer.cpp:156-161) — realized here by reduce_window's -inf pad.
  * AVE divides by the window area clipped to [start, in + pad) with the RAW
    (possibly negative) start (pooling_layer.cpp:199-203) — divisors are
    position-dependent at borders and computed statically at trace time.
  * STOCHASTIC samples an element proportional to its value in TRAIN and
    takes the value-weighted average in TEST (st_pooling GPU kernels).
SPP (reference spp_layer.cpp:12-56) stacks per-level poolings whose
kernel/pad derive from the input size.
"""

import numpy as np
import jax
from jax import lax
import jax.numpy as jnp

from ..graph.registry import Layer, register

MAX, AVE, STOCHASTIC = 0, 1, 2


def caffe_pool_geometry(pp, in_h, in_w):
    """Resolve kernel/stride/pad + ceil-mode output sizes from a
    PoolingParameter, reproducing pooling_layer.cpp LayerSetUp/Reshape."""
    if pp.global_pooling:
        kh, kw = in_h, in_w
        sh = sw = 1
        ph = pw = 0
    else:
        if pp.has_kernel_size():
            kh = kw = int(pp.kernel_size)
        else:
            kh, kw = int(pp.kernel_h), int(pp.kernel_w)
        if pp.has_stride_h():
            sh, sw = int(pp.stride_h), int(pp.stride_w)
        else:
            sh = sw = int(pp.stride)
        if pp.has_pad_h():
            ph, pw = int(pp.pad_h), int(pp.pad_w)
        else:
            ph = pw = int(pp.pad)
    oh = int(np.ceil((in_h + 2 * ph - kh) / sh)) + 1
    ow = int(np.ceil((in_w + 2 * pw - kw) / sw)) + 1
    if ph or pw:
        if (oh - 1) * sh >= in_h + ph:
            oh -= 1
        if (ow - 1) * sw >= in_w + pw:
            ow -= 1
    return (kh, kw), (sh, sw), (ph, pw), (oh, ow)


def _edge_pad(in_size, k, s, p, out):
    """Right-side padding needed so every (possibly overhanging) ceil-mode
    window lies inside the padded array."""
    return max(0, (out - 1) * s + k - p - in_size)


def _ave_counts(in_size, k, s, p, out):
    """Caffe AVE divisor per output position (raw start, end clipped to in+p)."""
    starts = np.arange(out) * s - p
    ends = np.minimum(starts + k, in_size + p)
    return (ends - starts).astype(np.float32)


def max_pool(x, kernel, stride, pad, out):
    (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out
    n, c, h, w = x.shape
    rh = _edge_pad(h, kh, sh, ph, oh)
    rw = _edge_pad(w, kw, sw, pw, ow)
    return lax.reduce_window(
        x, -np.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else np.iinfo(np.dtype(x.dtype)).min,
        lax.max,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, sh, sw),
        padding=((0, 0), (0, 0), (ph, rh), (pw, rw)),
    )


def ave_pool(x, kernel, stride, pad, out):
    (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out
    n, c, h, w = x.shape
    rh = _edge_pad(h, kh, sh, ph, oh)
    rw = _edge_pad(w, kw, sw, pw, ow)
    sums = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, sh, sw),
        padding=((0, 0), (0, 0), (ph, rh), (pw, rw)),
    )
    counts = np.outer(_ave_counts(h, kh, sh, ph, oh),
                      _ave_counts(w, kw, sw, pw, ow))
    return sums / jnp.asarray(counts, x.dtype)[None, None, :, :]


def _patches(x, kernel, stride, pad, out):
    """(N, C, kh*kw, OH, OW) zero-padded window patches."""
    (kh, kw), (sh, sw), (ph, pw), (oh, ow) = kernel, stride, pad, out
    n, c, h, w = x.shape
    rh = _edge_pad(h, kh, sh, ph, oh)
    rw = _edge_pad(w, kw, sw, pw, ow)
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, rh), (pw, rw)))
    p = lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), [(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # (N, C*kh*kw, OH, OW), channel-major ordering
    return p.reshape(n, c, kh * kw, oh, ow)


def stochastic_pool(x, kernel, stride, pad, out, train, rng):
    p = _patches(x, kernel, stride, pad, out)
    if train:
        logits = jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-30)), -jnp.inf)
        # all-nonpositive windows: fall back to uniform choice over window
        dead = jnp.all(p <= 0, axis=2, keepdims=True)
        logits = jnp.where(dead, jnp.zeros_like(logits), logits)
        from ..parallel import context
        idx = jax.random.categorical(context.shard_key(rng), logits, axis=2)
        return jnp.take_along_axis(p, idx[:, :, None], axis=2)[:, :, 0]
    denom = jnp.sum(p, axis=2)
    num = jnp.sum(p * p, axis=2)
    return jnp.where(denom > 0, num / jnp.maximum(denom, 1e-30),
                     jnp.zeros_like(denom))


@register
class Pooling(Layer):
    type_name = "Pooling"
    needs_rng = True  # only STOCHASTIC actually consumes it

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        pp = lp.pooling_param
        self.method = int(pp.pool)
        n, c, h, w = bottom_shapes[0]
        self.kernel, self.stride, self.pad, self.out = \
            caffe_pool_geometry(pp, h, w)

    def out_shapes(self):
        n, c, h, w = self.bottom_shapes[0]
        return [(n, c) + self.out]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        if self.method == MAX:
            return [max_pool(x, self.kernel, self.stride, self.pad, self.out)]
        if self.method == AVE:
            return [ave_pool(x, self.kernel, self.stride, self.pad, self.out)]
        return [stochastic_pool(x, self.kernel, self.stride, self.pad,
                                self.out, train, rng)]


@register
class SPP(Layer):
    """Spatial pyramid pooling (reference spp_layer.cpp): levels 0..H-1 with
    2^i x 2^i bins each, flattened and concatenated along channels."""

    type_name = "SPP"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        sp = lp.spp_param
        self.method = int(sp.pool)
        self.height = int(sp.pyramid_height)
        n, c, h, w = bottom_shapes[0]
        self.levels = []
        for i in range(self.height):
            bins = 2 ** i
            kh = int(np.ceil(h / bins))
            ph = (kh * bins - h + 1) // 2
            kw = int(np.ceil(w / bins))
            pw = (kw * bins - w + 1) // 2
            self.levels.append(((kh, kw), (kh, kw), (ph, pw), (bins, bins)))

    def out_shapes(self):
        n, c, h, w = self.bottom_shapes[0]
        total = sum(b * b for _, _, _, (b, _) in
                    [(k, s, p, o) for k, s, p, o in self.levels]) * c
        return [(n, total)]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        n = x.shape[0]
        outs = []
        for kernel, stride, pad, out in self.levels:
            if self.method == MAX:
                y = max_pool(x, kernel, stride, pad, out)
            elif self.method == AVE:
                y = ave_pool(x, kernel, stride, pad, out)
            else:
                y = stochastic_pool(x, kernel, stride, pad, out, train, rng)
            outs.append(y.reshape(n, -1))
        return [jnp.concatenate(outs, axis=1)]
