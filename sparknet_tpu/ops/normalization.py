"""Normalization layers: BatchNorm (stateful), MVN, LayerNorm and RMSNorm.

BatchNorm matches reference batch_norm_layer.cpp: three non-learnable blobs
[running_mean*s, running_var*s, s] where s is the accumulated scale factor;
use_global_stats defaults to (phase == TEST) (:14-16); TRAIN normalizes by
batch statistics (biased var) and updates the moving blobs with
moving_average_fraction and the m/(m-1) bias correction. Running stats are
framework *state*, threaded functionally through the compiled step rather
than mutated in place.

MVN (mvn_layer.cpp) normalizes each sample (per channel, or across channels)
to zero mean and, optionally, unit variance with divisor (std + eps).

LayerNorm is a sparknet_tpu extension (no CNN-era reference twin): last-axis
normalization with learned gamma/beta, the transformer-block complement of
the Attention layer. Statistics in fp32 regardless of activation dtype (the
bf16 mixed-precision path keeps reductions exact). RMSNorm is its
mean-free, bias-free sibling with one blob, in the zero-centred form
(1 + w) or the plain one; `rms_norm` is also what the attention layer's
query/key norm and the Gated DeltaNet's gated norm call.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..graph.registry import Layer, register


@register
class BatchNorm(Layer):
    type_name = "BatchNorm"
    has_state = True

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.batch_norm_param
        self.eps = float(p.eps)
        self.maf = float(p.moving_average_fraction)
        if p.has("use_global_stats"):
            self.use_global = bool(p.use_global_stats)
        else:
            self.use_global = (phase == 1)  # TEST
        self.channels = bottom_shapes[0][1]

    def state_shapes(self):
        c = self.channels
        return [((c,), 0.0), ((c,), 0.0), ((1,), 0.0)]

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply_stateful(self, params, state, bottoms, train, rng):
        x = bottoms[0]
        mean_b, var_b, scale_b = state
        axes = (0,) + tuple(range(2, x.ndim))
        if self.use_global or not train:
            s = scale_b[0]
            factor = jnp.where(s == 0, 0.0, 1.0 / jnp.where(s == 0, 1.0, s))
            mean = mean_b * factor
            var = var_b * factor
            new_state = state
        else:
            mean = jnp.mean(x, axis=axes)
            var = jnp.mean((x - _bcast(mean, x)) ** 2, axis=axes)
            m = x.size // self.channels
            correction = m / (m - 1) if m > 1 else 1.0
            new_state = [
                self.maf * mean_b + mean,
                self.maf * var_b + correction * var,
                self.maf * scale_b + 1.0,
            ]
        inv = 1.0 / jnp.sqrt(var + self.eps)
        y = (x - _bcast(mean, x)) * _bcast(inv, x)
        return [y], new_state


def _bcast(v, x):
    shape = [1] * x.ndim
    shape[1] = v.shape[0]
    return v.reshape(shape)


@register
class LayerNorm(Layer):
    type_name = "LayerNorm"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.layer_norm_param
        self.eps = float(p.eps)
        self.affine = bool(int(p.affine))
        self.dim = int(bottom_shapes[0][-1])

    def param_shapes(self):
        if not self.affine:
            return []
        from ..proto import Message
        from .convolution import _param_mults
        mults = _param_mults(self.lp, 2)
        ones = Message("FillerParameter", type="constant", value=1.0)
        return [((self.dim,), ones, *mults[0]),          # gamma
                ((self.dim,), None, *mults[1])]          # beta (zeros)

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
        y = (xf - mean) / jnp.sqrt(var + self.eps)
        if self.affine:
            y = y * params[0].astype(jnp.float32) \
                + params[1].astype(jnp.float32)
        return [y.astype(x.dtype)]


def rms_norm(x, w, eps, zero_centered=True):
    """x / sqrt(mean(x^2) + eps) * (1 + w) over the last axis (or * w when
    not zero_centered), computed in float32, returned in x's type."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    return (y * (1.0 + wf if zero_centered else wf)).astype(x.dtype)


@register
class RMSNorm(Layer):
    """sparknet_tpu extension: last-axis RMS norm with one learned blob,
    no mean subtraction and no bias. `zero_centered` (the default) is the
    form y = x / rms(x) * (1 + w) with w filled with 0; otherwise
    y = x / rms(x) * w with w filled with 1. Statistics in float32."""

    type_name = "RMSNorm"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.rms_norm_param
        self.eps = float(p.eps)
        self.zero_centered = bool(int(p.zero_centered))
        self.dim = int(bottom_shapes[0][-1])

    def param_shapes(self):
        from ..proto import Message
        from .convolution import _param_mults
        fill = Message("FillerParameter", type="constant",
                       value=0.0 if self.zero_centered else 1.0)
        return [((self.dim,), fill, *_param_mults(self.lp, 1)[0])]

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        return [rms_norm(bottoms[0], params[0], self.eps,
                         self.zero_centered)]


@register
class MVN(Layer):
    type_name = "MVN"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.mvn_param
        self.normalize_variance = bool(p.normalize_variance)
        self.across_channels = bool(p.across_channels)
        self.eps = float(p.eps)

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        axes = tuple(range(1, x.ndim)) if self.across_channels \
            else tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        y = x - mean
        if self.normalize_variance:
            std = jnp.sqrt(jnp.mean(y * y, axis=axes, keepdims=True))
            y = y / (std + self.eps)
        return [y]
