"""Neuron (elementwise) layers — XLA fuses these into adjacent matmul/conv
HLOs, so each is a plain jnp expression (replaces the per-op CUDA kernels in
reference neuron layers, e.g. relu_layer.cu, dropout_layer.cu).
"""

import jax
import jax.numpy as jnp

from ..graph.registry import Layer, register
from ..proto.message import Message


class _Elementwise(Layer):
    def out_shapes(self):
        return [self.bottom_shapes[0]]


@register
class ReLU(_Elementwise):
    type_name = "ReLU"

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        slope = self.lp.relu_param.negative_slope if self.lp.has("relu_param") \
            else 0.0
        if slope:
            return [jnp.where(x > 0, x, slope * x)]
        return [jnp.maximum(x, 0)]


@register
class PReLU(_Elementwise):
    """Learned negative slope (reference prelu_layer.cpp); slope blob is per
    channel, or a single scalar when channel_shared."""

    type_name = "PReLU"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.prelu_param
        self.shared = bool(p.channel_shared)
        self.channels = bottom_shapes[0][1] if len(bottom_shapes[0]) > 1 else 1
        self.filler = p.filler if p.has("filler") else \
            Message("FillerParameter", type="constant", value=0.25)

    def param_shapes(self):
        from .convolution import _param_mults
        shape = (1,) if self.shared else (self.channels,)
        (m,) = _param_mults(self.lp, 1)
        return [(shape, self.filler, *m)]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        slope = params[0].astype(x.dtype)
        if not self.shared:
            bshape = [1] * x.ndim
            bshape[1] = self.channels
            slope = slope.reshape(bshape)
        return [jnp.maximum(x, 0) + slope * jnp.minimum(x, 0)]


@register
class Sigmoid(_Elementwise):
    type_name = "Sigmoid"

    def apply(self, params, bottoms, train, rng):
        return [jax.nn.sigmoid(bottoms[0])]


@register
class TanH(_Elementwise):
    type_name = "TanH"

    def apply(self, params, bottoms, train, rng):
        return [jnp.tanh(bottoms[0])]


@register
class BNLL(_Elementwise):
    """log(1 + exp(x)), computed stably (reference bnll_layer.cpp)."""

    type_name = "BNLL"

    def apply(self, params, bottoms, train, rng):
        return [jax.nn.softplus(bottoms[0])]


@register
class AbsVal(_Elementwise):
    type_name = "AbsVal"

    def apply(self, params, bottoms, train, rng):
        return [jnp.abs(bottoms[0])]


@register
class Power(_Elementwise):
    """(shift + scale * x) ^ power (reference power_layer.cpp)."""

    type_name = "Power"

    def apply(self, params, bottoms, train, rng):
        p = self.lp.power_param
        y = p.shift + p.scale * bottoms[0]
        if p.power == 1.0:
            return [y]
        return [y ** p.power]


@register
class Exp(_Elementwise):
    """base^(shift + scale*x); base -1 means e (reference exp_layer.cpp)."""

    type_name = "Exp"

    def apply(self, params, bottoms, train, rng):
        p = self.lp.exp_param
        inner = p.shift + p.scale * bottoms[0]
        if p.base == -1.0:
            return [jnp.exp(inner)]
        return [jnp.asarray(p.base, bottoms[0].dtype) ** inner]


@register
class Log(_Elementwise):
    """log_base(shift + scale*x) (reference log_layer.cpp)."""

    type_name = "Log"

    def apply(self, params, bottoms, train, rng):
        p = self.lp.log_param
        y = jnp.log(p.shift + p.scale * bottoms[0])
        if p.base != -1.0:
            y = y / jnp.log(jnp.asarray(p.base, bottoms[0].dtype))
        return [y]


@register
class Threshold(_Elementwise):
    """x > threshold ? 1 : 0 (reference threshold_layer.cpp)."""

    type_name = "Threshold"

    def apply(self, params, bottoms, train, rng):
        t = self.lp.threshold_param.threshold
        x = bottoms[0]
        return [(x > t).astype(x.dtype)]


@register
class Dropout(_Elementwise):
    """Inverted dropout (reference dropout_layer.cpp): TRAIN scales kept
    units by 1/(1-ratio); TEST is identity."""

    type_name = "Dropout"
    needs_rng = True

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        if not train:
            return [x]
        ratio = self.lp.dropout_param.dropout_ratio
        keep = 1.0 - ratio
        # one shard of a data-parallel step keeps its rows of the global
        # batch's draw: the mesh step equals the one-device step
        from ..parallel import context
        mask = context.shard_rows(
            lambda k, shape: jax.random.bernoulli(k, keep, shape),
            rng, x.shape)
        return [jnp.where(mask, x / keep, 0).astype(x.dtype)]
