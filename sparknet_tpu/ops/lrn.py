"""Local Response Normalization, both norm regions.

Reference lrn_layer.cpp:
  ACROSS_CHANNELS (:108-151): scale = k + (alpha/n) * sum_{window n over C} x^2,
    zero-padded at the channel edges; out = x * scale^-beta.
  WITHIN_CHANNEL (:28-62, :155-162): out = x * (1 + alpha * s)^-beta where s is
    an AVE-pool of x^2 with kernel local_size, stride 1, pad (n-1)/2 — using
    Caffe AVE pooling's pad-inclusive divisor, which this reuses from ops.pooling.
"""

import os

from jax import lax
import jax.numpy as jnp

from ..graph.registry import Layer, register
from ..obs.trace import kernel_import


# XLA:TPU rewrites convolutions whose batch is under 8 with its
# space-to-batch pass, and that pass carries its layout on through the
# pool into this channel-window sum and gets the shape wrong (libtpu
# 0.0.34: CaffeNet's TEST forward is refused at batch 1-7 with "Binary op
# with incompatible shapes f32[..,96] and f32[..,92]", the train step
# aborts the compiler in space_to_batch_converter.cc). A barrier in front
# of the window sum stops the propagation; batches of 8 and more never
# enter the pass and keep their program as it was.
# tests/test_tpu_compile.py::test_caffenet_forward_small_batch_compiles
_S2B_BATCH = 8


def _lrn_mode():
    # read the env var here (NOT via pallas_lrn.lrn_mode) so the default
    # xla path never imports pallas/mosaic at all
    return os.environ.get("SPARKNET_LRN", "xla").lower()
from .pooling import ave_pool, caffe_pool_geometry
from ..proto.message import Message


@register
class LRN(Layer):
    type_name = "LRN"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.lrn_param
        self.size = int(p.local_size)
        self.alpha = float(p.alpha)
        self.beta = float(p.beta)
        self.k = float(p.k)
        self.within = int(p.norm_region) == 1
        if self.within:
            pp = Message("PoolingParameter", pool="AVE",
                         kernel_size=self.size, stride=1,
                         pad=(self.size - 1) // 2)
            n, c, h, w = bottom_shapes[0]
            self.pool_geom = caffe_pool_geometry(pp, h, w)

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        if self.within:
            kernel, stride, pad, out = self.pool_geom
            s = ave_pool(x * x, kernel, stride, pad, out)
            scale = 1.0 + self.alpha * s
        elif x.ndim == 4 and _lrn_mode() == "pallas":
            with kernel_import("sparknet_tpu.ops.pallas_lrn"):
                from .pallas_lrn import lrn_across
            return [lrn_across(x, self.size, self.alpha, self.beta, self.k)]
        else:
            half = (self.size - 1) // 2
            sq = x * x
            if x.shape[0] < _S2B_BATCH:
                sq = lax.optimization_barrier(sq)
            ssum = lax.reduce_window(
                sq, 0.0, lax.add,
                window_dimensions=(1, self.size, 1, 1),
                window_strides=(1, 1, 1, 1),
                padding=((0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)),
            )
            scale = self.k + (self.alpha / self.size) * ssum
        return [x * scale ** (-self.beta)]
