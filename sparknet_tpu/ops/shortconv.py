"""Gated short convolution — the mixer of a conv/attention hybrid LM.

sparknet_tpu extension (no CNN-era twin): the layer that three of four
blocks of such a model use in place of attention. Bottom (B, S, E), top
(B, S, E); no bias anywhere.

  [B | C | u] = W_in x          three chunks of E, in that order
  z   = B * u                   the input gate
  c_t = sum_j w[:, j] * z_{t-K+1+j}   depthwise over the channels, causal:
                                the first K-1 positions see zeros
  y   = W_out (C * c)           the output gate, then the out projection

Blobs: W_in (3E, E) | conv (E, K) | W_out (E, E). The taps are filled
uniform(+-1/sqrt(K)) unless `conv_filler` says otherwise (what a depthwise
torch Conv1d of K taps fills unasked).

Scopes inside the layer's own: shortconv_in (the in projection),
shortconv_mix (both gates and the conv: K shifted multiply-adds over
(B, S, E), nothing for the MXU, bound by memory), shortconv_out. The mix is
XLA's: the shifts, the taps and both gates are one elementwise expression
that the compiler fuses, and the layer has no kernel of its own; one
`shortconv.path` record a trace of the layer in the ring of obs/trace.py
says so (`layer`, `kernel` = the taps, `channels`, `form`).
"""

import math

import jax

from ..proto import Message
from ..graph.registry import Layer, register
from ..obs.trace import default_tracer
from .convolution import _param_mults
from .deltanet import causal_depthwise_conv


@register
class ShortConv(Layer):
    type_name = "ShortConv"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        self.p = lp.short_conv_param
        self.embed = int(bottom_shapes[0][-1])
        self.kernel = int(self.p.kernel)
        if self.kernel < 1:
            raise ValueError(f"{lp.name}: short_conv_param.kernel must be "
                             ">= 1")

    def param_shapes(self):
        mults = _param_mults(self.lp, 3)
        wf = self.p.weight_filler if self.p.has("weight_filler") \
            else Message("FillerParameter", type="gaussian", std=0.02)
        lim = 1.0 / math.sqrt(self.kernel)
        taps = self.p.conv_filler if self.p.has("conv_filler") \
            else Message("FillerParameter", type="uniform", min=-lim,
                         max=lim)
        e = self.embed
        return [((3 * e, e), wf, *mults[0]),                # W_in
                ((e, self.kernel), taps, *mults[1]),        # conv
                ((e, e), wf, *mults[2])]                    # W_out

    def out_shapes(self):
        return [tuple(self.bottom_shapes[0])]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        # each weight's cast under the scope that uses it, all three first
        # as before: the traced operations keep their order
        with jax.named_scope("shortconv_in"):
            w_in = params[0].astype(x.dtype)
        with jax.named_scope("shortconv_mix"):
            conv = params[1].astype(x.dtype)
        with jax.named_scope("shortconv_out"):
            w_out = params[2].astype(x.dtype)
        e = self.embed
        tracer = default_tracer()
        now = tracer.now_ns()
        tracer.record("shortconv.path", now, now, layer=self.lp.name,
                      kernel=self.kernel, channels=e,
                      form="xla: the shifts, the taps and both gates fuse "
                           "into one elementwise pass; no kernel of its own")
        with jax.named_scope("shortconv_in"):
            bcu = x @ w_in.T
        with jax.named_scope("shortconv_mix"):
            z = bcu[..., :e] * bcu[..., 2 * e:]
            y = bcu[..., e:2 * e] * causal_depthwise_conv(z, conv)
        with jax.named_scope("shortconv_out"):
            return [y @ w_out.T]
