"""Convolution-family layers lowered to XLA's conv HLO.

Replaces Caffe's im2col+GEMM path (reference base_conv_layer.cpp,
util/im2col.cpp) with ``lax.conv_general_dilated`` — XLA tiles the conv
directly onto the MXU, so there is no materialized im2col buffer and no
hand-written GEMM. Grouped convolution (AlexNet conv2/4/5) maps to
``feature_group_count``. A grouped conv runs NHWC and every other conv
NCHW: the feature-group split tiles along the minor (lane) axis, which
measured +13% on CaffeNet (earlier rig, PERF.md section 6); the layer sees
its own ``group``, so nothing selects the layout from outside.

Shape/param semantics match reference conv_layer.cpp / base_conv_layer.cpp:
  out = (in + 2*pad - kernel) / stride + 1      (floor)
  weight blob (num_output, C/group, kh, kw), bias blob (num_output,)
Deconvolution is the conv transpose (reference deconv_layer.cpp):
  out = stride * (in - 1) + kernel - 2*pad
with weight blob (C_in, num_output/group, kh, kw).
"""

from jax import lax

from ..graph.registry import Layer, register


def _pair(rep_field, h_field, w_field, lp_param, default):
    """Resolve Caffe's (repeated | _h/_w) spatial-param convention."""
    rep = list(rep_field)
    if lp_param.has(h_field) or lp_param.has(w_field):
        return int(getattr(lp_param, h_field)), int(getattr(lp_param, w_field))
    if len(rep) == 0:
        return default, default
    if len(rep) == 1:
        return int(rep[0]), int(rep[0])
    return int(rep[0]), int(rep[1])


def resolve_conv_geometry(cp):
    kh, kw = _pair(cp.kernel_size, "kernel_h", "kernel_w", cp, None)
    if kh is None:
        raise ValueError("convolution requires kernel_size")
    sh, sw = _pair(cp.stride, "stride_h", "stride_w", cp, 1)
    ph, pw = _pair(cp.pad, "pad_h", "pad_w", cp, 0)
    return (kh, kw), (sh, sw), (ph, pw)


def _param_mults(lp, n_blobs):
    """Per-blob (lr_mult, decay_mult) from the layer's ParamSpecs
    (reference net.cpp AppendParam; missing specs default to 1/1)."""
    out = []
    for i in range(n_blobs):
        if i < len(lp.param):
            out.append((lp.param[i].lr_mult, lp.param[i].decay_mult))
        else:
            out.append((1.0, 1.0))
    return out


@register
class Convolution(Layer):
    type_name = "Convolution"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        cp = lp.convolution_param
        self.cp = cp
        (self.kh, self.kw), (self.sh, self.sw), (self.ph, self.pw) = \
            resolve_conv_geometry(cp)
        self.group = int(cp.group)
        self.num_output = int(cp.num_output)
        self.bias_term = bool(cp.bias_term)
        n, c, h, w = bottom_shapes[0]
        if c % self.group or self.num_output % self.group:
            raise ValueError("channels must divide group")
        self.weight_shape = (self.num_output, c // self.group, self.kh, self.kw)

    def param_shapes(self):
        mults = _param_mults(self.lp, 2 if self.bias_term else 1)
        out = [(self.weight_shape, self.cp.weight_filler, *mults[0])]
        if self.bias_term:
            out.append(((self.num_output,), self.cp.bias_filler, *mults[1]))
        return out

    def out_shapes(self):
        n, c, h, w = self.bottom_shapes[0]
        oh = (h + 2 * self.ph - self.kh) // self.sh + 1
        ow = (w + 2 * self.pw - self.kw) // self.sw + 1
        return [(n, self.num_output, oh, ow)]

    def apply_raw(self, params, bottoms, train, rng):
        """The convolution WITHOUT its bias add, NCHW out. The fused-
        epilogue path (graph/compiler.py + ops/pallas_epilogue.py) calls
        this and applies bias+ReLU(+LRN) in one pallas pass."""
        x = bottoms[0]
        w = params[0].astype(x.dtype)
        nhwc = self.group > 1
        if nhwc:
            x, w = x.transpose(0, 2, 3, 1), w.transpose(2, 3, 1, 0)
        y = lax.conv_general_dilated(
            x, w,
            window_strides=(self.sh, self.sw),
            padding=[(self.ph, self.ph), (self.pw, self.pw)],
            dimension_numbers=("NHWC", "HWIO", "NHWC") if nhwc
            else ("NCHW", "OIHW", "NCHW"),
            feature_group_count=self.group,
        )
        if nhwc:
            y = y.transpose(0, 3, 1, 2)
        return y

    def apply(self, params, bottoms, train, rng):
        y = self.apply_raw(params, bottoms, train, rng)
        if self.bias_term:
            y = y + params[1].astype(y.dtype)[None, :, None, None]
        return [y]

    def apply_fissioned(self, params, branches, train, rng):
        """conv over a virtual concat (graph/fission.py): one partial conv
        per branch with the matching input-channel slice of the SAME
        weight blob, summed; bias added once. group==1 only, so NCHW."""
        w = params[0]
        y = None
        off = 0
        for x in branches.parts:
            c = x.shape[1]
            wi = w[:, off:off + c].astype(x.dtype)
            off += c
            yi = lax.conv_general_dilated(
                x, wi,
                window_strides=(self.sh, self.sw),
                padding=[(self.ph, self.ph), (self.pw, self.pw)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            y = yi if y is None else y + yi
        if self.bias_term:
            y = y + params[1].astype(y.dtype)[None, :, None, None]
        return y


@register
class Deconvolution(Layer):
    type_name = "Deconvolution"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        cp = lp.convolution_param
        self.cp = cp
        (self.kh, self.kw), (self.sh, self.sw), (self.ph, self.pw) = \
            resolve_conv_geometry(cp)
        self.group = int(cp.group)
        self.num_output = int(cp.num_output)
        self.bias_term = bool(cp.bias_term)
        n, c, h, w = bottom_shapes[0]
        self.in_channels = c
        self.weight_shape = (c, self.num_output // self.group, self.kh, self.kw)

    def param_shapes(self):
        mults = _param_mults(self.lp, 2 if self.bias_term else 1)
        out = [(self.weight_shape, self.cp.weight_filler, *mults[0])]
        if self.bias_term:
            out.append(((self.num_output,), self.cp.bias_filler, *mults[1]))
        return out

    def out_shapes(self):
        n, c, h, w = self.bottom_shapes[0]
        oh = self.sh * (h - 1) + self.kh - 2 * self.ph
        ow = self.sw * (w - 1) + self.kw - 2 * self.pw
        return [(n, self.num_output, oh, ow)]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        g, o_g = self.group, self.num_output // self.group
        c_g = self.in_channels // g
        w = params[0].astype(x.dtype)
        # (C_in, O/g, kh, kw) -> (O, C_in/g, kh, kw), spatially flipped:
        # forward deconv == gradient of the corresponding forward conv.
        w = w.reshape(g, c_g, o_g, self.kh, self.kw)
        w = w.transpose(0, 2, 1, 3, 4).reshape(self.num_output, c_g,
                                               self.kh, self.kw)
        w = w[:, :, ::-1, ::-1]
        y = lax.conv_general_dilated(
            x, w,
            window_strides=(1, 1),
            padding=[(self.kh - 1 - self.ph,) * 2, (self.kw - 1 - self.pw,) * 2],
            lhs_dilation=(self.sh, self.sw),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=g,
        )
        if self.bias_term:
            y = y + params[1].astype(x.dtype)[None, :, None, None]
        return [y]


@register
class Im2col(Layer):
    """Explicit im2col as a layer (reference im2col_layer.cpp) — rarely used,
    kept for parity; XLA does not need it for convs."""

    type_name = "Im2col"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        (self.kh, self.kw), (self.sh, self.sw), (self.ph, self.pw) = \
            resolve_conv_geometry(lp.convolution_param)

    def out_shapes(self):
        n, c, h, w = self.bottom_shapes[0]
        oh = (h + 2 * self.ph - self.kh) // self.sh + 1
        ow = (w + 2 * self.pw - self.kw) // self.sw + 1
        return [(n, c * self.kh * self.kw, oh, ow)]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        patches = lax.conv_general_dilated_patches(
            x, (self.kh, self.kw), (self.sh, self.sw),
            [(self.ph, self.ph), (self.pw, self.pw)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        return [patches]
