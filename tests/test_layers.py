"""Per-layer forward-correctness + numerical gradient checks.

The TPU-native analog of the reference's GradientChecker harness
(test_gradient_check_util.hpp:19): every differentiable layer's jax.grad is
compared against central finite differences, and forwards are checked against
straightforward numpy re-computations of the Caffe formulas (pooling's
ceil-mode/pad-divisor corner cases hand-derived from pooling_layer.cpp).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.proto import Message
from sparknet_tpu.graph.registry import get as get_layer

RNG = np.random.RandomState(0)


def make_layer(type_name, bottom_shapes, phase=0, **layer_fields):
    lp = Message("LayerParameter", name="t", type=type_name, **layer_fields)
    cls = get_layer(type_name)
    return cls(lp, bottom_shapes, phase), lp


def init_params(layer, seed=0):
    rng = jax.random.PRNGKey(seed)
    out = []
    for i, (shape, filler, lr, dc) in enumerate(layer.param_shapes()):
        k = jax.random.fold_in(rng, i)
        out.append(0.1 * jax.random.normal(k, shape))
    return out


def numeric_grad(f, x, step=1e-2):
    """Central-difference gradient of scalar f at x (mirrors the reference
    checker's two-sided estimate, test_gradient_check_util.hpp:160-171)."""
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    flat = x.ravel()
    gflat = g.ravel()
    for i in range(flat.size):
        old = flat[i]
        flat[i] = old + step
        fp = float(f(jnp.asarray(x, jnp.float32)))
        flat[i] = old - step
        fm = float(f(jnp.asarray(x, jnp.float32)))
        flat[i] = old
        gflat[i] = (fp - fm) / (2 * step)
    return g


def check_grad(f, x, step=1e-2, tol=2e-2):
    analytic = np.asarray(jax.grad(lambda v: f(v))(jnp.asarray(x, jnp.float32)))
    numeric = numeric_grad(f, x, step)
    scale = max(1.0, np.abs(numeric).max())
    np.testing.assert_allclose(analytic, numeric, atol=tol * scale,
                               err_msg="analytic vs numeric gradient")


def direct_conv(x, w, b, stride, pad, group=1):
    """The Caffe formula tap by tap: y[n, o, p, q] = b[o] + sum over
    (c, i, j) of x[n, c, p*s + i - pad, q*s + j - pad] * w[o, c, i, j],
    each group over its own channels. No conv primitive, so jax.grad of
    it is a gradient reference too."""
    n, c, h, wd = x.shape
    o, cg, kh, kw = w.shape
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    xp = xp.reshape(n, group, cg, *xp.shape[2:])
    wg = w.reshape(group, o // group, cg, kh, kw)
    y = 0.0
    for i in range(kh):
        for j in range(kw):
            win = xp[:, :, :, i:i + stride * (oh - 1) + 1:stride,
                     j:j + stride * (ow - 1) + 1:stride]
            y = y + jnp.einsum("ngchw,goc->ngohw", win, wg[:, :, :, i, j],
                               precision="highest")
    return y.reshape(n, o, oh, ow) + b[None, :, None, None]


# (in_shape, num_output, kernel, stride, pad, group): the stem convs of the
# two benchmark CNNs, CaffeNet's grouped conv2, and odd strided corners
CONV_GEOMETRIES = [
    pytest.param((2, 3, 5, 5), 4, 3, 1, 1, 1, id="k3s1p1"),
    pytest.param((2, 3, 227, 227), 8, 11, 4, 0, 1, id="caffenet-conv1"),
    pytest.param((2, 3, 224, 224), 8, 7, 2, 3, 1, id="googlenet-conv1"),
    pytest.param((1, 3, 33, 33), 4, 5, 3, 2, 1, id="odd-k5s3p2"),
    pytest.param((2, 8, 27, 27), 16, 5, 1, 2, 2, id="caffenet-conv2-group2"),
    pytest.param((1, 4, 16, 16), 4, 4, 4, 0, 1, id="k-divisible-by-s"),
    pytest.param((1, 2, 15, 17), 3, 3, 2, 1, 1, id="rect-input"),
]


def _conv_case(in_shape, num_output, k, s, p, group):
    layer, _ = make_layer(
        "Convolution", [in_shape],
        convolution_param=dict(num_output=num_output, kernel_size=[k],
                               stride=[s], pad=[p], group=group))
    params = init_params(layer)
    x = jnp.asarray(np.random.RandomState(3).randn(*in_shape), jnp.float32)
    return layer, params, x


class TestConvolution:
    @pytest.mark.parametrize("in_shape,num_output,k,s,p,group",
                             CONV_GEOMETRIES)
    def test_forward_matches_direct(self, in_shape, num_output, k, s, p,
                                    group):
        layer, params, x = _conv_case(in_shape, num_output, k, s, p, group)
        (y,) = layer.apply(params, [x], False, None)
        assert y.shape == tuple(layer.out_shapes()[0])
        want = direct_conv(x, params[0], params[1], s, p, group)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("in_shape,num_output,k,s,p,group",
                             CONV_GEOMETRIES[1:5])
    def test_gradients_match_direct(self, in_shape, num_output, k, s, p,
                                    group):
        layer, params, x = _conv_case(in_shape, num_output, k, s, p, group)

        def loss(conv):
            def f(w, b, xv):
                y = conv(w, b, xv)
                return (y * jnp.cos(jnp.arange(y.size, dtype=jnp.float32)
                                    .reshape(y.shape))).sum()
            return f

        got = jax.grad(loss(lambda w, b, xv: layer.apply(
            [w, b], [xv], False, None)[0]), argnums=(0, 1, 2))(*params, x)
        want = jax.grad(loss(lambda w, b, xv: direct_conv(
            xv, w, b, s, p, group)), argnums=(0, 1, 2))(*params, x)
        assert got[0].shape == params[0].shape
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("group,lhs_spec", [(1, (0, 1, 2, 3)),
                                                (2, (0, 3, 1, 2))])
    def test_layout_follows_the_group(self, group, lhs_spec):
        """The layer picks its layout from its own ``group``: a grouped
        conv lowers NHWC (batch 0, features 3, spatial 1-2), any other
        NCHW. Read from the jaxpr's dimension numbers."""
        layer, params, x = _conv_case((1, 4, 8, 8), 6, 3, 1, 1, group)
        jaxpr = jax.make_jaxpr(
            lambda w, xv: layer.apply_raw([w], [xv], False, None))(
                params[0], x)
        (conv,) = [e for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "conv_general_dilated"]
        dn = conv.params["dimension_numbers"]
        assert tuple(dn.lhs_spec) == lhs_spec
        assert tuple(dn.out_spec) == lhs_spec
        assert conv.params["feature_group_count"] == group

    def test_grouped(self):
        layer, _ = make_layer(
            "Convolution", [(1, 4, 4, 4)],
            convolution_param=dict(num_output=6, kernel_size=[3], group=2))
        params = init_params(layer)
        assert params[0].shape == (6, 2, 3, 3)
        x = jnp.asarray(RNG.randn(1, 4, 4, 4), jnp.float32)
        (y,) = layer.apply(params, [x], False, None)
        assert y.shape == (1, 6, 2, 2)
        # group 0 outputs depend only on channels 0-1
        x2 = x.at[:, 2:].set(0.0)
        (y2,) = layer.apply(params, [x2], False, None)
        np.testing.assert_allclose(y[:, :3], y2[:, :3], rtol=1e-5)

    def test_rect_kernel_stride(self):
        layer, _ = make_layer(
            "Convolution", [(1, 2, 8, 9)],
            convolution_param=dict(num_output=3, kernel_h=3, kernel_w=2,
                                   stride_h=2, stride_w=3, pad_h=1, pad_w=0))
        assert layer.out_shapes() == [(1, 3, 4, 3)]

    def test_gradcheck(self):
        layer, _ = make_layer(
            "Convolution", [(1, 2, 4, 4)],
            convolution_param=dict(num_output=2, kernel_size=[3], pad=[1]))
        params = init_params(layer)
        x = np.asarray(0.5 * RNG.randn(1, 2, 4, 4), np.float32)
        check_grad(lambda v: layer.apply(params, [v], False, None)[0].sum(), x)
        check_grad(lambda w: layer.apply([w, params[1]],
                                         [jnp.asarray(x)], False, None)[0].sum(),
                   np.asarray(params[0]))


class TestDeconvolution:
    def test_shape_and_inverse_of_conv(self):
        layer, _ = make_layer(
            "Deconvolution", [(1, 3, 4, 4)],
            convolution_param=dict(num_output=2, kernel_size=[4], stride=[2],
                                   pad=[1]))
        assert layer.out_shapes() == [(1, 2, 8, 8)]
        params = init_params(layer)
        x = jnp.asarray(RNG.randn(1, 3, 4, 4), jnp.float32)
        (y,) = layer.apply(params, [x], False, None)
        assert y.shape == (1, 2, 8, 8)

    def test_gradcheck(self):
        layer, _ = make_layer(
            "Deconvolution", [(1, 2, 3, 3)],
            convolution_param=dict(num_output=2, kernel_size=[2], stride=[2]))
        params = init_params(layer)
        x = np.asarray(0.5 * RNG.randn(1, 2, 3, 3), np.float32)
        check_grad(lambda v: layer.apply(params, [v], False, None)[0].sum(), x)


class TestPooling:
    def test_ceil_mode_sizing(self):
        # CIFAR pool1: 32x32, k3 s2 -> ceil((32-3)/2)+1 = 16
        layer, _ = make_layer("Pooling", [(1, 1, 32, 32)],
                              pooling_param=dict(pool="MAX", kernel_size=3,
                                                 stride=2))
        assert layer.out_shapes() == [(1, 1, 16, 16)]
        # AlexNet pool5: 13x13 k3 s2 -> ceil(10/2)+1 = 6
        layer, _ = make_layer("Pooling", [(1, 1, 13, 13)],
                              pooling_param=dict(pool="MAX", kernel_size=3,
                                                 stride=2))
        assert layer.out_shapes() == [(1, 1, 6, 6)]

    def test_pad_clip_rule(self):
        # in=4, k=3, s=2, p=1: ceil((4+2-3)/2)+1 = 3; (3-1)*2=4 < 4+1 -> keep 3
        layer, _ = make_layer("Pooling", [(1, 1, 4, 4)],
                              pooling_param=dict(pool="AVE", kernel_size=3,
                                                 stride=2, pad=1))
        assert layer.out_shapes() == [(1, 1, 3, 3)]
        # in=2, k=2, s=2, p=1: ceil((2+2-2)/2)+1 = 2; (2-1)*2=2 >= 2+1? no -> 2
        layer, _ = make_layer("Pooling", [(1, 1, 2, 2)],
                              pooling_param=dict(pool="AVE", kernel_size=2,
                                                 stride=2, pad=1))
        assert layer.out_shapes() == [(1, 1, 2, 2)]

    def test_max_ignores_padding(self):
        layer, _ = make_layer("Pooling", [(1, 1, 2, 2)],
                              pooling_param=dict(pool="MAX", kernel_size=2,
                                                 stride=2, pad=1))
        x = -jnp.ones((1, 1, 2, 2))  # all negative; pad must not win
        (y,) = layer.apply([], [x], False, None)
        assert float(y.max()) == -1.0

    def test_ave_divisor_includes_pad(self):
        # caffe AVE: divisor = raw window clipped to in+pad
        layer, _ = make_layer("Pooling", [(1, 1, 3, 3)],
                              pooling_param=dict(pool="AVE", kernel_size=3,
                                                 stride=2, pad=1))
        x = jnp.ones((1, 1, 3, 3))
        (y,) = layer.apply([], [x], False, None)
        # out position (0,0): window rows/cols [-1,2): 2 real rows of 3-col
        # window... divisor = (min(-1+3, 3+1) - (-1))^2 = 3^2 = 9, sum = 4
        np.testing.assert_allclose(y[0, 0, 0, 0], 4.0 / 9.0, rtol=1e-6)
        # center (1,1): window [1,4) clip->[1,3) real sum 4; divisor:
        # (min(1+3,4)-1)=3 per axis -> 9
        np.testing.assert_allclose(y[0, 0, 1, 1], 4.0 / 9.0, rtol=1e-6)

    def test_ave_matches_numpy_nopad(self):
        layer, _ = make_layer("Pooling", [(2, 3, 6, 6)],
                              pooling_param=dict(pool="AVE", kernel_size=2,
                                                 stride=2))
        x = RNG.randn(2, 3, 6, 6).astype(np.float32)
        (y,) = layer.apply([], [jnp.asarray(x)], False, None)
        want = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
        np.testing.assert_allclose(y, want, rtol=1e-5)

    def test_global_pooling(self):
        layer, _ = make_layer("Pooling", [(2, 5, 7, 7)],
                              pooling_param=dict(pool="AVE",
                                                 global_pooling=True))
        assert layer.out_shapes() == [(2, 5, 1, 1)]
        x = RNG.randn(2, 5, 7, 7).astype(np.float32)
        (y,) = layer.apply([], [jnp.asarray(x)], False, None)
        np.testing.assert_allclose(y[:, :, 0, 0], x.mean(axis=(2, 3)),
                                   rtol=1e-5)

    def test_stochastic_train_and_test(self):
        layer, _ = make_layer("Pooling", [(1, 1, 4, 4)],
                              pooling_param=dict(pool="STOCHASTIC",
                                                 kernel_size=2, stride=2))
        x = jnp.abs(jnp.asarray(RNG.randn(1, 1, 4, 4), jnp.float32)) + 0.1
        (y,) = layer.apply([], [x], True, jax.random.PRNGKey(0))
        # every sampled value must be one of the window members
        xa = np.asarray(x).reshape(2, 2, 2, 2)
        for i in range(2):
            for j in range(2):
                win = np.asarray(x)[0, 0, 2*i:2*i+2, 2*j:2*j+2].ravel()
                assert float(y[0, 0, i, j]) in [float(v) for v in win]
        (yt,) = layer.apply([], [x], False, None)
        xs = np.asarray(x)
        for i in range(2):
            for j in range(2):
                win = xs[0, 0, 2*i:2*i+2, 2*j:2*j+2].ravel()
                np.testing.assert_allclose(
                    yt[0, 0, i, j], (win ** 2).sum() / win.sum(), rtol=1e-5)

    @pytest.mark.parametrize("method", ["MAX", "AVE"])
    def test_gradcheck(self, method):
        layer, _ = make_layer("Pooling", [(1, 2, 4, 4)],
                              pooling_param=dict(pool=method, kernel_size=3,
                                                 stride=2, pad=1))
        # distinct values keep max-pool away from ties
        x = np.arange(32, dtype=np.float32).reshape(1, 2, 4, 4) / 7.0
        x += 0.01 * RNG.randn(*x.shape).astype(np.float32)
        check_grad(lambda v: (layer.apply([], [v], False, None)[0]
                              * jnp.arange(18.0).reshape(1, 2, 3, 3)).sum(),
                   x, step=1e-3)


class TestLRN:
    def test_across_channels_formula(self):
        layer, _ = make_layer("LRN", [(1, 5, 2, 2)],
                              lrn_param=dict(local_size=3, alpha=0.1,
                                             beta=0.75))
        x = RNG.rand(1, 5, 2, 2).astype(np.float32)
        (y,) = layer.apply([], [jnp.asarray(x)], False, None)
        # channel 2 at (0,0): window channels 1..3
        s = 1.0 + (0.1 / 3) * (x[0, 1:4, 0, 0] ** 2).sum()
        np.testing.assert_allclose(y[0, 2, 0, 0], x[0, 2, 0, 0] * s ** -0.75,
                                   rtol=1e-5)
        # edge channel 0: window channels 0..1 (zero padded below)
        s0 = 1.0 + (0.1 / 3) * (x[0, 0:2, 0, 0] ** 2).sum()
        np.testing.assert_allclose(y[0, 0, 0, 0], x[0, 0, 0, 0] * s0 ** -0.75,
                                   rtol=1e-5)

    def test_within_channel_formula(self):
        # CIFAR-full config: local_size 3, WITHIN_CHANNEL
        layer, _ = make_layer("LRN", [(1, 1, 3, 3)],
                              lrn_param=dict(local_size=3, alpha=5e-5,
                                             beta=0.75,
                                             norm_region="WITHIN_CHANNEL"))
        x = RNG.rand(1, 1, 3, 3).astype(np.float32)
        (y,) = layer.apply([], [jnp.asarray(x)], False, None)
        # center: full 3x3 window, AVE divisor 9
        s = 1.0 + 5e-5 * ((x[0, 0] ** 2).sum() / 9.0)
        np.testing.assert_allclose(y[0, 0, 1, 1], x[0, 0, 1, 1] * s ** -0.75,
                                   rtol=1e-5)
        # corner (0,0): window [-1,2)x[-1,2) -> 4 real values, divisor 9
        sc = 1.0 + 5e-5 * ((x[0, 0, :2, :2] ** 2).sum() / 9.0)
        np.testing.assert_allclose(y[0, 0, 0, 0], x[0, 0, 0, 0] * sc ** -0.75,
                                   rtol=1e-5)

    @pytest.mark.parametrize("region", ["ACROSS_CHANNELS", "WITHIN_CHANNEL"])
    def test_gradcheck(self, region):
        layer, _ = make_layer("LRN", [(1, 4, 3, 3)],
                              lrn_param=dict(local_size=3, alpha=0.05,
                                             beta=0.75, norm_region=region))
        x = np.asarray(RNG.randn(1, 4, 3, 3), np.float32)
        wts = jnp.asarray(RNG.rand(1, 4, 3, 3), jnp.float32)
        check_grad(lambda v: (layer.apply([], [v], False, None)[0]
                              * wts).sum(), x, step=1e-2)


class TestInnerProduct:
    def test_forward_and_axis(self):
        layer, _ = make_layer("InnerProduct", [(2, 3, 4, 4)],
                              inner_product_param=dict(num_output=7))
        params = init_params(layer)
        assert params[0].shape == (7, 48)
        x = RNG.randn(2, 3, 4, 4).astype(np.float32)
        (y,) = layer.apply(params, [jnp.asarray(x)], False, None)
        want = x.reshape(2, 48) @ np.asarray(params[0]).T + np.asarray(params[1])
        np.testing.assert_allclose(y, want, rtol=1e-4)

    def test_gradcheck(self):
        layer, _ = make_layer("InnerProduct", [(2, 5)],
                              inner_product_param=dict(num_output=3))
        params = init_params(layer)
        x = np.asarray(RNG.randn(2, 5), np.float32)
        check_grad(lambda v: layer.apply(params, [v], False, None)[0].sum(), x)
        check_grad(lambda w: layer.apply([w, params[1]], [jnp.asarray(x)],
                                         False, None)[0].sum(),
                   np.asarray(params[0]))


class TestActivations:
    def test_relu_and_leaky(self):
        layer, _ = make_layer("ReLU", [(2, 3)])
        x = jnp.asarray([[-1.0, 0.0, 2.0], [3.0, -4.0, 5.0]])
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, [[0, 0, 2], [3, 0, 5]])
        layer, _ = make_layer("ReLU", [(2, 3)],
                              relu_param=dict(negative_slope=0.1))
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, [[-0.1, 0, 2], [3, -0.4, 5]], rtol=1e-6)

    def test_prelu(self):
        layer, _ = make_layer("PReLU", [(2, 3, 2, 2)])
        params = [jnp.asarray([0.1, 0.2, 0.3])]
        x = -jnp.ones((2, 3, 2, 2))
        (y,) = layer.apply(params, [x], False, None)
        np.testing.assert_allclose(y[0, :, 0, 0], [-0.1, -0.2, -0.3],
                                   rtol=1e-6)

    def test_dropout_train_test(self):
        layer, _ = make_layer("Dropout", [(1000,)],
                              dropout_param=dict(dropout_ratio=0.3))
        x = jnp.ones((1000,))
        (y,) = layer.apply([], [x], True, jax.random.PRNGKey(0))
        kept = float((y > 0).mean())
        assert abs(kept - 0.7) < 0.05
        np.testing.assert_allclose(np.asarray(y)[np.asarray(y) > 0],
                                   1.0 / 0.7, rtol=1e-5)
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, x)

    def test_power_exp_log_bnll_threshold_absval(self):
        x = jnp.asarray([[0.5, 1.0, 2.0]])
        layer, _ = make_layer("Power", [(1, 3)],
                              power_param=dict(power=2.0, scale=3.0,
                                               shift=1.0))
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, (1 + 3 * np.asarray(x)) ** 2, rtol=1e-5)
        layer, _ = make_layer("Exp", [(1, 3)],
                              exp_param=dict(base=2.0))
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, 2.0 ** np.asarray(x), rtol=1e-5)
        layer, _ = make_layer("Log", [(1, 3)])
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, np.log(np.asarray(x)), rtol=1e-5)
        layer, _ = make_layer("BNLL", [(1, 3)])
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, np.log1p(np.exp(np.asarray(x))),
                                   rtol=1e-5)
        layer, _ = make_layer("Threshold", [(1, 3)],
                              threshold_param=dict(threshold=0.75))
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(y, [[0.0, 1.0, 1.0]])
        layer, _ = make_layer("AbsVal", [(1, 3)])
        (y,) = layer.apply([], [-x], False, None)
        np.testing.assert_allclose(y, x)

    @pytest.mark.parametrize("ltype", ["Sigmoid", "TanH", "BNLL", "PReLU"])
    def test_gradcheck(self, ltype):
        layer, _ = make_layer(ltype, [(2, 3)])
        params = init_params(layer)
        x = np.asarray(RNG.randn(2, 3), np.float32) + 0.2
        check_grad(lambda v: (layer.apply(params, [v], False, None)[0]
                              * jnp.asarray([[1., 2, 3], [4, 5, 6]])).sum(), x)


class TestBatchNorm:
    def test_train_normalizes_and_updates_state(self):
        layer, _ = make_layer("BatchNorm", [(4, 3, 2, 2)])
        state = [jnp.zeros(3), jnp.zeros(3), jnp.zeros(1)]
        x = jnp.asarray(RNG.randn(4, 3, 2, 2) * 2 + 1, jnp.float32)
        (y,), st = layer.apply_stateful([], state, [x], True,
                                        jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(y).mean(axis=(0, 2, 3)), 0,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).var(axis=(0, 2, 3)), 1,
                                   atol=1e-3)
        np.testing.assert_allclose(st[2], [1.0])
        m = 16
        np.testing.assert_allclose(
            st[1], np.asarray(x).var(axis=(0, 2, 3)) * m / (m - 1), rtol=1e-4)

    def test_global_stats(self):
        layer, _ = make_layer("BatchNorm", [(4, 2, 1, 1)], phase=1)
        assert layer.use_global
        mean = jnp.asarray([1.0, 2.0])
        var = jnp.asarray([4.0, 9.0])
        state = [mean * 2, var * 2, jnp.asarray([2.0])]  # scale factor 2
        x = jnp.zeros((4, 2, 1, 1))
        (y,), st = layer.apply_stateful([], state, [x], False, None)
        want = (0 - np.asarray(mean)) / np.sqrt(np.asarray(var) + 1e-5)
        np.testing.assert_allclose(y[0, :, 0, 0], want, rtol=1e-4)


class TestStructural:
    def test_softmax(self):
        layer, _ = make_layer("Softmax", [(2, 5)])
        x = RNG.randn(2, 5).astype(np.float32)
        (y,) = layer.apply([], [jnp.asarray(x)], False, None)
        e = np.exp(x - x.max(1, keepdims=True))
        np.testing.assert_allclose(y, e / e.sum(1, keepdims=True), rtol=1e-5)

    def test_concat_slice_roundtrip(self):
        a = jnp.asarray(RNG.randn(2, 3, 2, 2), jnp.float32)
        b = jnp.asarray(RNG.randn(2, 5, 2, 2), jnp.float32)
        layer, _ = make_layer("Concat", [(2, 3, 2, 2), (2, 5, 2, 2)])
        (y,) = layer.apply([], [a, b], False, None)
        assert y.shape == (2, 8, 2, 2)
        lp = Message("LayerParameter", name="s", type="Slice",
                     top=["t1", "t2"], slice_param=dict(slice_point=[3]))
        sl = get_layer("Slice")(lp, [(2, 8, 2, 2)], 0)
        t1, t2 = sl.apply([], [y], False, None)
        np.testing.assert_allclose(t1, a)
        np.testing.assert_allclose(t2, b)

    def test_flatten_reshape(self):
        layer, _ = make_layer("Flatten", [(2, 3, 4, 5)])
        assert layer.out_shapes() == [(2, 60)]
        layer, _ = make_layer(
            "Reshape", [(2, 8)],
            reshape_param=dict(shape=dict(dim=[0, 2, -1])))
        assert layer.out_shapes() == [(2, 2, 4)]
        layer, _ = make_layer(
            "Reshape", [(2, 8)],
            reshape_param=dict(shape=dict(dim=[2, 4]), axis=1))
        assert layer.out_shapes() == [(2, 2, 4)]

    def test_eltwise(self):
        a = jnp.asarray([[1.0, 2]])
        b = jnp.asarray([[3.0, 4]])
        for op, want in [("PROD", [[3, 8]]), ("SUM", [[4, 6]]),
                         ("MAX", [[3, 4]])]:
            layer, _ = make_layer("Eltwise", [(1, 2), (1, 2)],
                                  eltwise_param=dict(operation=op))
            (y,) = layer.apply([], [a, b], False, None)
            np.testing.assert_allclose(y, want)
        layer, _ = make_layer("Eltwise", [(1, 2), (1, 2)],
                              eltwise_param=dict(operation="SUM",
                                                 coeff=[2.0, -1.0]))
        (y,) = layer.apply([], [a, b], False, None)
        np.testing.assert_allclose(y, [[-1, 0]])

    def test_tile_argmax_reduction(self):
        layer, _ = make_layer("Tile", [(2, 3)], tile_param=dict(tiles=2))
        (y,) = layer.apply([], [jnp.asarray([[1., 2, 3], [4, 5, 6]])],
                           False, None)
        assert y.shape == (2, 6)
        layer, _ = make_layer("ArgMax", [(2, 4)])
        (y,) = layer.apply([], [jnp.asarray([[1., 9, 2, 3], [7, 1, 8, 2]])],
                           False, None)
        np.testing.assert_allclose(y[:, 0, 0], [1, 2])
        layer, _ = make_layer("Reduction", [(2, 3)],
                              reduction_param=dict(operation="MEAN", axis=1,
                                                   coeff=2.0))
        (y,) = layer.apply([], [jnp.asarray([[1., 2, 3], [4, 5, 6]])],
                           False, None)
        np.testing.assert_allclose(y, [4.0, 10.0])

    def test_embed_batchreindex(self):
        layer, _ = make_layer("Embed", [(4,)],
                              embed_param=dict(num_output=3, input_dim=5))
        params = init_params(layer)
        idx = jnp.asarray([0, 2, 4, 2])
        (y,) = layer.apply(params, [idx], False, None)
        np.testing.assert_allclose(
            y, np.asarray(params[0])[np.asarray(idx)] + np.asarray(params[1]),
            rtol=1e-5)
        layer, _ = make_layer("BatchReindex", [(3, 2), (4,)])
        (y,) = layer.apply([], [jnp.asarray([[1., 1], [2, 2], [3, 3]]),
                                jnp.asarray([2, 0, 1, 1])], False, None)
        np.testing.assert_allclose(y[:, 0], [3, 1, 2, 2])

    def test_mvn(self):
        layer, _ = make_layer("MVN", [(2, 3, 4, 4)])
        x = jnp.asarray(RNG.randn(2, 3, 4, 4) * 3 + 2, jnp.float32)
        (y,) = layer.apply([], [x], False, None)
        np.testing.assert_allclose(np.asarray(y).mean(axis=(2, 3)), 0,
                                   atol=1e-5)
        std = np.asarray(y).std(axis=(2, 3))
        np.testing.assert_allclose(std, 1.0, atol=1e-2)


class TestLosses:
    def test_softmax_loss_uniform(self):
        layer, _ = make_layer("SoftmaxWithLoss", [(4, 10), (4,)])
        x = jnp.zeros((4, 10))
        lab = jnp.asarray([1, 2, 3, 4])
        (loss,) = layer.apply([], [x, lab], True, None)
        np.testing.assert_allclose(loss, np.log(10), rtol=1e-5)

    def test_softmax_loss_spatial_and_ignore(self):
        lp = Message("LayerParameter", type="SoftmaxWithLoss",
                     loss_param=dict(ignore_label=255))
        layer = get_layer("SoftmaxWithLoss")(lp, [(2, 3, 2, 2), (2, 2, 2)], 0)
        x = jnp.asarray(RNG.randn(2, 3, 2, 2), jnp.float32)
        lab = np.zeros((2, 2, 2), np.int32)
        lab[1, 1, 1] = 255
        (loss,) = layer.apply([], [x, jnp.asarray(lab)], True, None)
        # manual
        xs = np.asarray(x)
        e = np.exp(xs - xs.max(1, keepdims=True))
        p = e / e.sum(1, keepdims=True)
        total, cnt = 0.0, 0
        for i in range(2):
            for h in range(2):
                for w in range(2):
                    if lab[i, h, w] == 255:
                        continue
                    total -= np.log(p[i, lab[i, h, w], h, w])
                    cnt += 1
        np.testing.assert_allclose(loss, total / cnt, rtol=1e-5)

    def test_softmax_loss_gradcheck(self):
        layer, _ = make_layer("SoftmaxWithLoss", [(3, 5), (3,)])
        lab = jnp.asarray([0, 2, 4])
        x = np.asarray(RNG.randn(3, 5), np.float32)
        check_grad(lambda v: layer.apply([], [v, lab], True, None)[0], x)

    def test_euclidean(self):
        layer, _ = make_layer("EuclideanLoss", [(4, 3), (4, 3)])
        a = jnp.asarray(RNG.randn(4, 3), jnp.float32)
        b = jnp.asarray(RNG.randn(4, 3), jnp.float32)
        (loss,) = layer.apply([], [a, b], True, None)
        np.testing.assert_allclose(
            loss, ((np.asarray(a) - np.asarray(b)) ** 2).sum() / 8, rtol=1e-5)
        x = np.asarray(a)
        check_grad(lambda v: layer.apply([], [v, b], True, None)[0], x)

    def test_hinge_l1(self):
        layer, _ = make_layer("HingeLoss", [(2, 3), (2,)])
        x = jnp.asarray([[2.0, -1.0, 0.5], [0.0, 3.0, -2.0]])
        lab = jnp.asarray([0, 1])
        (loss,) = layer.apply([], [x, lab], True, None)
        # i=0: margins max(0, 1 + [-2, -1... wait sign: correct class
        # negated: [1-2, 1-1+... manual:
        m0 = [max(0, 1 - 2.0), max(0, 1 + -1.0), max(0, 1 + 0.5)]
        m1 = [max(0, 1 + 0.0), max(0, 1 - 3.0), max(0, 1 + -2.0)]
        np.testing.assert_allclose(loss, (sum(m0) + sum(m1)) / 2, rtol=1e-5)

    def test_sigmoid_ce(self):
        layer, _ = make_layer("SigmoidCrossEntropyLoss", [(3, 4), (3, 4)])
        x = jnp.asarray(RNG.randn(3, 4), jnp.float32)
        t = jnp.asarray(RNG.rand(3, 4) > 0.5, jnp.float32)
        (loss,) = layer.apply([], [x, t], True, None)
        p = 1 / (1 + np.exp(-np.asarray(x)))
        want = -(np.asarray(t) * np.log(p) +
                 (1 - np.asarray(t)) * np.log(1 - p)).sum() / 3
        np.testing.assert_allclose(loss, want, rtol=1e-4)
        check_grad(lambda v: layer.apply([], [v, t], True, None)[0],
                   np.asarray(x))

    def test_multinomial_and_infogain_identity(self):
        probs = jnp.asarray(RNG.dirichlet(np.ones(4), size=3), jnp.float32)
        lab = jnp.asarray([0, 1, 2])
        layer, _ = make_layer("MultinomialLogisticLoss", [(3, 4), (3,)])
        (loss,) = layer.apply([], [probs, lab], True, None)
        want = -np.log(np.asarray(probs)[np.arange(3), [0, 1, 2]]).sum() / 3
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        # Infogain with identity H == multinomial logistic
        lp = Message("LayerParameter", type="InfogainLoss")
        ig = get_layer("InfogainLoss")(lp, [(3, 4), (3,), (4, 4)], 0)
        (loss2,) = ig.apply([], [probs, lab, jnp.eye(4)], True, None)
        np.testing.assert_allclose(loss2, want, rtol=1e-5)

    def test_contrastive(self):
        a = jnp.asarray(RNG.randn(4, 3), jnp.float32)
        b = jnp.asarray(RNG.randn(4, 3), jnp.float32)
        y = jnp.asarray([1, 0, 1, 0], jnp.float32)
        layer, _ = make_layer("ContrastiveLoss", [(4, 3), (4, 3), (4,)],
                              contrastive_loss_param=dict(margin=2.0))
        (loss,) = layer.apply([], [a, b, y], True, None)
        d = np.asarray(a) - np.asarray(b)
        dsq = (d ** 2).sum(1)
        ya = np.asarray(y)
        want = (ya * dsq + (1 - ya) *
                np.maximum(2.0 - np.sqrt(dsq), 0) ** 2).sum() / 8
        np.testing.assert_allclose(loss, want, rtol=1e-5)

    def test_accuracy_topk(self):
        x = jnp.asarray([[0.1, 0.9, 0.0, 0.0],
                         [0.5, 0.1, 0.4, 0.0],
                         [0.0, 0.2, 0.3, 0.5]])
        lab = jnp.asarray([1, 2, 0])
        layer, _ = make_layer("Accuracy", [(3, 4), (3,)])
        (acc,) = layer.apply([], [x, lab], False, None)
        np.testing.assert_allclose(acc, 1.0 / 3.0, rtol=1e-6)
        layer, _ = make_layer("Accuracy", [(3, 4), (3,)],
                              accuracy_param=dict(top_k=2))
        (acc,) = layer.apply([], [x, lab], False, None)
        np.testing.assert_allclose(acc, 2.0 / 3.0, rtol=1e-6)


class TestAttention:
    """The long-context extension layer (ops/attention.py): shape, causal
    masking, and gradient correctness vs central differences."""

    def _layer(self, b=2, s=8, e=12, heads=3, causal=True):
        return make_layer("Attention", [(b, s, e)],
                          attention_param=dict(num_heads=heads,
                                               causal=causal))

    def test_forward_shape_and_causality(self):
        layer, _ = self._layer()
        params = init_params(layer)
        x = jnp.asarray(RNG.randn(2, 8, 12), jnp.float32)
        (y,) = layer.apply(params, [x], False, None)
        assert y.shape == (2, 8, 12)
        # causality: perturbing a LATER position must not change earlier rows
        x2 = np.asarray(x).copy()
        x2[:, 5] += 10.0
        (y2,) = layer.apply(params, [jnp.asarray(x2)], False, None)
        np.testing.assert_allclose(np.asarray(y)[:, :5],
                                   np.asarray(y2)[:, :5], atol=1e-5)
        assert not np.allclose(np.asarray(y)[:, 5:], np.asarray(y2)[:, 5:])

    def test_gradient_wrt_input(self):
        layer, _ = self._layer(b=1, s=4, e=6, heads=2)
        params = init_params(layer)
        x = 0.5 * RNG.randn(1, 4, 6)

        def f(v):
            (y,) = layer.apply(params, [v], True, None)
            return jnp.sum(y * jnp.asarray(WEIGHTS_A[: y.size]
                                           .reshape(y.shape)))
        check_grad(f, x, step=1e-3, tol=2e-2)

    def test_gradient_wrt_qkv_weight(self):
        layer, _ = self._layer(b=1, s=4, e=6, heads=2)
        params = init_params(layer)
        x = jnp.asarray(0.5 * RNG.randn(1, 4, 6), jnp.float32)

        def f(w):
            (y,) = layer.apply([w] + params[1:], [x], True, None)
            return jnp.sum(y * jnp.asarray(WEIGHTS_A[: y.size]
                                           .reshape(y.shape)))
        check_grad(f, np.asarray(params[0]), step=1e-3, tol=2e-2)


WEIGHTS_A = np.linspace(-1.0, 1.0, 4096).astype(np.float32)


# -- Filter (capacity-padded semantics; see ops/structural.py) -------------

def _filter_layer(bottom_shapes, ntops, name="filt"):
    lp = Message("LayerParameter", name=name, type="Filter")
    lp.bottom.extend([f"b{i}" for i in range(len(bottom_shapes))])
    lp.top.extend([f"t{i}" for i in range(ntops)])
    return get_layer("Filter")(lp, bottom_shapes, 0)


def test_filter_compacts_selected_rows_and_zero_pads():
    layer = _filter_layer([(5, 3), (5,), (5, 1)], 2)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(5, 3), jnp.float32)
    z = jnp.asarray(rs.randn(5), jnp.float32)
    sel = jnp.asarray([1.0, 0.0, 1.0, 0.0, 1.0]).reshape(5, 1)
    tx, tz = layer.apply([], [x, z, sel], True, None)
    # selected rows 0,2,4 compacted to the front in order; tail zeros
    np.testing.assert_allclose(np.asarray(tx[:3]),
                               np.asarray(x)[[0, 2, 4]])
    np.testing.assert_allclose(np.asarray(tx[3:]), 0.0)
    np.testing.assert_allclose(np.asarray(tz[:3]),
                               np.asarray(z)[[0, 2, 4]])
    np.testing.assert_allclose(np.asarray(tz[3:]), 0.0)
    # full-batch (padded) static shapes
    assert tx.shape == (5, 3) and tz.shape == (5,)


def test_filter_valid_count_top():
    layer = _filter_layer([(4, 2), (4,)], 2)   # data top + count top
    assert layer.out_shapes() == [(4, 2), ()]
    sel = jnp.asarray([0.0, 1.0, 1.0, 0.0])
    _, cnt = layer.apply([], [jnp.zeros((4, 2)), sel], True, None)
    assert int(cnt) == 2


def test_filter_gradients_scatter_to_selected_rows():
    """Autodiff through the compaction == filter_layer.cpp Backward_cpu:
    cotangents land on selected rows, zero elsewhere."""
    layer = _filter_layer([(4, 3), (4,)], 2)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(4, 3), jnp.float32)
    sel = jnp.asarray([1.0, 0.0, 0.0, 1.0])
    w = jnp.asarray(rs.randn(4, 3), jnp.float32)

    def f(x):
        y, _ = layer.apply([], [x, sel], True, None)
        return jnp.sum(y * w)

    g = np.asarray(jax.grad(f)(x))
    want = np.zeros((4, 3), np.float32)
    want[0] = np.asarray(w)[0]        # row 0 -> slot 0
    want[3] = np.asarray(w)[1]        # row 3 -> slot 1
    np.testing.assert_allclose(g, want, atol=1e-6)


def test_filter_shape_validation():
    with pytest.raises(ValueError, match="singletons"):
        _filter_layer([(4, 3), (4, 2)], 1)
    with pytest.raises(ValueError, match="batch"):
        _filter_layer([(3, 3), (4,)], 1)
    with pytest.raises(ValueError, match="tops"):
        _filter_layer([(4, 3), (4,)], 3 + 1)


def test_filter_compiles_in_a_net():
    """Filter inside a CompiledNet: static shapes end to end."""
    from sparknet_tpu.models import dsl
    from sparknet_tpu.graph.compiler import CompiledNet, TRAIN
    lp = Message("LayerParameter", name="filt", type="Filter")
    lp.bottom.extend(["x", "sel"])
    lp.top.extend(["xf", "nvalid"])
    npm = dsl.NetParam("t", dsl.RDDLayer("x", [4, 3]),
                       dsl.RDDLayer("sel", [4]), lp)
    net = CompiledNet(npm, TRAIN)
    params, state = net.init(jax.random.PRNGKey(0))
    blobs, _ = net.apply(params, state,
                         {"x": np.ones((4, 3), np.float32),
                          "sel": np.asarray([1, 0, 1, 0], np.float32)},
                         train=True)
    assert blobs["xf"].shape == (4, 3)
    assert int(blobs["nvalid"]) == 2
