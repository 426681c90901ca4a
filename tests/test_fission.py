"""Inception concat fission (graph/fission.py): the virtual-concat pass
must be numerically equivalent to the literal graph — same loss, same
gradients — while never materializing inception concats in the hot path."""

import re

import numpy as np
import pytest

import jax

from sparknet_tpu.models.dsl import (
    RDDLayer, ConvolutionLayer, PoolingLayer, ReLULayer, ConcatLayer,
    InnerProductLayer, SoftmaxWithLoss, NetParam)
from sparknet_tpu.graph.compiler import CompiledNet, TRAIN


def _conv(name, bottom, num_output, k, pad=None):
    return ConvolutionLayer(name, [bottom], (k, k), num_output,
                            pad=(pad, pad) if pad else None,
                            weight_filler=dict(type="gaussian", std=0.05),
                            bias_filler=dict(type="constant", value=0.1))


def inception_net(batch=4, stochastic_pool=False):
    """A 2-module inception-ish net: concat consumed by convs AND a pool
    chain, second concat reaching the classifier through global avgpool."""
    pool2 = "STOCHASTIC" if stochastic_pool else "MAX"
    layers = [
        RDDLayer("data", [batch, 8, 16, 16]),
        RDDLayer("label", [batch]),
        _conv("stem", "data", 16, 3, pad=1),
        ReLULayer("relu_stem", ["stem"], tops=["stem"]),
        # module 1
        _conv("b1", "stem", 8, 1),
        _conv("b2", "stem", 12, 3, pad=1),
        PoolingLayer("bp", ["stem"], "MAX", (3, 3), (1, 1), pad=1),
        _conv("bp_proj", "bp", 6, 1),
        ConcatLayer("inc1", ["b1", "b2", "bp_proj"]),
        # module 2 consumes the (virtual) concat: convs + a pooling branch
        _conv("c1", "inc1", 10, 1),
        _conv("c2", "inc1", 14, 3, pad=1),
        PoolingLayer("cp", ["inc1"], pool2, (3, 3), (1, 1), pad=1),
        _conv("cp_proj", "cp", 6, 1),
        ConcatLayer("inc2", ["c1", "c2", "cp_proj"]),
        PoolingLayer("gap", ["inc2"], "AVE", (16, 16), (1, 1)),
        InnerProductLayer("fc", ["gap"], 5,
                          weight_filler=dict(type="gaussian", std=0.1)),
        SoftmaxWithLoss("loss", ["fc", "label"]),
    ]
    return NetParam("fisstest", *layers)


def _loss_and_grads(net_param, on, batch, seed=0):
    net = CompiledNet(net_param, TRAIN)
    net.fission = on
    params, state = net.init(jax.random.PRNGKey(seed))

    def lf(p):
        loss, _ = net.loss_fn(p, state, batch,
                              rng=jax.random.PRNGKey(1))
        return loss
    loss, grads = jax.value_and_grad(lf)(params)
    return float(loss), grads


@pytest.fixture(scope="module")
def batch():
    rs = np.random.RandomState(0)
    return {"data": rs.randn(4, 8, 16, 16).astype(np.float32),
            "label": rs.randint(0, 5, 4)}


def test_fission_matches_literal_graph(batch):
    np_ = inception_net()
    l_on, g_on = _loss_and_grads(np_, True, batch)
    l_off, g_off = _loss_and_grads(np_, False, batch)
    assert np.isfinite(l_on)
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    for lname in g_off:
        for a, b in zip(g_on[lname], g_off[lname]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"grad mismatch: {lname}")


def test_fission_emits_no_module1_concat(batch):
    """With every module-1 consumer fissionable, the compiled training HLO
    contains no concatenate at the module-1 activation shape."""
    net = CompiledNet(inception_net(), TRAIN)
    params, state = net.init(jax.random.PRNGKey(0))

    def lf(p, batch):
        loss, _ = net.loss_fn(p, state, batch,
                              rng=jax.random.PRNGKey(1))
        return loss
    txt = jax.jit(jax.grad(lf)).lower(params, batch).as_text()
    # inc1 is (4,26,16,16); its consumers (two convs + MAX pool->conv) all
    # stay virtual, so no concatenate of that shape may appear fwd or bwd
    assert not re.search(r'\[4,26,16,16\][^=]*concatenate', txt), \
        "module-1 activation concat was materialized"


def test_stochastic_pool_consumer_materializes(batch):
    """STOCHASTIC pooling can't map over branches (its rng stream would
    change); the pass must fall back to the literal concat and still be
    equivalent."""
    np_ = inception_net(stochastic_pool=True)
    l_on, g_on = _loss_and_grads(np_, True, batch)
    l_off, g_off = _loss_and_grads(np_, False, batch)
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    for lname in g_off:
        for a, b in zip(g_on[lname], g_off[lname]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
