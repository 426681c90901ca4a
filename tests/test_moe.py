"""Switch-MoE layer: routing/capacity math, aux loss, and the
expert-parallel all_to_all path == the single-device path."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from sparknet_tpu.proto import Message
from sparknet_tpu.models import dsl
from sparknet_tpu.graph.compiler import CompiledNet, TRAIN
from sparknet_tpu.parallel import make_mesh, context

from test_layers import make_layer
from sparknet_tpu.parallel.compat import shard_map


def _params(layer, seed=0, scale=0.3):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
            for shape, *_ in layer.param_shapes()]


def _dense_reference(x, params, capacity_factor):
    """All-experts-on-all-tokens reference with the same capacity drop."""
    router, w1, b1, w2, b2 = [np.asarray(p, np.float64) for p in params]
    b, s, e = x.shape
    X = router.shape[0]
    xt = np.asarray(x, np.float64).reshape(-1, e)
    n = len(xt)
    logits = xt @ router.T
    gates = np.exp(logits - logits.max(1, keepdims=True))
    gates /= gates.sum(1, keepdims=True)
    idx = gates.argmax(1)
    import math
    C = max(1, math.ceil(n / X * capacity_factor))
    counts = np.zeros(X, int)
    y = np.zeros_like(xt)
    for i in range(n):
        ex = idx[i]
        if counts[ex] >= C:
            continue                       # dropped token -> zeros
        counts[ex] += 1
        h = np.maximum(w1[ex] @ xt[i] + b1[ex], 0)
        y[i] = (w2[ex] @ h + b2[ex]) * gates[i, ex]
    return y.reshape(b, s, e)


def test_moe_matches_dense_reference():
    layer, _ = make_layer("MoE", [(2, 8, 16)],
                          moe_param=dict(num_experts=4))
    params = _params(layer)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 8, 16), jnp.float32)
    (y,) = layer.apply(params, [x], True, None)
    want = _dense_reference(x, params, 1.25)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)


def test_moe_capacity_drops_overflow():
    # capacity_factor tiny -> C=1: at most one token per expert survives
    layer, _ = make_layer("MoE", [(1, 8, 8)],
                          moe_param=dict(num_experts=2,
                                         capacity_factor=0.25))
    params = _params(layer)
    x = jnp.asarray(np.random.RandomState(2).randn(1, 8, 8), jnp.float32)
    (y,) = layer.apply(params, [x], True, None)
    nonzero_rows = np.abs(np.asarray(y).reshape(8, 8)).sum(1) > 1e-9
    assert nonzero_rows.sum() <= 2


def test_moe_aux_loss_top():
    lp = Message("LayerParameter", name="m", type="MoE",
                 moe_param=dict(num_experts=4))
    lp.top.extend(["m", "m_aux"])
    from sparknet_tpu.graph.registry import get as get_layer
    layer = get_layer("MoE")(lp, [(2, 4, 8)], 0)
    assert layer.out_shapes() == [(2, 4, 8), ()]
    params = _params(layer)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 4, 8), jnp.float32)
    y, aux = layer.apply(params, [x], True, None)
    # balanced uniform routing gives aux ~= 1; any routing gives >= 1
    assert float(aux) >= 1.0 - 1e-5


def test_moe_rejects_single_expert():
    with pytest.raises(ValueError, match="num_experts"):
        make_layer("MoE", [(2, 4, 8)], moe_param=dict(num_experts=1))


def test_moe_expert_parallel_matches_single_device():
    """shard_map over an 8-way "expert" axis (params expert-sharded,
    tokens replicated) == the unsharded forward."""
    layer, _ = make_layer("MoE", [(2, 16, 16)],
                          moe_param=dict(num_experts=8,
                                         expert_parallel=True))
    params = _params(layer, seed=4)
    x = jnp.asarray(np.random.RandomState(5).randn(2, 16, 16), jnp.float32)

    with context.axis_context():            # no expert axis -> local path
        (want,) = layer.apply(params, [x], True, None)

    mesh = make_mesh({"expert": 8})

    def fwd(router, w1, b1, w2, b2, xs):
        (y,) = layer.apply([router, w1, b1, w2, b2], [xs], True, None)
        return y

    with context.axis_context(expert="expert"):
        sharded = jax.jit(shard_map(
            fwd, mesh=mesh,
            in_specs=(P(), P("expert"), P("expert"), P("expert"),
                      P("expert"), P()),
            out_specs=P(), check_vma=False))
        out = sharded(*params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)


def test_moe_expert_parallel_shards_compute():
    """Tokens sharded along the expert axis (the dp-x-ep composition):
    the per-device dispatch buffer must shrink ep-fold vs replicated
    tokens, and the forward must equal the single-device forward."""
    X, EP, E = 8, 8, 16
    # capacity_factor = X so no token can ever overflow, locally or
    # globally -> sharded and unsharded routing are identical
    layer, _ = make_layer("MoE", [(2, 16, E)],
                          moe_param=dict(num_experts=X,
                                         capacity_factor=float(X),
                                         expert_parallel=True))
    params = _params(layer, seed=4)
    x = jnp.asarray(np.random.RandomState(5).randn(2, 16, E), jnp.float32)
    n = 2 * 16

    with context.axis_context():            # single device reference
        (want,) = layer.apply(params, [x], True, None)
    assert layer._last_dispatch_shape == (X, n, E)   # C = n at cf = X

    mesh = make_mesh({"expert": EP})

    def fwd(router, w1, b1, w2, b2, xs):
        (y,) = layer.apply([router, w1, b1, w2, b2], [xs], True, None)
        return y

    with context.axis_context(expert="expert"):
        sharded = jax.jit(shard_map(
            fwd, mesh=mesh,
            in_specs=(P(), P("expert"), P("expert"), P("expert"),
                      P("expert"), P(None, "expert")),   # tokens SHARDED
            out_specs=P(None, "expert"), check_vma=False))
        out = sharded(*params, x)
    # per-device workload: X/EP experts over ep*C_local = n slots = an
    # EP-fold shrink from the replicated-token EP shape (X/EP, EP*n, E)
    assert layer._last_dispatch_shape == (X // EP, n, E)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)


def test_moe_in_transformer_net_trains():
    """MoE as the FFN of a one-block net: loss_fn runs and decreases."""
    from sparknet_tpu.solver.solver import Solver
    net = dsl.NetParam(
        "moe_lm",
        dsl.RDDLayer("data", [2, 8]),
        dsl.RDDLayer("label", [2, 8]),
        dsl.EmbedLayer("emb", ["data"], 32, 16,
                       weight_filler=dict(type="xavier")),
        dsl.LayerNormLayer("ln", ["emb"]),
        dsl.MoELayer("moe", ["ln"], num_experts=4, aux_loss_weight=0.01),
        dsl.EltwiseLayer("res", ["emb", "moe"]),
        dsl.InnerProductLayer("head", ["res"], 32,
                              weight_filler=dict(type="xavier"), axis=2),
        dsl.SoftmaxWithLoss("loss", ["head", "label"], axis=2),
    )
    sp = Message("SolverParameter", base_lr=0.2, lr_policy="fixed",
                 momentum=0.9, display=0, random_seed=0)
    solver = Solver(sp, net_param=net)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 32, (2, 8))
    batch = {"data": toks, "label": (toks + 1) % 32}
    first = float(solver.train_step(batch))
    for _ in range(15):
        last = float(solver.train_step(batch))
    assert last < first - 0.5


def _dense_mask_moe(layer, params, x):
    """The O(n^2) one-hot-mask formulation (reference math, differentiable)
    used to validate the production sort/scatter path's GRADIENTS."""
    import math
    router, w1, b1, w2, b2 = params
    b, s, e = x.shape
    n = b * s
    X = router.shape[0]
    xt = x.reshape(n, e)
    logits = xt @ router.T
    gates = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(gates, axis=-1)
    gate = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(idx, X)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    C = max(1, math.ceil(n / X * layer.capacity_factor))
    keep = (pos < C).astype(jnp.float32)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C) * keep[:, None]
    mask = onehot[:, :, None] * slot[:, None, :]
    xe = jnp.einsum("ne,nxc->xce", xt, mask)
    h = jax.nn.relu(jnp.einsum("xce,xfe->xcf", xe, w1) + b1[:, None, :])
    ye = jnp.einsum("xcf,xef->xce", h, w2) + b2[:, None, :]
    y = jnp.einsum("xce,nxc->ne", ye, mask) * gate[:, None]
    return y.reshape(b, s, e)


def test_moe_gradients_match_dense_mask_formulation():
    """The sort/scatter dispatch must be gradient-equivalent to the dense
    one-hot-mask einsum formulation (same routing, same capacity)."""
    layer, _ = make_layer("MoE", [(2, 6, 8)],
                          moe_param=dict(num_experts=4))
    params = _params(layer, seed=7)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 6, 8), jnp.float32)
    tgt = jnp.asarray(np.random.RandomState(9).randn(2, 6, 8), jnp.float32)

    def loss_prod(ps):
        (y,) = layer.apply(ps, [x], True, None)
        return jnp.sum((y - tgt) ** 2)

    def loss_dense(ps):
        return jnp.sum((_dense_mask_moe(layer, ps, x) - tgt) ** 2)

    gp = jax.grad(loss_prod)(params)
    gd = jax.grad(loss_dense)(params)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4)


# -- the no-drop form: top-k over all experts, a share of them held ----------

def _layer(lp, shapes):
    from sparknet_tpu.graph.registry import get
    return get(lp.type)(lp, shapes, 0)


def _topk_reference(x, params, top_k, first, held, shared):
    """numpy, float64: every token's top_k experts with renormalised
    weights, the held ones applied one pair at a time, nothing dropped."""
    ps = [np.asarray(p, np.float64) for p in params]
    router, wg, wu, wd = ps[:4]
    b, s, e = x.shape
    xt = np.asarray(x, np.float64).reshape(-1, e)
    logits = xt @ router.T
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)

    def silu(a):
        return a / (1.0 + np.exp(-a))
    y = np.zeros_like(xt)
    pairs_here = 0
    for i in range(len(xt)):
        top = np.argsort(-p[i], kind="stable")[:top_k]
        w = p[i, top] / p[i, top].sum()
        for ex, wt in zip(top, w):
            if first <= ex < first + held:
                j = ex - first
                y[i] += wt * (wd[j] @ (silu(wg[j] @ xt[i])
                                       * (wu[j] @ xt[i])))
                pairs_here += 1
    if shared:
        sg, su, sd, gate = ps[4:8]
        opened = 1.0 / (1.0 + np.exp(-(xt @ gate.T)))
        y += opened * ((silu(xt @ sg.T) * (xt @ su.T)) @ sd.T)
    return y.reshape(b, s, e), pairs_here


@pytest.mark.parametrize("top_k,held,first,shared,tile", [
    (2, 8, 0, 0, 4),          # the whole layer, no shared expert
    (4, 8, 0, 16, 128),       # one ragged tile an expert
    (4, 3, 2, 16, 4),         # a share of three experts from the third on
    (8, 8, 0, 16, 8),         # every expert chosen by every token
])
def test_moe_topk_no_drop_matches_pairwise_reference(top_k, held, first,
                                                     shared, tile):
    lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=top_k,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=shared, tile_rows=tile)
    layer = _layer(lp, [(2, 10, 6)])
    params = _params(layer, seed=3)
    assert [p.shape for p in params[:4]] == [(8, 6), (held, 12, 6),
                                             (held, 12, 6), (held, 6, 12)]
    x = jnp.asarray(np.random.RandomState(4).randn(2, 10, 6), jnp.float32)
    want, pairs_here = _topk_reference(x, params, top_k, first, held,
                                       bool(shared))
    assert pairs_here > 0
    np.testing.assert_allclose(
        np.asarray(layer.apply(params, [x], True, None)[0]), want,
        atol=2e-5, rtol=2e-5)


def test_moe_no_drop_under_total_imbalance():
    """Every token's first choice is expert 0: the Switch form with its
    capacity cut would drop most of them, the no-drop form keeps all."""
    lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=2,
                      tile_rows=4)
    layer = _layer(lp, [(2, 10, 6)])
    params = _params(layer, seed=5)
    x = jnp.asarray(np.abs(np.random.RandomState(6).randn(2, 10, 6)) + 0.5,
                    jnp.float32)
    params[0] = params[0].at[0].set(jnp.full((6,), 5.0))
    idx, _ = layer.route(x.reshape(20, 6), params[0])
    assert int(jnp.sum(idx[:, 0] == 0)) == 20
    want, _ = _topk_reference(x, params, 2, 0, 8, False)
    np.testing.assert_allclose(
        np.asarray(layer.apply(params, [x], True, None)[0]), want,
        atol=2e-5, rtol=2e-5)


def test_moe_forms_do_not_mix():
    with pytest.raises(ValueError, match="no-drop form"):
        lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12)
        lp.moe_param.top_k = 2
        _layer(lp, [(2, 10, 6)])
    with pytest.raises(ValueError, match="experts 4..12"):
        _layer(dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=2,
                                experts_held=8, first_expert=4),
                   [(2, 10, 6)])


def test_moe_top1_with_capacity_is_the_switch_form():
    """`top_k = 1` and a capacity factor stay the Switch layer: five blobs
    with biases, tokens over capacity dropped."""
    lp = dsl.MoELayer("moe", ["x"], 4, hidden_dim=8, capacity_factor=0.5)
    layer = _layer(lp, [(1, 16, 6)])
    assert not layer.gated and len(layer.param_shapes()) == 5


# -- ReLU-gated experts, and the router on a second bottom -------------------

def _reglu_loop(g, h, params, top_k, first, held, act=jax.nn.relu):
    """jax.numpy, a loop over the held experts with a mask: the router
    reads h, the experts read g; nothing dropped."""
    router, wg, wu, wd = params
    n = g.shape[0] * g.shape[1]
    gt, ht = g.reshape(n, -1), h.reshape(n, -1)
    p = jax.nn.softmax(jnp.dot(ht, router.T, precision="highest"), -1)
    top, idx = jax.lax.top_k(p, top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    y = jnp.zeros_like(gt)
    for j in range(held):
        weight = jnp.sum(jnp.where(idx == first + j, top, 0.0), -1)
        y = y + weight[:, None] * (
            (act(gt @ wg[j].T) * (gt @ wu[j].T)) @ wd[j].T)
    return y.reshape(g.shape), idx


@pytest.mark.parametrize("held,first,tile,skew", [
    (8, 0, 4, False), (3, 2, 8, False), (8, 0, 4, True), (4, 4, 128, True)])
def test_reglu_experts_routed_from_a_second_bottom(held, first, tile, skew):
    """Output, and the gradients of BOTH bottoms and every blob, against
    the loop; under the skew most tokens' first choice is one expert and
    the windows spill, with nothing dropped."""
    lp = dsl.MoELayer("moe", ["g", "h"], 8, hidden_dim=12, top_k=3,
                      experts_held=held, first_expert=first, tile_rows=tile,
                      expert_activation="relu")
    layer = _layer(lp, [(2, 24, 6), (2, 24, 6)])
    assert layer.act == "relu" and layer.router_bottom
    params = _params(layer, seed=7)
    rs = np.random.RandomState(8)
    g = jnp.asarray(rs.randn(2, 24, 6), jnp.float32)
    h = jnp.asarray(np.abs(rs.randn(2, 24, 6)) + 0.5, jnp.float32)
    if skew:
        params[0] = params[0].at[first].set(jnp.full((6,), 0.5))
    cot = jnp.asarray(rs.randn(2, 24, 6), jnp.float32)
    want, idx = _reglu_loop(g, h, params, 3, first, held)
    if skew:
        assert int(jnp.sum(idx[:, 0] == first)) >= 40
    got = layer.apply(params, [g, h], True, None)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the experts do not read h, the router does not read g
    other = layer.apply(params, [g, h[:, ::-1]], True, None)[0]
    assert np.abs(np.asarray(other - got)).max() > 1e-3
    assert np.abs(np.asarray(layer.apply(
        params, [g, g], True, None)[0] - got)).max() > 1e-3

    def mine(g, h, ps):
        return jnp.sum(layer.apply(ps, [g, h], True, None)[0] * cot)

    def loop(g, h, ps):
        return jnp.sum(_reglu_loop(g, h, ps, 3, first, held)[0] * cot)
    gm = jax.grad(mine, (0, 1, 2))(g, h, params)
    gl = jax.grad(loop, (0, 1, 2))(g, h, params)
    for a, b in zip(jax.tree_util.tree_leaves(gm),
                    jax.tree_util.tree_leaves(gl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)
    # the router's gradient reaches the second bottom, nothing else does
    assert np.abs(np.asarray(gm[1])).max() > 0


def test_relu_and_silu_experts_differ_only_in_the_activation():
    """One bottom, both activations, against the same loop."""
    for act, fn in (("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=2,
                          tile_rows=4, expert_activation=act)
        layer = _layer(lp, [(2, 10, 6)])
        params = _params(layer, seed=9)
        x = jnp.asarray(np.random.RandomState(10).randn(2, 10, 6),
                        jnp.float32)
        want, _ = _reglu_loop(x, x, params, 2, 0, 8, act=fn)
        np.testing.assert_allclose(
            np.asarray(layer.apply(params, [x], True, None)[0]),
            np.asarray(want), atol=2e-5, rtol=2e-5)
        ga = jax.grad(lambda x: jnp.sum(jnp.sin(
            layer.apply(params, [x], True, None)[0])))(x)
        gb = jax.grad(lambda x: jnp.sum(jnp.sin(
            _reglu_loop(x, x, params, 2, 0, 8, act=fn)[0])))(x)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   atol=5e-5, rtol=5e-4)


def test_second_bottom_and_activation_belong_to_the_no_drop_form():
    with pytest.raises(ValueError, match="no-drop form"):
        _layer(dsl.MoELayer("moe", ["x", "h"], 8, hidden_dim=12),
               [(2, 10, 6), (2, 10, 6)])
    with pytest.raises(ValueError, match="silu or relu"):
        _layer(dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=2,
                            expert_activation="gelu"), [(2, 10, 6)])
    with pytest.raises(ValueError, match="router's bottom"):
        _layer(dsl.MoELayer("moe", ["x", "h"], 8, hidden_dim=12, top_k=2),
               [(2, 10, 6), (2, 5, 6)])
    # defaults: an existing layer's prototxt does not name the new fields
    lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=2)
    assert not lp.moe_param.has("expert_activation")
    assert _layer(lp, [(2, 10, 6)]).act == "silu"
    ap = dsl.AttentionLayer("a", ["x"], 2, causal=True)
    assert not ap.attention_param.has("window")


# -- the route's later fields (score_function, selection_bias, topk_eps,
# routed_scaling_factor): unset, the layer is the one it was --------------

def _route_as_it_was(layer, xt, router):
    """`MoE.route` as PR 34 left it, word for word: softmax over all the
    outputs, the top_k of the same numbers, divided by their sum."""
    from jax import lax
    logits = jnp.dot(xt.astype(jnp.float32),
                     router.astype(jnp.float32).T,
                     precision=lax.Precision.HIGHEST)
    top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), layer.top_k)
    if layer.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top


# the two accepted LM cells' layers: (tokens, embed, hidden, experts, top_k,
# held, shared, activation, a second bottom for the router)
ROUTE_SHAPES = {
    "qwen3_next": (16384, 2048, 512, 512, 10, 16, 512, None, False),
    "smallthinker": (32768, 2560, 768, 64, 6, 8, None, "relu", True)}


@pytest.mark.parametrize("cell", list(ROUTE_SHAPES))
def test_route_and_layer_with_the_new_fields_unset_trace_as_before(
        cell, monkeypatch):
    """Jaxpr for jaxpr at both accepted cells' shapes (nothing runs): the
    route, and the whole layer forward and backward, against the route as
    the parent had it; naming the defaults changes nothing either."""
    n, e, f, experts, k, held, shared, act, second = ROUTE_SHAPES[cell]
    bottoms = ["x", "h"] if second else ["x"]
    shapes = [(2, n // 2, e)] * len(bottoms)

    def build(**more):
        return _layer(dsl.MoELayer(
            "moe", bottoms, experts, hidden_dim=f, top_k=k,
            experts_held=held, shared_hidden_dim=shared,
            expert_activation=act, **more), shapes)
    layer = build()
    assert not layer.lp.moe_param.has("score_function")
    assert (layer.score, layer.selection_bias, layer.topk_eps,
            layer.scaling) == ("softmax", False, 0.0, 1.0)
    xt = jax.ShapeDtypeStruct((n, e), jnp.bfloat16)
    router = jax.ShapeDtypeStruct((experts, e), jnp.float32)
    was = str(jax.make_jaxpr(lambda x, r: _route_as_it_was(layer, x, r))(
        xt, router))
    assert str(jax.make_jaxpr(layer.route)(xt, router)) == was

    def whole(layer):
        params = [jax.ShapeDtypeStruct(s[0], jnp.float32)
                  for s in layer.param_shapes()]
        xs = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]

        def loss(params, xs):
            y = layer.apply(params, xs, True, None)[0]
            return jnp.sum(y.astype(jnp.float32))
        return str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, xs))
    now = whole(layer)
    named = build(score_function="softmax", selection_bias=False,
                  topk_eps=0.0, routed_scaling_factor=1.0)
    assert len(named.param_shapes()) == len(layer.param_shapes())
    assert whole(named) == now
    older = build()
    monkeypatch.setattr(
        older, "route",
        lambda xt, router, bias=None: _route_as_it_was(older, xt, router))
    assert whole(older) == now


def test_sigmoid_scores_without_a_bias_and_the_scaling_factor():
    """The score function alone: sigmoid of each logit, top_k of the same
    numbers, (sum + eps) under them, times the factor; the bias blob is
    the last, after a shared expert's."""
    lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=12, top_k=3, tile_rows=4,
                      score_function="sigmoid", topk_eps=1e-6,
                      routed_scaling_factor=1.5, shared_hidden_dim=6,
                      selection_bias=True)
    layer = _layer(lp, [(2, 10, 6)])
    assert [s[0] for s in layer.param_shapes()][-2:] == [(1, 6), (8,)]
    params = _params(layer, seed=11)
    x = jnp.asarray(np.random.RandomState(12).randn(20, 6), jnp.float32)
    idx, top = layer.route(x, params[0])
    score = np.asarray(jax.nn.sigmoid(x @ params[0].T), np.float64)
    want = -np.sort(-score, axis=1)[:, :3]
    np.testing.assert_allclose(
        np.asarray(top), 1.5 * want / (want.sum(1, keepdims=True) + 1e-6),
        rtol=1e-5)
    assert (np.asarray(idx) == np.argsort(-score, axis=1)[:, :3]).all()
    # in the layer the last blob decides who is chosen
    y0 = layer.apply(params, [x.reshape(2, 10, 6)], True, None)[0]
    lifted = params[:-1] + [params[-1].at[5].add(10.0)]
    y1 = layer.apply(lifted, [x.reshape(2, 10, 6)], True, None)[0]
    assert np.abs(np.asarray(y1 - y0)).max() > 1e-3
