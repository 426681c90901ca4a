"""Per-block rematerialization (SPARKNET_REMAT): gradient-exact.

jax.checkpoint over the zoo's "block{i}/" layer runs trades backward
FLOPs for activation memory; it must not change a single value — loss,
gradients, updated params, BN state — versus the unwrapped graph.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.proto import Message
from sparknet_tpu.models import zoo
from sparknet_tpu.graph.compiler import CompiledNet, TRAIN
from sparknet_tpu.solver.solver import Solver


def _lm_net():
    return zoo.transformer_lm(vocab_size=48, seq_len=32, batch_size=2,
                              d_model=24, num_layers=2, num_heads=2,
                              flash=False)


def _batch():
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 48, (2, 33))
    return {"data": toks[:, :-1], "label": toks[:, 1:]}


def test_remat_groups_follow_block_prefixes():
    net = CompiledNet(_lm_net(), TRAIN)
    groups = net._remat_groups()
    assert groups, "transformer blocks should form remat segments"
    for lo, hi in groups.items():
        names = [net.layers[i][0].name for i in range(lo, hi)]
        prefixes = {n.split("/")[0] for n in names}
        assert len(prefixes) == 1 and hi - lo >= 2, names


def test_remat_loss_and_grads_exact(monkeypatch):
    net = CompiledNet(_lm_net(), TRAIN)
    params, state = net.init(jax.random.PRNGKey(0))
    batch = _batch()
    rng = jax.random.PRNGKey(7)

    def loss(p, on):
        monkeypatch.setenv("SPARKNET_REMAT", "1" if on else "0")
        l, (blobs, st) = net.loss_fn(p, state, batch, rng=rng)
        return l

    l_off, g_off = jax.value_and_grad(lambda p: loss(p, False))(params)
    l_on, g_on = jax.value_and_grad(lambda p: loss(p, True))(params)
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        g_on, g_off)


def test_remat_solver_step_matches(monkeypatch):
    def run(on):
        monkeypatch.setenv("SPARKNET_REMAT", "1" if on else "0")
        sp = Message("SolverParameter", base_lr=0.1, lr_policy="fixed",
                     momentum=0.9, display=0, random_seed=0)
        s = Solver(sp, net_param=_lm_net())
        losses = [float(s.train_step(_batch())) for _ in range(3)]
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_remat_keeps_bn_state_updates(monkeypatch):
    # a conv/BN net whose layer names use the "/" convention so a remat
    # segment CONTAINS stateful BatchNorm layers
    from sparknet_tpu.models import dsl
    net_param = dsl.NetParam(
        "bnblock",
        dsl.RDDLayer("data", [2, 3, 8, 8]),
        dsl.RDDLayer("label", [2]),
        dsl.ConvolutionLayer("blk/conv", ["data"], (3, 3), 4, pad=(1, 1),
                             weight_filler=dict(type="xavier")),
        dsl.BatchNormLayer("blk/bn", ["blk/conv"]),
        dsl.ReLULayer("blk/relu", ["blk/bn"], tops=["blk/bn"]),
        dsl.InnerProductLayer("ip", ["blk/bn"], 5,
                              weight_filler=dict(type="xavier")),
        dsl.SoftmaxWithLoss("loss", ["ip", "label"]),
    )
    rs = np.random.RandomState(1)
    batch = {"data": rs.randn(2, 3, 8, 8).astype(np.float32),
             "label": rs.randint(0, 5, 2)}

    def step(on):
        monkeypatch.setenv("SPARKNET_REMAT", "1" if on else "0")
        net = CompiledNet(net_param, TRAIN)
        params, state = net.init(jax.random.PRNGKey(0))
        blobs, new_state = net.apply(params, state, batch, train=True)
        return new_state

    s_on, s_off = step(True), step(False)
    assert set(s_on) == set(s_off)
    for k in s_on:
        for a, b in zip(s_on[k], s_off[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_remat_off_for_eval_keeps_all_blobs(monkeypatch):
    monkeypatch.setenv("SPARKNET_REMAT", "1")
    net = CompiledNet(_lm_net(), TRAIN)
    params, state = net.init(jax.random.PRNGKey(0))
    blobs, _ = net.apply(params, state, _batch(), train=False)
    # eval ignores remat: every internal block blob stays inspectable
    assert any(k.startswith("block0/") for k in blobs)


def test_remat_no_stale_pre_segment_blob(monkeypatch):
    """A blob produced BEFORE a remat segment and overwritten in-place
    inside it must be absent from the returned dict, not stale: returning
    the pre-segment value would silently hand callers wrong data."""
    from sparknet_tpu.models import dsl

    def _renamed_top(lp, top):
        lp.clear("top")
        lp.top.append(top)
        return lp

    net_param = dsl.NetParam(
        "stale",
        dsl.RDDLayer("data", [2, 8]),
        dsl.RDDLayer("label", [2, 8]),
        dsl.EmbedLayer("emb", ["data"], 16, 8,
                       weight_filler=dict(type="xavier")),
        # "x" is produced BEFORE the segment, then blk/ip re-tops it and
        # blk/relu overwrites it in-place inside the "blk/" remat segment
        _renamed_top(dsl.InnerProductLayer(
            "pre", ["emb"], 8, weight_filler=dict(type="xavier"), axis=2),
            "x"),
        _renamed_top(dsl.InnerProductLayer(
            "blk/ip", ["x"], 8, weight_filler=dict(type="xavier"), axis=2),
            "x"),
        dsl.ReLULayer("blk/relu", ["x"], tops=["x"]),
        dsl.InnerProductLayer("blk/head", ["x"], 16,
                              weight_filler=dict(type="xavier"), axis=2),
        dsl.SoftmaxWithLoss("loss", ["blk/head", "label"], axis=2),
    )
    net = CompiledNet(net_param, TRAIN)
    assert net._remat_groups(), "blk/ layers should form a segment"
    params, state = net.init(jax.random.PRNGKey(0))
    batch = {"data": np.zeros((2, 8), np.int32),
             "label": np.zeros((2, 8), np.int32)}

    monkeypatch.setenv("SPARKNET_REMAT", "0")
    blobs_off, _ = net.apply(params, state, batch, train=True)
    monkeypatch.setenv("SPARKNET_REMAT", "1")
    blobs_on, _ = net.apply(params, state, batch, train=True)
    # "x" is overwritten inside the segment and not needed afterwards:
    # it must be ABSENT, never the stale pre-segment value
    assert "x" in blobs_off
    assert "x" not in blobs_on


# --- under remat a kernel's forward runs once ------------------------------
# A kernel's wrapper names what its backward reads of its forward's results
# (graph/remat.py:keep) and every policy of compiler._checkpointed saves that
# name; the parent's jax.checkpoint(fn) ran the forward kernel a second time.
# CPU, interpret mode.

from sparknet_tpu.graph import compiler                     # noqa: E402
from sparknet_tpu.obs.trace import default_tracer           # noqa: E402

S, E = 256, 64


def _flash_case(heads, kv_heads, window):
    """x -> x + out(flash(q(x), k(x), v(x))): (block, x, weights, the
    forward kernel's name, what is kept: array -> (shape, dtype))."""
    from sparknet_tpu.ops.pallas_attention import flash_attention
    d = E // heads
    rs = np.random.RandomState(heads + window)
    x = jnp.asarray(rs.randn(1, S, E), jnp.float32)
    ws = [jnp.asarray(0.2 * rs.randn(E, n * d), jnp.float32)
          for n in (heads, kv_heads, kv_heads)]
    ws.append(jnp.asarray(0.2 * rs.randn(E, E), jnp.float32))

    def block(x, wq, wk, wv, wo):
        q, k, v = [jnp.moveaxis((x @ w).reshape(1, S, -1, d), 1, 2)
                   for w in (wq, wk, wv)]
        o = flash_attention(q, k, v, True, None, 64, 64, window, "blk/attn")
        return x + jnp.moveaxis(o, 2, 1).reshape(1, S, E) @ wo
    kept = {"o": ((1, heads, S, d), "float32"),
            "lse": ((heads, S), "float32")}     # one column, not 128 lanes
    return block, x, ws, "flash_swa_fwd" if window else "flash_fwd", kept


def _delta_case():
    """The delta rule's kernel pair at head size 128: one key head, two
    value heads, two chunks of 64."""
    from sparknet_tpu.ops.pallas_deltanet import chunk_rule
    t, e, d, hv = 128, 32, 128, 2
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(1, t, e), jnp.float32)
    ws = [jnp.asarray(0.2 * rs.randn(e, n), jnp.float32)
          for n in (d, d, hv * d, hv, hv)]
    ws.append(jnp.asarray(0.2 * rs.randn(hv * d, e), jnp.float32))

    def block(x, wq, wk, wv, wb, wg, wo):
        o, _ = chunk_rule((x @ wq).reshape(1, t, 1, d),
                          (x @ wk).reshape(1, t, 1, d),
                          (x @ wv).reshape(1, t, hv, d),
                          jax.nn.sigmoid(x @ wb),
                          -jax.nn.softplus(x @ wg), layer="blk/mixer")
        return x + o.reshape(1, t, hv * d) @ wo
    # the state after is named too, and nothing in this backward reads it
    kept = {"o": ((1, t, hv * d), "float32"),
            "s_end": ((1, hv, d, d), "float32"),
            "starts": ((1, hv, 1, d, d), "float32"),
            "tinv": ((1, 1, 2, 64, hv * 64), "float32")}
    return block, x, ws, "gdn_chunk_fwd", kept


_KERNEL_CASES = {"causal": lambda: _flash_case(2, 2, 0),
                 "window": lambda: _flash_case(2, 2, 96),
                 "gqa": lambda: _flash_case(4, 2, 0),
                 "delta": _delta_case}


def _parents_checkpoint(fn, pol):
    """compiler._checkpointed as it was before the name."""
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _assert_bit_equal(got, want):
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("scanned", [False, True], ids=["block", "scan"])
@pytest.mark.parametrize("pol", ["full", "dots"])
@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_a_kernels_forward_runs_once_under_remat(case, pol, scanned):
    """The gradient's jaxpr of a checkpointed block holds ONE forward
    kernel call where the parent's holds two, alone and as the body of a
    scan over two like blocks, and loss and gradients are the parent's
    bit for bit."""
    block, x, ws, kernel, _ = _KERNEL_CASES[case]()

    def step(checkpointed):
        body = checkpointed(block, pol)

        def loss(x, *ws):
            if not scanned:
                return jnp.sum(body(x, *ws) ** 2)
            stacked = [jnp.stack([w, 0.5 * w]) for w in ws]
            y, _ = jax.lax.scan(lambda c, w: (body(c, *w), None), x, stacked)
            return jnp.sum(y ** 2)
        return jax.value_and_grad(loss, tuple(range(1, 1 + len(ws))))

    mine, parents = step(compiler._checkpointed), step(_parents_checkpoint)

    def forward_calls(fn):
        return str(jax.make_jaxpr(fn)(x, *ws)).count(f"name={kernel}\n")
    assert (forward_calls(mine), forward_calls(parents)) == (1, 2)
    _assert_bit_equal(jax.jit(mine)(x, *ws), jax.jit(parents)(x, *ws))


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_what_a_block_keeps_is_the_named_arrays_and_its_inputs(case):
    """`saved_residuals` of a block under "full": the segment's inputs, the
    arrays the wrapper named that the backward reads, and nothing else from
    inside the wrapper — no lane-replicated logsumexp; and one `remat.kept`
    record a named array in the ring, with its bytes."""
    block, x, ws, _, kept = _KERNEL_CASES[case]()
    ring = default_tracer()
    before = ring.mark()
    # what jax.ad_checkpoint.print_saved_residuals prints, as a list
    from jax._src.ad_checkpoint import saved_residuals
    saved = saved_residuals(compiler._checkpointed(block, "full"), x, *ws)
    records = ring.since(before, "remat.kept")
    assert [(r["array"], r["shape"], r["dtype"]) for r in records] == [
        (name, *kept[name]) for name in kept]
    for r in records:
        assert r["layer"] in ("blk/attn", "blk/mixer")
        assert r["bytes"] == 4 * int(np.prod(r["shape"]))

    inside = [(tuple(a.shape), str(a.dtype)) for a, why in saved
              if "from the argument" not in why]
    assert sum("from the argument" in why for _, why in saved) == 1 + len(ws)
    assert sorted(inside) == sorted(
        kept[name] for name in kept if name != "s_end")
    if case != "delta":
        assert not [s for s, _ in inside if s[-1] == 128]
        assert sum(f"named '{compiler.KERNEL_OUT}'" in why
                   for _, why in saved) >= 1


def _flash_lm(num_layers=2):
    return zoo.transformer_lm(vocab_size=48, seq_len=128, batch_size=1,
                              d_model=32, num_layers=num_layers,
                              num_heads=2, flash=True)


@pytest.mark.parametrize("scan", ["on", "off"])
@pytest.mark.parametrize("pol", ["full", "dots"])
def test_compiled_net_runs_each_flash_forward_once(monkeypatch, pol, scan):
    """Through CompiledNet.apply, as a solver takes it: two flash blocks as
    remat segments (scan off: two forward calls where the parent's has
    four) and as one checkpointed scan body (one where it has two); loss
    and gradients the parent's bit for bit."""
    monkeypatch.setenv("SPARKNET_SCAN", scan)
    net = CompiledNet(_flash_lm(), TRAIN)
    net.remat = pol
    params, state = net.init(jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(0, 48, (1, 129))
    batch = {"data": toks[:, :-1], "label": toks[:, 1:]}

    def read():
        # a function of its own a reading: jax keeps a traced one
        step = jax.value_and_grad(lambda p: net.loss_fn(
            p, state, batch, rng=jax.random.PRNGKey(7))[0])
        calls = str(jax.make_jaxpr(step)(params)).count("name=flash_fwd\n")
        return calls, jax.jit(step)(params)
    mine, got = read()
    monkeypatch.setattr(compiler, "_checkpointed", _parents_checkpoint)
    parents, want = read()
    assert (mine, parents) == ((1, 2) if scan == "on" else (2, 4))
    _assert_bit_equal(got, want)
