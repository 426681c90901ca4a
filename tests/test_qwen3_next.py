"""Qwen3-Next's layers and the whole 4-block model against the plain
reference (`benchmark/reference/qwen3_next.py`, imported from where it
lies, not copied): small widths, seeded weights, float32 on the CPU.

The reference computes the DeltaNet token by token, attention as a masked
softmax, the MoE as a loop over the held experts with a mask; the program
computes them in chunks, through the dense or the flash path, and over
ragged groups a window of rows at a time.
"""

import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.ops import deltanet, moe as moe_ops
from sparknet_tpu.ops.attention import rotary
from sparknet_tpu.ops.normalization import rms_norm
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def ref():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("reference.qwen3_next")


TOY = dict(hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=8,
           linear_conv_kernel_dim=4, num_experts=8, num_experts_per_tok=4,
           moe_intermediate_size=16, shared_expert_intermediate_size=16,
           norm_topk_prob=True, vocab_size=64, router_outputs=32,
           first_expert=0, seq_len=64)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def layer(lp, shape):
    return get_layer(lp.type)(lp, [shape], 0)


def fill(impl, key, std=0.3):
    """Seeded blobs for a layer: gaussian matrices, its own constants
    perturbed so that no norm weight or decay is at a special value."""
    out = []
    for i, (shape, *_) in enumerate(impl.param_shapes()):
        out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32))
    return out


# ------------------------------------------------------------------ RMSNorm

@pytest.mark.parametrize("zero_centered", [True, False])
def test_rms_norm_matches_reference(ref, zero_centered):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    impl = layer(dsl.RMSNormLayer("n", ["x"], eps=1e-6,
                                  zero_centered=zero_centered), x.shape)
    assert [s[0] for s in impl.param_shapes()] == [(32,)]
    assert impl.param_shapes()[0][1].value == (0.0 if zero_centered else 1.0)

    def mine(x, w):
        return jnp.sum(jnp.sin(impl.apply([w], [x], True, None)[0]))

    def theirs(x, w):
        return jnp.sum(jnp.sin(ref.rms_norm(x, w, 1e-6, zero_centered)))
    close(impl.apply([w], [x], True, None)[0],
          ref.rms_norm(x, w, 1e-6, zero_centered))
    for a, b in zip(jax.grad(mine, (0, 1))(x, w),
                    jax.grad(theirs, (0, 1))(x, w)):
        close(a, b)


def test_rms_norm_is_float32_inside_bfloat16():
    x = (100 * jax.random.normal(jax.random.PRNGKey(0), (4, 64))) \
        .astype(jnp.bfloat16)
    y = rms_norm(x, jnp.zeros((64,)), 1e-6)
    assert y.dtype == jnp.bfloat16
    want = x.astype(jnp.float32)
    want = want / jnp.sqrt(jnp.mean(want ** 2, -1, keepdims=True) + 1e-6)
    close(y.astype(jnp.float32), want, tol=1e-2)


# ------------------------------------------------------------------- rotary

def test_partial_rotary_matches_reference(ref):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 3, 16))
    got = rotary(x, 4, 1e7)
    for b in range(2):
        close(got[b], ref.rope(x[b], 4, 1e7))
    # the other 12 dimensions are untouched, position 0 is the identity
    np.testing.assert_array_equal(np.asarray(got[..., 4:]),
                                  np.asarray(x[..., 4:]))
    close(got[:, 0], x[:, 0])
    g_mine = jax.grad(lambda x: jnp.sum(jnp.cos(rotary(x, 4, 1e7))))(x)
    g_ref = jax.grad(lambda x: sum(
        jnp.sum(jnp.cos(ref.rope(x[b], 4, 1e7))) for b in range(2)))(x)
    close(g_mine, g_ref)


# -------------------------------------------------------- gated attention

def attention_layer(flash, seq):
    lp = dsl.AttentionLayer("mixer", ["x"], 4, head_dim=16, causal=True,
                            flash=flash, num_kv_heads=2, qk_norm=True,
                            rotary_dim=4, rope_theta=1e7, output_gate=True)
    return layer(lp, (2, seq, 32))


@pytest.mark.parametrize("flash,seq", [(False, 48), (True, 128)])
def test_gated_attention_matches_reference(ref, flash, seq):
    impl = attention_layer(flash, seq)
    assert [s[0] for s in impl.param_shapes()] == [
        (4 * 2 * 16, 32), (2 * 16, 32), (2 * 16, 32), (32, 4 * 16),
        (16,), (16,)]
    blobs = fill(impl, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, seq, 32))
    cot = jax.random.normal(jax.random.PRNGKey(5), (2, seq, 32))

    def mine(x, blobs):
        return jnp.sum(cot * impl.apply(blobs, [x], True, None)[0])

    def theirs(x, blobs):
        return sum(jnp.sum(cot[b] * ref.gated_attention(x[b], blobs, TOY))
                   for b in range(2))
    close(impl.apply(blobs, [x], True, None)[0],
          jnp.stack([ref.gated_attention(x[b], blobs, TOY)
                     for b in range(2)]))
    gm, gt = jax.grad(mine, (0, 1))(x, blobs), jax.grad(theirs, (0, 1))(
        x, blobs)
    close(gm[0], gt[0])
    for a, b in zip(gm[1], gt[1]):
        close(a, b)


def test_attention_forms_do_not_mix():
    with pytest.raises(ValueError, match="num_kv_heads"):
        lp = dsl.AttentionLayer("a", ["x"], 4)
        lp.attention_param.qk_norm = True
        layer(lp, (2, 8, 32))
    with pytest.raises(ValueError, match="multiple"):
        layer(dsl.AttentionLayer("a", ["x"], 4, num_kv_heads=3),
              (2, 8, 32))


# ----------------------------------------------------------------- DeltaNet

def delta_inputs(t, h=3, dk=8, dv=8, g_scale=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (t, h, dk))
    k = jax.random.normal(ks[1], (t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (t, h)))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[4], (t, h)))
    return q, k, v, beta, g


@pytest.mark.parametrize("t,g_scale", [(64, 1.0), (128, 1.0), (192, 1.0),
                                       (100, 1.0), (128, 40.0),
                                       (128, 0.01)])
def test_chunked_delta_rule_matches_token_recurrence(ref, t, g_scale):
    args = delta_inputs(t, g_scale=g_scale)
    cot = jax.random.normal(jax.random.PRNGKey(9), (t, 3, 8))

    def mine(*a):
        return deltanet.gated_delta_rule(*[x[None] for x in a],
                                         chunk=64)[0]
    close(mine(*args), ref.delta_rule(*args))
    gm = jax.grad(lambda *a: jnp.sum(cot * mine(*a)), range(5))(*args)
    gt = jax.grad(lambda *a: jnp.sum(cot * ref.delta_rule(*a)),
                  range(5))(*args)
    for a, b in zip(gm, gt):
        assert np.isfinite(np.asarray(a)).all()
        close(a, b, tol=5e-4)


def test_unit_lower_inverse():
    a = jnp.tril(0.1 * jax.random.normal(jax.random.PRNGKey(0),
                                         (3, 64, 64)), -1)
    inv = deltanet.unit_lower_inverse(a)
    close(inv @ (jnp.eye(64) + a), jnp.broadcast_to(jnp.eye(64), a.shape),
          tol=1e-3)


def test_gated_delta_net_layer_matches_reference(ref):
    lp = dsl.GatedDeltaNetLayer("mixer", ["x"], 2, 4, 8, 8, conv_kernel=4)
    impl = layer(lp, (2, 128, 32))
    assert [s[0] for s in impl.param_shapes()] == [
        (2 * 16 + 2 * 32, 32), (8, 32), (2 * 16 + 32, 4), (4,), (4,), (8,),
        (32, 32)]
    fillers = [s[1] for s in impl.param_shapes()]
    assert (fillers[3].type, fillers[4].value, fillers[5].value) == \
        ("uniform", 1.0, 1.0)
    blobs = fill(impl, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 32))
    cot = jax.random.normal(jax.random.PRNGKey(8), (2, 128, 32))

    def mine(x, blobs):
        return jnp.sum(cot * impl.apply(blobs, [x], True, None)[0])

    def theirs(x, blobs):
        return sum(jnp.sum(cot[b] * ref.gated_delta_net(x[b], blobs, TOY))
                   for b in range(2))
    close(impl.apply(blobs, [x], True, None)[0],
          jnp.stack([ref.gated_delta_net(x[b], blobs, TOY)
                     for b in range(2)]))
    gm, gt = jax.grad(mine, (0, 1))(x, blobs), jax.grad(theirs, (0, 1))(
        x, blobs)
    close(gm[0], gt[0], tol=5e-4)
    for a, b in zip(gm[1], gt[1]):
        close(a, b, tol=5e-4)


# ---------------------------------------------------------------------- MoE

def moe_layer(held=None, first=None, shared=16, top_k=10, experts=32,
              tile=8, stats=False):
    lp = dsl.MoELayer("moe", ["x"], experts, hidden_dim=16, top_k=top_k,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=shared, tile_rows=tile, stats=stats)
    return layer(lp, (2, 48, 32))


def moe_dims(held=32, first=0, top_k=10):
    return dict(TOY, num_experts=held, first_expert=first,
                num_experts_per_tok=top_k, router_outputs=32)


def skewed(key, pull=6.0):
    """Inputs and a router under which far more than a third of the
    tokens send one of their ten choices to expert 0."""
    kx, kb = jax.random.split(key)
    base = jax.random.normal(kb, (32,))
    x = 0.3 * jax.random.normal(kx, (2, 48, 32)) + base
    return x, base * pull / jnp.sum(base * base)


def moe_out(impl, blobs, x):
    return impl.apply(blobs, [x], True, None)[0]


def test_moe_top10_of_32_under_skew_drops_nothing(ref):
    impl = moe_layer()
    blobs = fill(impl, jax.random.PRNGKey(10))
    x, pull = skewed(jax.random.PRNGKey(11))
    blobs[0] = blobs[0].at[0].set(pull)
    d = moe_dims()
    idx, _ = ref.route(x.reshape(96, 32), blobs[0], d)
    assert int(jnp.sum(idx == 0)) >= 96 // 3       # the skew is there
    assert int(jnp.sum(idx == 0)) > 3 * 96 * 10 // 32
    cot = jax.random.normal(jax.random.PRNGKey(12), (96, 32))

    def mine(x, blobs):
        return jnp.sum(cot * moe_out(impl, blobs, x).reshape(96, 32))

    def theirs(x, blobs):
        return jnp.sum(cot * ref.moe(x.reshape(96, 32), blobs, d))
    close(moe_out(impl, blobs, x).reshape(96, 32),
          ref.moe(x.reshape(96, 32), blobs, d))
    gm, gt = jax.grad(mine, (0, 1))(x, blobs), jax.grad(theirs, (0, 1))(
        x, blobs)
    close(gm[0], gt[0])
    for a, b in zip(gm[1], gt[1]):
        close(a, b)


@pytest.mark.parametrize("held,first", [(8, 0), (8, 16), (16, 16)])
def test_moe_held_share_matches_reference(ref, held, first):
    impl = moe_layer(held=held, first=first)
    assert [s[0] for s in impl.param_shapes()][:4] == [
        (32, 32), (held, 16, 32), (held, 16, 32), (held, 32, 16)]
    blobs = fill(impl, jax.random.PRNGKey(13))
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 48, 32))
    d = moe_dims(held, first)
    close(moe_out(impl, blobs, x).reshape(96, 32),
          ref.moe(x.reshape(96, 32), blobs, d))
    gm = jax.grad(lambda b: jnp.sum(moe_out(impl, b, x) ** 2))(blobs)
    gt = jax.grad(lambda b: jnp.sum(
        ref.moe(x.reshape(96, 32), b, d) ** 2))(blobs)
    for a, b in zip(gm, gt):
        close(a, b)


def test_moe_shares_add_up_to_the_uncut_layer():
    """32 experts as 4 shares of 8: the routed parts that all the shares
    give, plus the shared expert once, are the uncut layer's output and
    input gradient."""
    whole = moe_layer()
    blobs = fill(whole, jax.random.PRNGKey(15))
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 48, 32))
    cot = jax.random.normal(jax.random.PRNGKey(17), (2, 48, 32))

    def out_and_dx(impl, blobs):
        y, vjp = jax.vjp(lambda x: moe_out(impl, blobs, x), x)
        return y, vjp(cot)[0]

    def share(j, shared):
        impl = moe_layer(held=8, first=8 * j, shared=16 if shared else 0)
        mine = [blobs[0]] + [w[8 * j:8 * j + 8] for w in blobs[1:4]]
        return out_and_dx(impl, mine + (blobs[4:] if shared else []))
    routed = [share(j, False) for j in range(4)]
    with_shared = share(0, True)
    y = sum(r[0] for r in routed) + with_shared[0] - routed[0][0]
    dx = sum(r[1] for r in routed) + with_shared[1] - routed[0][1]
    want_y, want_dx = out_and_dx(whole, blobs)
    close(y, want_y)
    close(dx, want_dx)
    # and a share is not the whole: the test can fail
    assert np.abs(np.asarray(routed[0][0] - want_y)).max() > 1e-3


def test_moe_window_plan_bounds_what_can_be_routed():
    """The pairs on held experts lie sorted by expert, unpadded, and run a
    window of rows at a time; the static bound is the worst case: every
    token's min(top_k, held) pairs land here, however they are spread."""
    n, k, held, tile = 96, 10, 8, 8
    # 8 of 32 experts: an even routing sends 240 pairs, a window takes 300
    window = moe_ops.window_rows(n, k, held, 32, tile)
    assert window == 38 * tile
    bound = math.ceil(n * 8 / window)           # windows, at the very most
    assert bound == 3
    rng = np.random.RandomState(0)
    for trial in range(20):
        # every token sends min(k, held) = 8 pairs to the 8 held experts
        counts = np.full(held, n)
        if trial:
            pairs = np.concatenate([rng.permutation(held)[:8]
                                    for _ in range(n)])
        else:
            pairs = np.tile(np.arange(held), n)
        pair_expert = np.concatenate(
            [pairs.reshape(n, 8), np.full((n, k - 8), held)], 1).reshape(-1)
        plan = moe_ops.plan_windows(jnp.asarray(pair_expert, jnp.int32),
                                    held, window)
        assert int(plan["windows"]) == bound
        np.testing.assert_array_equal(np.asarray(plan["count"]), counts)
        # the held pairs first, each expert's one contiguous group; then
        # padding, so that every window is a slice
        order = np.asarray(plan["order"])
        assert len(order) % window == 0 and not order[n * k:].any()
        assert (np.diff(pair_expert[order[:n * k]]) >= 0).all()
        np.testing.assert_array_equal(np.asarray(plan["bounds"]),
                                      n * np.arange(held + 1))
    # one token-expert pair each for 8 experts: one window, where the
    # tile table needed 8 ragged tiles
    one = moe_ops.window_rows(1, 8, held, 8, tile)
    plan = moe_ops.plan_windows(jnp.arange(8, dtype=jnp.int32), held, one)
    assert one == tile and int(plan["windows"]) == 1


def test_moe_statistics_top():
    impl = moe_layer(held=8, first=0, stats=True)
    assert impl.has_state and impl.out_shapes()[1] == (3,)
    blobs = fill(impl, jax.random.PRNGKey(18))
    x = jax.random.normal(jax.random.PRNGKey(19), (2, 48, 32))
    (y, stats), state = impl.apply_stateful(blobs, [jnp.zeros(3)], [x],
                                            True, None)
    idx, _ = impl.route(x.reshape(96, 32), blobs[0])
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=32)[:8]
    # share of the pairs held here, largest over mean load, windows run
    close(stats, [load.sum() / 960.0, load.max() / load.mean(),
                  math.ceil(load.sum() / moe_ops.window_rows(
                      96, 10, 8, 32, 8))])
    close(state[0], stats)


# ------------------------------------------------------------ whole model

def toy_net(**kw):
    args = {k: v for k, v in TOY.items()
            if k not in ("router_outputs", "first_expert")}
    args.update(num_experts=32, experts_held=8, batch_size=2, flash=False)
    args.update(kw)
    return zoo.qwen3_next(**args)


SOLVER = dict(type="Adam", base_lr=1e-3, lr_policy="fixed", momentum=0.9,
              momentum2=0.95, delta=1e-8, weight_decay=0.1)


def toy_config():
    config = {k: v for k, v in TOY.items()
              if k not in ("router_outputs", "first_expert", "seq_len")}
    config.update(published={"num_experts": 32},
                  builder_args={"seq_len": 64})
    return config


def tokens(seed=0):
    draw = np.random.RandomState(seed).randint(0, 64, (2, 65))
    return draw[:, :-1].astype(np.int32), draw[:, 1:].astype(np.int32)


def seeded(solver, reference, seed=0):
    """The reference's fillers into the program's solver."""
    sys.path.insert(0, BENCH)
    import weights
    w0 = weights.make_weights(reference.specs, seed)
    assert set(w0) == set(solver.params)
    for name, blobs in w0.items():
        assert [b.shape for b in blobs] == \
            [p.shape for p in solver.params[name]], name
        solver.params[name] = [jnp.array(b) for b in blobs]
    return w0


def test_whole_model_three_adam_steps_match_reference(ref):
    reference = ref.build(toy_config(), 2)
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(), log_fn=None)
    # the program's multipliers are the reference's, blob for blob
    for name, blobs in reference.specs:
        assert solver.updater.mults[name] == [b[2] for b in blobs], name
    w0 = seeded(solver, reference)
    step = reference.make_step(SOLVER, block_rows=1)
    data, labels = tokens()
    params, history = w0, None
    for i in range(3):
        got = float(solver.train_step({"data": data, "label": labels}))
        params, history, want, grads = step(params, history, data, labels,
                                            None)
        assert abs(got - float(want)) <= 2e-5 * abs(float(want)), i
        if i == 0:
            # the first gradient, out of Adam's first moment
            for name, blobs in grads.items():
                for j, g in enumerate(blobs):
                    decay = dict(reference.specs)[name][j][2][1]
                    m1 = solver.history[name][j][0]
                    close(m1 / 0.1 - 0.1 * decay * w0[name][j], g,
                          tol=2e-3)
    # Adam divides by the root of its second moment: an element whose tiny
    # gradient differs in the last bits moves a visible part of a step, so
    # the three steps' change is compared blob by blob in the norm
    for name, blobs in params.items():
        for j, w in enumerate(blobs):
            got = np.asarray(solver.params[name][j] - w0[name][j])
            want = np.asarray(w - w0[name][j])
            assert np.linalg.norm(got - want) <= \
                0.05 * np.linalg.norm(want), (name, j)


def test_ids_and_loss_are_over_the_held_slice(ref):
    """The vocabulary is the held slice: with a zero head the loss is
    ln(rows held), and the benchmark's feed draws every id inside it, all
    rows different."""
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(vocab_size=48), log_fn=None)
    assert solver.params["tok_embed"][0].shape == (48, 32)
    assert solver.params["lm_head"][0].shape == (48, 32)
    solver.params["lm_head"] = [jnp.zeros((48, 32))]
    sys.path.insert(0, BENCH)
    feed = importlib.import_module("feeds.resident_tokens").build(
        traffic={}, config={"vocab_size": 48, "builder_args": {}}, seed=5,
        solver=solver, data_shape=(2, 64), num_classes=None)
    batch = next(feed)
    data, label = np.asarray(batch["data"]), np.asarray(batch["label"])
    assert data.min() >= 0 and max(data.max(), label.max()) < 48
    np.testing.assert_array_equal(data[:, 1:], label[:, :-1])
    assert (data[0] != data[1]).any()
    loss = float(solver.train_step(batch))
    assert abs(loss - math.log(48)) < 1e-5


def grads_of(solver, batch):
    net = solver.net
    return jax.grad(lambda p: net.loss_fn(p, solver.state, batch)[0])(
        solver.params)


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan):
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    data, labels = tokens(1)
    batch = {"data": jnp.asarray(data), "label": jnp.asarray(labels)}
    plain = Solver(sp, net_param=toy_net(), log_fn=None)
    plain.set_scan("off")
    knobbed = Solver(sp, net_param=toy_net(), log_fn=None, remat=remat)
    assert knobbed.net.remat == remat
    knobbed.set_scan(scan)
    # one period: the three DeltaNet blocks are a run, the fourth is not
    runs = knobbed.net._scan_runs()
    assert [(r["n"], r["glen"]) for r in runs] == [(3, 6)]
    seeded(plain, ref.build(toy_config(), 2))
    seeded(knobbed, ref.build(toy_config(), 2))
    want, got = grads_of(plain, batch), grads_of(knobbed, batch)
    for name in want:
        for a, b in zip(got[name], want[name]):
            close(a, b, tol=1e-3)


def test_solver_takes_the_remat_policy_where_it_is_built():
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    with pytest.raises(ValueError, match="remat policy"):
        Solver(sp, net_param=toy_net(), log_fn=None, remat="some")
    assert Solver(sp, net_param=toy_net(), log_fn=None).net.remat is None


def test_moe_load_is_recorded_where_the_solver_fetches_a_loss():
    from sparknet_tpu.obs.trace import Tracer
    tracer = Tracer()
    sp = Message("SolverParameter", display=1, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(moe_stats=True), log_fn=None,
                    tracer=tracer)
    data, labels = tokens(2)
    solver.step(2, iter([{"data": data, "label": labels}] * 2))
    loads = tracer.spans("moe.load")
    assert len(loads) == 2 * 4                 # per fetch, per block
    assert {r["layer"] for r in loads} == {f"block{i}/moe"
                                           for i in range(4)}
    for r in loads:
        assert 0.0 < r["held_share"] < 1.0 and r["max_over_mean"] >= 1.0
        assert r["windows"] >= 1.0
        assert r["parent"] == "solver.fetch"
