"""Qwen3-Next's layers and the whole 4-block model against the plain
reference (`benchmark/reference/qwen3_next.py`): small widths, seeded
weights, float32 on the CPU. The family's record and the bodies of the
tests every family has are in `tests/lm_family.py`.

The reference computes attention as a masked softmax and the MoE as a loop
over the held experts with a mask; the program goes through the dense or
the flash path, and over ragged groups a window of rows at a time. (The
DeltaNet's chunked rule against the token recurrence:
`tests/test_deltanet.py`.)
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl
from sparknet_tpu.ops import moe as moe_ops
from sparknet_tpu.ops.attention import rotary
from sparknet_tpu.ops.normalization import rms_norm
from tests import lm_family as lm
from tests.lm_family import close, fill, layer, ref  # noqa: F401  (fixture)

FAMILY = lm.QWEN3_NEXT
TOY = FAMILY.toy


# ------------------------------------------------------------------ RMSNorm

@pytest.mark.parametrize("zero_centered", [True, False])
def test_rms_norm_matches_reference(ref, zero_centered):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    impl = layer(dsl.RMSNormLayer("n", ["x"], eps=1e-6,
                                  zero_centered=zero_centered), [x.shape])
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
    return layer(lp, [(2, seq, 32)])


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
        layer(lp, [(2, 8, 32)])
    with pytest.raises(ValueError, match="multiple"):
        layer(dsl.AttentionLayer("a", ["x"], 4, num_kv_heads=3),
              [(2, 8, 32)])


# ---------------------------------------------------------------------- MoE

def moe_layer(held=None, first=None, shared=16, top_k=10, experts=32,
              tile=8, stats=False):
    lp = dsl.MoELayer("moe", ["x"], experts, hidden_dim=16, top_k=top_k,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=shared, tile_rows=tile, stats=stats)
    return layer(lp, [(2, 48, 32)])


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
    lm.held_share(FAMILY, impl, [(2, 48, 32)], moe_dims(held, first), 13,
                  tol=2e-4)


def test_moe_shares_add_up_to_the_uncut_layer():
    """32 experts as 4 shares of 8: the routed parts that all the shares
    give, plus the shared expert once, are the uncut layer's output and
    input gradient."""
    whole = moe_layer()
    blobs = fill(whole, jax.random.PRNGKey(15))
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 48, 32))
    cot = jax.random.normal(jax.random.PRNGKey(17), (2, 48, 32))

    def shares(chips, shared):
        return lm.sum_of_shares(
            lambda per, lo: moe_layer(held=per, first=lo, shared=shared),
            chips, 8, blobs if shared else blobs[:4], [x], cot)
    first, routed, with_shared = shares(1, 0), shares(4, 0), shares(1, 16)
    want = lm.out_and_input_grads(whole, blobs, [x], cot)
    for j in range(2):              # the output, the input's gradient
        close(routed[j] + with_shared[j] - first[j], want[j])
    # and a share is not the whole: the test can fail
    assert np.abs(np.asarray(first[0] - want[0])).max() > 1e-3


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

def test_whole_model_three_adam_steps_match_reference(ref):
    lm.three_adam_steps(FAMILY)


def test_ids_and_loss_are_over_the_held_slice(ref):
    """The vocabulary is the held slice: with a zero head the loss is
    ln(rows held), and the benchmark's feed draws every id inside it, all
    rows different."""
    solver = FAMILY.solver(dict(vocab_size=48))
    assert solver.params["tok_embed"][0].shape == (48, 32)
    assert solver.params["lm_head"][0].shape == (48, 32)
    solver.params["lm_head"] = [jnp.zeros((48, 32))]
    feed = lm.bench("feeds.resident_tokens").build(
        traffic={}, config={"vocab_size": 48, "builder_args": {}}, seed=5,
        solver=solver, data_shape=(2, 64), num_classes=None)
    batch = next(feed)
    data, label = np.asarray(batch["data"]), np.asarray(batch["label"])
    assert data.min() >= 0 and max(data.max(), label.max()) < 48
    np.testing.assert_array_equal(data[:, 1:], label[:, :-1])
    assert (data[0] != data[1]).any()
    loss = float(solver.train_step(batch))
    assert abs(loss - math.log(48)) < 1e-5


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan):
    lm.remat_and_scan(FAMILY, remat, scan)


def test_solver_takes_the_remat_policy_where_it_is_built():
    with pytest.raises(ValueError, match="remat policy"):
        FAMILY.solver(remat="some")
    assert FAMILY.solver().net.remat is None


def test_moe_load_is_recorded_where_the_solver_fetches_a_loss():
    tracer, _ = lm.traced_steps(FAMILY, 2, dict(moe_stats=True))
    loads = lm.held_loads(tracer, [f"block{i}/moe" for i in range(4)])
    assert len(loads) == 2 * 4                 # per fetch, per block
    for r in loads:
        assert r["max_over_mean"] >= 1.0 and r["parent"] == "solver.fetch"
