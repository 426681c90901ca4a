"""The no-drop MoE's held experts a window of rows at a time
(`ops/moe.py`: plan_windows, held_experts) and the grouped product under
them in both implementations: XLA's ragged product, and the megablox
kernels of `ops/pallas_moe.py` in interpret mode (the CPU has no other).

The oracle is a dense loop over the held experts with a mask: every token
through every expert, weighted by what the routing gave it there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import dsl
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.ops import moe as moe_ops

HELD, TILE, E, F = 4, 8, 32, 16
FORMS = pytest.mark.parametrize("kernel", [False, True],
                                ids=["xla", "kernel"])


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    scale = max(np.abs(b).max(initial=0.0), 1e-12)
    assert np.abs(a - b).max(initial=0.0) <= tol * scale, (
        np.abs(a - b).max(), scale)


def weights(key):
    kg, ku, kd = jax.random.split(key, 3)
    return (0.3 * jax.random.normal(kg, (HELD, F, E)),
            0.3 * jax.random.normal(ku, (HELD, F, E)),
            0.3 * jax.random.normal(kd, (HELD, E, F)))


# rows of each of the four groups inside one window of 32 rows
WINDOW_CASES = {
    "an_empty_expert": [5, 0, 11, 3],
    "a_group_of_one_row": [7, 1, 8, 2],
    "groups_off_the_tile": [5, 11, 3, 9],
    "one_expert_fills_the_window": [32, 0, 0, 0],
    "nothing_held": [0, 0, 0, 0],
}


@FORMS
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_grouped_product_against_a_masked_loop_over_experts(case, kernel):
    sizes = np.asarray(WINDOW_CASES[case], np.int32)
    m, used = 32, int(sizes.sum())
    group = np.repeat(np.arange(HELD), sizes)          # rows 0..used
    kl, kr, kw = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    lhs = jax.random.normal(kl, (m, E))
    rhs = jax.random.normal(kr, (m, F))
    wg, _, wd = weights(kw)
    dot, dot_t = moe_ops._grouped(kernel, TILE, jnp.asarray(sizes))

    def masked(e, rows):
        return jnp.where((group == e)[:, None], rows[:used], 0.0)
    want_nt = sum(masked(e, lhs) @ wg[e].T for e in range(HELD))
    want_nn = sum(masked(e, lhs) @ wd[e] for e in range(HELD))
    got_nt = dot(lhs, wg, True, "moe_gmm_fwd")
    got_nn = dot(lhs, wd, False, "moe_gmm_bwd")
    assert got_nt.shape == (m, F) and got_nt.dtype == jnp.float32
    # the rows of no group are the caller's to mask: the kernels leave
    # them unwritten, the XLA form zeroes them
    close(got_nt[:used], want_nt)
    close(got_nn[:used], want_nn)
    if not kernel:
        assert not np.asarray(got_nt[used:]).any()
    # per group lhs^T rhs on top of a running total, the rows of no group
    # left out whatever they hold
    total = jax.random.normal(kw, (HELD, E, F))
    dirty = lhs.at[used:].set(jnp.nan) if kernel else lhs
    want_t = jnp.stack([masked(e, lhs).T @ rhs[:used] for e in range(HELD)])
    close(dot_t(dirty, rhs, total, "moe_gmm_dw"), total + want_t)


def oracle(x, pair_weight, pair_expert, wg, wu, wd, top_k):
    n = x.shape[0]
    expert, weight = pair_expert.reshape(n, top_k), pair_weight.reshape(
        n, top_k)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(HELD):
        here = jnp.sum(jnp.where(expert == e, weight, 0.0), -1)
        h = jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T)
        y = y + here[:, None] * (h @ wd[e].T)
    return y


# pairs on each of the four held experts, of 24 tokens x top-4 = 96, in
# windows of 16 rows (two tiles): (counts, windows the loop runs)
LAYER_CASES = {
    "an_empty_expert": ([13, 0, 21, 6], 3),
    "a_group_of_one_row": ([9, 1, 15, 4], 2),
    "groups_off_the_tile": ([5, 11, 3, 9], 2),
    "every_pair_on_one_expert": ([96, 0, 0, 0], 6),
    "nothing_held": ([0, 0, 0, 0], 0),
}


@FORMS
@pytest.mark.parametrize("case", list(LAYER_CASES))
def test_held_experts_across_window_boundaries(case, kernel):
    """Output, dx, d pair_weight and the three weight gradients against
    the oracle, the groups cut by window boundaries wherever they fall."""
    counts, windows = LAYER_CASES[case]
    n, top_k, window = 24, 4, 16
    rng = np.random.RandomState(len(case))
    pairs = np.concatenate([np.repeat(np.arange(HELD), counts),
                            np.full(n * top_k - sum(counts), HELD)])
    pair_expert = jnp.asarray(rng.permutation(pairs), jnp.int32)
    kx, kp, kw, kc = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    x = jax.random.normal(kx, (n, E))
    pair_weight = jax.random.uniform(kp, (n * top_k,), minval=0.1)
    cot = jax.random.normal(kc, (n, E))
    plan = moe_ops.plan_windows(pair_expert, HELD, window)
    assert int(plan["windows"]) == windows
    np.testing.assert_array_equal(np.asarray(plan["count"]), counts)

    def mine(x, pw, wg, wu, wd):
        return moe_ops.held_experts(x, pw, plan, wg, wu, wd, TILE, top_k,
                                    window, kernel)

    def theirs(x, pw, wg, wu, wd):
        return oracle(x, pw, pair_expert, wg, wu, wd, top_k)
    args = (x, pair_weight, *weights(kw))
    got, vjp = jax.vjp(mine, *args)
    want, vjp_want = jax.vjp(theirs, *args)
    assert got.dtype == jnp.float32
    close(got, want)
    if not windows:
        assert not np.asarray(got).any()
    for a, b in zip(vjp(cot), vjp_want(cot)):
        close(a, b)


def test_window_rows_is_static_and_sized_by_an_even_routing():
    # the LM cell: 16,384 tokens x top-10, 16 of 512 experts held, tiles of
    # 128: an even routing sends 5,120 pairs, the window takes 6,400
    assert moe_ops.window_rows(16384, 10, 16, 512, 128) == 50 * 128
    # the SmallThinker cell: 32,768 tokens x top-6, 8 of 64 held: 24,576
    # pairs and a quarter more in ONE window at the default tile
    assert moe_ops.window_rows(32768, 6, 8, 64, 128) == 240 * 128
    # the LFM2 cell: 24,576 tokens x top-4, 8 of 32 held: the same 24,576
    # pairs, the same one window of 30,720 rows (batch 4 would want 40,960
    # and get the cap of 32,768: an even routing's 32,768 pairs fill it)
    assert moe_ops.window_rows(24576, 4, 8, 32, 128) == 30720
    assert moe_ops.window_rows(32768, 4, 8, 32, 128) == 32768
    # a share of a toy layer, and the whole of it: never more than
    # WINDOW_TILES tiles, so memory is a window's and not the routing's
    assert moe_ops.window_rows(96, 10, 8, 32, 8) == 38 * 8
    assert moe_ops.window_rows(96, 10, 32, 32, 8) == 120 * 8
    assert moe_ops.window_rows(256, 10, 32, 32, 8) == 256 * 8
    # no more rows than pairs that can land here, in whole tiles
    assert moe_ops.window_rows(24, 4, 4, 4, 8) == 96
    assert moe_ops.window_rows(1, 8, 8, 8, 8) == 8
    assert moe_ops.window_rows(3, 2, 8, 8, 8) == 8


def moe_paths():
    return [(s["layer"], s["path"], s["reason"])
            for s in default_tracer().spans("moe.path")]


@pytest.mark.parametrize("backend,widths,tile,path,reason", [
    ("cpu", (128, 128), 8, "xla", "the backend is cpu, not a TPU"),
    ("tpu", (128, 64), 8, "xla", "widths 128 and 64 are not multiples of "
                                 "the lane width 128"),
    ("tpu", (128, 128), 4, "xla", "tile_rows 4 is not a multiple of 8"),
    ("tpu", (128, 128), 8, "kernel", "backend, widths and tile_rows fit")])
def test_layer_takes_the_product_it_can_and_records_it(
        monkeypatch, backend, widths, tile, path, reason):
    """One `moe.path` record a trace of the layer, as `gdn.path`: the
    kernels on a TPU backend where widths and tile_rows allow, else XLA's
    ragged product over the same window. The backend is the test's to
    pretend: the program has no option for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    embed, hidden = widths
    name = f"moe_{backend}_{hidden}_{tile}"
    lp = dsl.MoELayer(name, ["x"], 8, hidden_dim=hidden, top_k=2,
                      experts_held=4, first_expert=0, tile_rows=tile)
    impl = get_layer(lp.type)(lp, [(1, 16, embed)], 0)
    blobs = [jax.ShapeDtypeStruct(s[0], jnp.float32)
             for s in impl.param_shapes()]
    before = len(moe_paths())
    text = str(jax.make_jaxpr(
        lambda p, x: impl.apply(p, [x], True, None)[0])(
        blobs, jax.ShapeDtypeStruct((1, 16, embed), jnp.float32)))
    assert moe_paths()[before:] == [(name, path, reason)]
    assert ("pallas_call" in text) == (path == "kernel")
    assert ("ragged_dot" in text) == (path == "xla")
    # one structure either way: a loop of dynamic length over windows
    assert " while[" in text and "scatter-add" in text
