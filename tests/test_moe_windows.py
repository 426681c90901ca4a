"""The no-drop MoE's held experts a window of rows at a time
(`ops/moe.py`: plan_windows, held_experts) and the grouped product under
them in both implementations: XLA's ragged product, and the megablox
kernels of `ops/pallas_moe.py` in interpret mode (the CPU has no other).

Two oracles: a dense loop over the held experts with a mask (every token
through every expert, weighted by what the routing gave it there), and,
for the combine, which since PR 39 gathers (`_token_major`, `_combine`),
the scatter-add it replaced: every held pair's row, weighted, added onto
its token by `.at[tok].add`, kept here.
"""

import re

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
    # per group lhs^T rhs, written once (zeros for a group of no row), the
    # rows of no group left out whatever they hold
    dirty = lhs.at[used:].set(jnp.nan) if kernel else lhs
    want_t = jnp.stack([masked(e, lhs).T @ rhs[:used] for e in range(HELD)])
    got_t = dot_t(dirty, rhs, "moe_gmm_dw")
    assert got_t.dtype == jnp.float32
    close(got_t, want_t)
    for e in np.flatnonzero(sizes == 0):
        assert not np.asarray(got_t[e]).any()


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
    # the backward's first window stands outside its loop over windows (PR
    # 48): full to its last row the loop runs no trip, one row more and it
    # runs one
    "exactly_one_full_window": ([4, 6, 0, 6], 1),
    "one_window_and_one_row": ([5, 6, 0, 6], 2),
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


@pytest.mark.parametrize("matrices,act", [(3, "silu"), (2, "relu2")],
                         ids=["three_matrices", "two_matrices"])
@pytest.mark.parametrize("tile", [8, 32])
def test_a_row_tile_larger_than_some_groups(tile, matrices, act):
    """The kernels (interpret mode) at a row tile that is larger than some
    groups, with an empty group: groups of 0, 3, 19 and 40 rows in one
    window of 64, which at a tile of 32 puts two groups and a part of the
    third into the first tile and ends the last group six rows into the
    second. Forward and every gradient against the same window through
    `lax.ragged_dot_general`."""
    counts, n, top_k, window = [0, 3, 19, 40], 24, 4, 64
    rng = np.random.RandomState(tile + matrices)
    pairs = np.concatenate([np.repeat(np.arange(HELD), counts),
                            np.full(n * top_k - sum(counts), HELD)])
    pair_expert = jnp.asarray(rng.permutation(pairs), jnp.int32)
    kx, kp, kw, kc = jax.random.split(jax.random.PRNGKey(tile), 4)
    x = jax.random.normal(kx, (n, E))
    pair_weight = jax.random.uniform(kp, (n * top_k,), minval=0.1)
    cot = jax.random.normal(kc, (n, E))
    plan = moe_ops.plan_windows(pair_expert, HELD, window)
    assert int(plan["windows"]) == 1
    np.testing.assert_array_equal(np.asarray(plan["count"]), counts)
    wg, wu, wd = weights(kw)
    if matrices == 2:
        wg = None

    def layer(kernel):
        return jax.vjp(lambda x, pw, wg, wu, wd: moe_ops.held_experts(
            x, pw, plan, wg, wu, wd, tile, top_k, window, kernel, act),
            x, pair_weight, wg, wu, wd)
    got, vjp = layer(True)
    want, vjp_want = layer(False)
    assert np.asarray(want).any()
    close(got, want)
    grads, grads_want = vjp(cot), vjp_want(cot)
    assert len(grads) == 5 and (grads[2] is None) == (matrices == 2)
    for a, b in zip(grads, grads_want):
        if b is not None:
            assert np.asarray(b).any()
            close(a, b)
    # the empty group's weights get no gradient, to the bit
    for g in grads[2:]:
        assert g is None or not np.asarray(g[0]).any()


def scatter_oracle(x, pair_weight, pair_expert, wg, wu, wd, top_k):
    """The combine as a scatter-add: each held pair's row through its
    expert, times its weight, added onto its token."""
    pairs = np.flatnonzero(np.asarray(pair_expert) < HELD)
    tok, e = pairs // top_k, np.asarray(pair_expert)[pairs]
    xw = x[tok]
    h = jax.nn.silu(jnp.einsum("me,mfe->mf", xw, wg[e])) \
        * jnp.einsum("me,mfe->mf", xw, wu[e])
    out = jnp.einsum("mf,mef->me", h, wd[e])
    return jnp.zeros(x.shape, jnp.float32).at[tok].add(
        out * pair_weight[pairs][:, None])


def _experts_of_tokens(n, top_k, rows):
    """(n x top_k,) local experts, nothing held but what `rows` (token ->
    its experts, slot by slot) says."""
    pair_expert = np.full((n, top_k), HELD, np.int32)
    for tok, experts in rows.items():
        pair_expert[tok, :len(experts)] = experts
    return pair_expert.reshape(-1)


def _all_on_expert_0_but(n, top_k, free):
    pair_expert = np.zeros((n, top_k), np.int32)
    pair_expert.reshape(-1)[free] = HELD
    return pair_expert.reshape(-1)


# 24 tokens x top-4 over the four held experts: (each pair's local expert,
# the window's rows, the windows the loop runs)
COMBINE_CASES = {
    # token 5's four pairs are all held, on four experts, and with three
    # other tokens' pairs they fit one window: the whole segment of J = 4
    "a_token_with_all_its_pairs_in_one_window": (_experts_of_tokens(
        24, 4, {5: [0, 1, 2, 3], 2: [1, HELD, 0], 9: [3, 3], 23: [HELD, 2],
                0: [2]}), 16, 1),
    # token 7's pair on expert 0 lies in the first window and its pairs on
    # expert 3 in the third: its sum is made window after window
    "a_token_astride_the_window_boundaries": (_experts_of_tokens(
        24, 4, {**{t: [1, 2] for t in range(8, 24)}, 7: [0, 3, HELD, 3],
                3: [0, 0, 1, 3]}), 16, 3),
    "every_pair_on_one_expert": (np.zeros(96, np.int32), 16, 6),
    "nothing_held": (np.full(96, HELD, np.int32), 16, 0),
    # 96 pairs in windows of 40 rows: `order` is padded by 24 rows that
    # repeat pair 0, which is held, and the third window runs over them
    # and over the rows of the five pairs not held
    "padding_rows_repeat_a_held_pair": (_all_on_expert_0_but(
        24, 4, [13, 14, 40, 77, 95]), 40, 3),
}


@FORMS
@pytest.mark.parametrize("case", list(COMBINE_CASES))
def test_gather_combine_against_a_scatter_add_oracle(case, kernel):
    """held_experts' output, dx, d pair_weight and the weights' gradients
    as the scatter-add gave them, every pair added once and none dropped;
    and the same bits on a second run: the order of the float32 additions
    is fixed by the pair index."""
    pair_expert, window, windows = COMBINE_CASES[case]
    n, top_k = 24, 4
    kx, kp, kw, kc = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    x = jax.random.normal(kx, (n, E))
    pair_weight = jax.random.uniform(kp, (n * top_k,), minval=0.1)
    cot = jax.random.normal(kc, (n, E))
    plan = moe_ops.plan_windows(jnp.asarray(pair_expert), HELD, window)
    assert int(plan["windows"]) == windows

    def mine(x, pw, wg, wu, wd):
        return moe_ops.held_experts(x, pw, plan, wg, wu, wd, TILE, top_k,
                                    window, kernel)

    def theirs(x, pw, wg, wu, wd):
        return scatter_oracle(x, pw, pair_expert, wg, wu, wd, top_k)
    args = (x, pair_weight, *weights(kw))
    got, vjp = jax.vjp(mine, *args)
    want, vjp_want = jax.vjp(theirs, *args)
    grads = vjp(cot)
    close(got, want)
    for a, b in zip(grads, vjp_want(cot)):
        close(a, b)
    # a token that nothing here serves reads exactly 0, and so does the
    # weight's gradient of a pair that is not held
    idle = np.all(pair_expert.reshape(n, top_k) == HELD, axis=1)
    assert not np.asarray(got)[idle].any()
    assert not np.asarray(grads[1])[pair_expert == HELD].any()
    again, vjp_again = jax.vjp(mine, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    for a, b in zip(grads, vjp_again(cot)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", list(COMBINE_CASES))
def test_pos_is_the_inverse_of_order_on_the_held_pairs(case):
    pair_expert, window, _ = COMBINE_CASES[case]
    plan = moe_ops.plan_windows(jnp.asarray(pair_expert), HELD, window)
    order, pos = np.asarray(plan["order"]), np.asarray(plan["pos"])
    held = pair_expert < HELD
    assert pos.dtype == np.int32 and pos.shape == pair_expert.shape
    np.testing.assert_array_equal(order[pos[held]], np.flatnonzero(held))
    assert (pos[held] < np.asarray(plan["bounds"])[-1]).all()
    # the others lie past every window
    assert (pos[~held] == pair_expert.size).all()
    assert order.size % window == 0


@pytest.mark.parametrize("case", list(COMBINE_CASES))
def test_a_window_token_by_token(case):
    """`_token_major` of every window that runs: the held pairs of the
    window ascending, each with its row of the expert-sorted window, every
    token's rows side by side from its `first`, at most J of them."""
    pair_expert, window, windows = COMBINE_CASES[case]
    top_k, pairs = 4, pair_expert.size
    plan = moe_ops.plan_windows(jnp.asarray(pair_expert), HELD, window)
    for w in range(windows):
        pair, _, valid, _, lo = moe_ops._window(plan, w, window, top_k)
        tm = {k: np.asarray(v) for k, v in moe_ops._token_major(
            plan, pair, valid, lo, top_k).items()}
        live = int(np.asarray(valid).sum())
        here = np.sort(np.asarray(pair)[:live])
        np.testing.assert_array_equal(tm["pair"][:live], here)
        assert (tm["pair"][live:] == pairs).all()
        np.testing.assert_array_equal(
            np.asarray(pair)[tm["row"][:live]], here)
        assert tm["count"].sum() == live and tm["count"].max() <= top_k
        for tok in np.flatnonzero(tm["count"]):
            rows = slice(tm["first"][tok], tm["first"][tok] + tm["count"][tok])
            assert (tm["tok"][rows] == tok).all()


# (the longest run of one token's rows, the window's rows, the block the
# kernel takes): runs that cross block boundaries, a halo of 8 and of 16
SEGMENT_CASES = {"runs_of_4_in_one_block": (4, 16, 16),
                 "runs_of_4_across_blocks_of_8": (4, 40, 8),
                 "runs_of_10_reach_16_rows_on": (10, 48, 16),
                 "runs_of_2": (2, 24, 8)}


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "plain"])
@pytest.mark.parametrize("case", list(SEGMENT_CASES))
def test_segment_add_kernel_to_the_bit(case, weighted):
    """`pallas_moe.segment_add` (interpret mode): a token's first row holds
    the float32 sum of its rows added in ascending order, bit for bit
    where no weight multiplies them; the rows of no pair, NaN here as a
    kernel's unwritten rows may be, add nothing; the rows past the window
    are zeros."""
    from sparknet_tpu.ops import pallas_moe
    segment, window, block = SEGMENT_CASES[case]
    assert pallas_moe.segment_block(window, segment) == block
    rng = np.random.RandomState(window)
    runs, left = [], window - 3            # the last three rows hold no pair
    while left:
        runs.append(min(left, rng.randint(1, segment + 1)))
        left -= runs[-1]
    tokens = 2 * len(runs)
    tok = np.concatenate([np.repeat(2 * np.arange(len(runs)), runs),
                          np.full(3, tokens)]).astype(np.int32)
    z = rng.randn(window, 128).astype(np.float32)
    z[-3:] = np.nan
    wt = rng.rand(window).astype(np.float32) if weighted else None
    got = np.asarray(pallas_moe.segment_add(
        jnp.asarray(z), None if wt is None else jnp.asarray(wt),
        jnp.asarray(tok), tokens, segment, block))
    assert got.shape == (window + block, 128)
    assert not got[window:].any()
    rows = z * wt[:, None] if weighted else z
    first = 0
    for run in runs:
        want = rows[first]
        for r in range(first + 1, first + run):
            want = want + rows[r]
        if weighted:    # the CPU contracts a product and an add into one
            np.testing.assert_allclose(got[first], want, rtol=2e-6,
                                       atol=1e-6)
        else:
            np.testing.assert_array_equal(got[first], want)
        first += run


def test_segment_add_has_no_block_for_a_long_segment_in_a_short_window():
    from sparknet_tpu.ops import pallas_moe
    # 9 rows on need a halo of 16, and a window of 24 rows has blocks of 8
    assert pallas_moe.segment_block(24, 10) == 0
    assert pallas_moe.segment_block(6400, 10) == 256
    assert pallas_moe.segment_block(30720, 6) == 512
    # a segment above 8 rows in blocks of 256 at most: 512 of them and their
    # shifted copies miss VMEM (tests/test_tpu_compile.py compiles
    # Qwen3-Next's net at batch 8, a window of 50 x 512 rows)
    assert pallas_moe.segment_block(40960, 8) == 512
    assert pallas_moe.segment_block(25600, 10) == 256


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


@pytest.mark.parametrize("shape,top_k,held,experts,named,tile", [
    # 3,072 rows an expert: six whole tiles of 512, and the experts' weights
    # are fetched once for 512 rows and not once for 128 (PR 47)
    pytest.param((3, 8192, 128), 4, 8, 32, None, 512, id="lfm2"),
    pytest.param((2, 16384, 128), 6, 8, 64, None, 512, id="smallthinker"),
    # 2,048 rows an expert: 512 tied with 256 in the cell's step, and the
    # tie keeps the tile the cell had: 32,768 even pairs, whose quarter more
    # is 40,960 rows, 256 tiles of 160, so never under two 128s a tile
    pytest.param((1, 32768, 128), 8, 16, 128, None, 256, id="keye"),
    # 320 rows an expert keep 128; 768 and 512 take 256
    pytest.param((2, 8192, 128), 10, 16, 512, None, 128, id="qwen3_next"),
    pytest.param((2, 8192, 128), 6, 8, 128, None, 256, id="nemotron"),
    pytest.param((1, 8192, 128), 4, 8, 64, None, 256, id="glm"),
    # the LFM2 net at batch 4: 4,096 rows an expert, and the window's floor
    # of 256 under it
    pytest.param((4, 8192, 128), 4, 8, 32, None, 512, id="lfm2_batch_4"),
    # the edges: 3,072 rows an expert take 512 and one row fewer 256, 512
    # take 256 and one fewer 128
    pytest.param((3, 8192, 128), 8, 8, 64, None, 512, id="from_3072_rows"),
    pytest.param((3, 8184, 128), 8, 8, 64, None, 256, id="a_row_short_of_it"),
    pytest.param((1, 4096, 128), 8, 8, 64, None, 256, id="from_512_rows"),
    pytest.param((1, 4088, 128), 8, 8, 64, None, 128, id="a_row_short_of_512"),
    # few rows an expert, but a share so wide that 128s miss one window:
    # the floor alone sets the tile
    pytest.param((1, 32768, 128), 2, 128, 256, None, 256,
                 id="the_floor_of_one_window"),
    # 128s and 256s miss the window, 384 would not: the next on the list, so
    # that only tiles that were timed and compiled come out ...
    pytest.param((1, 32768, 128), 3, 192, 256, None, 512,
                 id="a_floor_off_the_list"),
    # ... save where the list's largest misses it too
    pytest.param((1, 65536, 128), 4, 128, 256, None, 640,
                 id="a_floor_above_the_list"),
    # a toy layer's groups are smaller than any tile: 128
    pytest.param((1, 16, 128), 2, 4, 8, None, 128, id="toy"),
    # a tile the net names is the layer's, whatever spills
    pytest.param((1, 32768, 128), 8, 16, 128, 128, 128, id="named_128"),
    pytest.param((3, 8192, 128), 4, 8, 32, 128, 128, id="named_under_rule"),
    pytest.param((1, 16, 128), 2, 4, 8, 8, 8, id="named_8")])
def test_a_layer_that_names_no_tile_gets_one_its_even_share_fits(
        shape, top_k, held, experts, named, tile):
    lp = dsl.MoELayer("moe", ["x"], experts, hidden_dim=128, top_k=top_k,
                      experts_held=held, first_expert=0, tile_rows=named)
    impl = get_layer(lp.type)(lp, [shape], 0)
    assert impl.tile == tile
    n = shape[0] * shape[1]
    even = n * top_k * held / experts
    if named is None:
        # one window still takes an even routing and a quarter more (or
        # every pair that can land here, where that is less) ...
        want = min(1.25 * even, n * min(top_k, held))
        assert moe_ops.window_rows(n, top_k, held, experts, tile) >= want
        # ... and a tile above 128 is one the expected group reaches the
        # least rows of, unless the next tile down would miss that window
        below = max([t for t in moe_ops.ROW_TILES if t < tile], default=128)
        least = dict(zip(moe_ops.ROW_TILES, moe_ops.LEAST_ROWS))
        assert tile == 128 or n * top_k / experts >= least.get(tile, 1e9) \
            or moe_ops.window_rows(n, top_k, held, experts, below) < want


@pytest.mark.parametrize("widths,itemsize,fitted,tile", [
    # the six cells' widths in their compute type, bfloat16, at the tile
    # their routing fits: the blocks fit VMEM, the tile stays
    pytest.param((2048, 1792), 2, 512, 512, id="lfm2"),
    pytest.param((2560, 768), 2, 512, 512, id="smallthinker"),
    pytest.param((2048, 768), 2, 256, 256, id="keye"),
    pytest.param((2048, 512), 2, 128, 128, id="qwen3_next"),
    pytest.param((2688, 1920), 2, 256, 256, id="nemotron"),
    pytest.param((2048, 1536), 2, 256, 256, id="glm"),
    # 3,072 rows an expert and more fit 512 by the routing; at 2,688 x 1,920
    # the float32 output block (512, 2688), twice and its accumulator, is
    # 16.5 MB alone, and 512 x 2,048's is 12 MB beside 5 MB of operands
    pytest.param((2688, 1920), 2, 512, 256, id="nemotron_3072_rows"),
    pytest.param((2048, 512), 2, 512, 256, id="qwen3_next_3072_rows"),
    # float32 operands are twice the bytes: a tile down, at Nemotron's
    # widths from its own cell's 256
    pytest.param((2048, 1792), 4, 512, 256, id="lfm2_float32"),
    pytest.param((2560, 768), 4, 512, 256, id="smallthinker_float32"),
    pytest.param((2688, 1920), 4, 256, 128, id="nemotron_float32"),
    pytest.param((2048, 768), 4, 512, 512, id="keye_float32"),
    # a floor above the list that fits stays; one that does not comes down
    # to the list
    pytest.param((2048, 768), 2, 640, 640, id="above_the_list"),
    pytest.param((2688, 1920), 2, 640, 256, id="above_the_list_too_wide")])
def test_a_fitted_tile_comes_down_to_what_the_kernels_blocks_fit(
        widths, itemsize, fitted, tile):
    """`fit_tile` reads the routing alone; on the kernels' path the layer
    takes the largest tile not above it whose blocks fit VMEM at its
    widths and compute type (tests/test_tpu_compile.py compiles both
    sides of the edge for the described chip)."""
    from sparknet_tpu.ops import pallas_moe
    assert moe_ops.kernel_tile(fitted, *widths, itemsize) == tile
    assert pallas_moe.fits(tile, *widths, itemsize)
    if tile < fitted:
        assert not pallas_moe.fits(fitted, *widths, itemsize)


# What the window of a THREE-matrix expert traces (forward and backward,
# XLA's ragged products), by activation: PR 42 gave the window a second
# form for an expert of two matrices, and the four accepted LM cells'
# steps must keep their text. Digests RETAKEN at PR 48 on its own tree: the
# parent's text cannot be kept, the backward's first window moved out of
# its loop (e3787889089128bb and 4364e0255f8740b2 before).
THREE_MATRIX_JAXPRS = {"silu": "413c8ca2648edeea",
                       "relu": "0d850c70e6be11a9"}


def _window_loss(act, gate=True):
    """(value and gradients of a toy layer's summed output on the XLA
    path, its arguments: 32 tokens x top-2, 4 of 8 experts held, a window
    of 40 rows)."""
    n, e, f, held, k, tile = 32, 16, 24, 4, 2, 8
    window = moe_ops.window_rows(n, k, held, 8, tile)
    x = jnp.zeros((n, e), jnp.bfloat16)
    pw = jnp.zeros((n * k,), jnp.float32)
    pe = jnp.zeros((n * k,), jnp.int32)
    wu = jnp.zeros((held, f, e), jnp.bfloat16)
    wd = jnp.zeros((held, e, f), jnp.bfloat16)

    def loss(x, pw, wg, wu, wd):
        plan = moe_ops.plan_windows(pe, held, window)
        return jnp.sum(moe_ops.held_experts(x, pw, plan, wg, wu, wd, tile,
                                            k, window, False, act))
    return (jax.value_and_grad(loss, (0, 1, 2, 3, 4)),
            (x, pw, wu if gate else None, wu, wd))


def _window_digest(act, gate=True):
    import hashlib
    fun, args = _window_loss(act, gate)
    text = str(jax.make_jaxpr(fun)(*args))
    assert "/root" not in text and "0x" not in text    # no path, no address
    return hashlib.sha256(text.encode()).hexdigest()[:16], text


@pytest.mark.parametrize("act", list(THREE_MATRIX_JAXPRS))
def test_the_three_matrix_window_traces_as_before(act):
    assert _window_digest(act)[0] == THREE_MATRIX_JAXPRS[act]


def test_the_two_matrix_window_is_two_products_and_two_weight_gradients():
    three, two = _window_digest("silu")[1], _window_digest("relu2", False)[1]
    # forward 3 and 2; backward the same recomputed, dh, dx (2 and 1) and
    # the weight gradients (3 and 2)
    # of ONE window a pass: the backward's first window and the body of
    # the loop over those after it bind one traced function (PR 48: a
    # second trace of it is a second of every LM cell's warm set-up)
    assert three.count("ragged_dot_general") == 3 + 3 + 1 + 2 + 3
    assert two.count("ragged_dot_general") == 2 + 2 + 1 + 1 + 2


def _zero_fills(text, shape):
    """How many float32 arrays of `shape` (as StableHLO spells it, "32x16")
    a lowered module fills with zeros: broadcasts of a scalar constant 0,
    function by function (a constant's name is its function's)."""
    fills = 0
    for func in text.split("func.func")[1:]:
        zeros = re.findall(r"(%\w+) = stablehlo\.constant "
                           r"dense<0\.0+e\+00> : tensor<f32>", func)
        fills += sum(len(re.findall(
            rf"broadcast_in_dim {z}, dims = \[\] : \(tensor<f32>\) -> "
            rf"tensor<{shape}xf32>", func)) for z in zeros)
    return fills


@pytest.mark.parametrize("matrices,act,products", [
    (3, "silu", (3, 9)), (2, "relu2", (2, 6))],
    ids=["three_matrices", "two_matrices"])
def test_the_backwards_first_window_stands_outside_the_loop(
        matrices, act, products):
    """Lowered for a TPU (the XLA path: `chlo.ragged_dot`), the module
    holds the forward window once and the backward window twice, before
    the loop over the windows after the first and inside it, and the
    backward's totals start as the first window's own: no float32 zeros of
    dx's or a weight gradient's shape are made; the forward's loop still
    starts from zeros of the result's shape. (The window is TRACED once,
    which the count of ragged products in the jaxpr pins above; ISSUE 48
    also asked for it "lowered once": jax 0.9 lowers a jitted function
    apart where one call lies outside a `while` and one inside, and XLA
    inlines every call, PERF.md section 6, PR 48.)"""
    forward, backward = products
    fun, args = _window_loss(act, matrices == 3)
    text = jax.jit(fun).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('"chlo.ragged_dot"(') == forward + 2 * backward
    assert text.count("stablehlo.while") >= 2
    # x is (32, 16) and a weight (4, 24, 16) or (4, 16, 24): the parent's
    # loops started from such zeros, y forward, dx and the weight
    # gradients' totals backward; y's stay, and d pair_weight's 64 scalars
    assert _zero_fills(text, "32x16") == 1
    assert not _zero_fills(text, "4x24x16") + _zero_fills(text, "4x16x24")
    assert _zero_fills(text, "64")


@pytest.mark.parametrize("k,n,most,want", [
    # the accepted cells' widths halve down to their blocks, as before
    (2048, 512, 1 << 20, (2048, 512)), (2048, 512, 1 << 19, (1024, 512)),
    (2560, 768, 1 << 20, (1280, 768)), (768, 2560, 1 << 19, (768, 640)),
    (2048, 1792, 1 << 20, (1024, 896)), (1792, 2048, 1 << 19, (896, 512)),
    # 2,688 = 21 x 128 and 1,920 = 15 x 128 have no such half: divisors in
    # whole lane rows, the most elements that fit (PR 42; a cut to ONE lane
    # row, 21 steps of (128, 1920), read 9.5% of the products' roofline)
    (2688, 1920, 1 << 20, (2688, 384)), (1920, 2688, 1 << 20, (384, 2688)),
    (2688, 1920, 1 << 19, (896, 384)), (1920, 2688, 1 << 19, (384, 896)),
    (128, 128, 1 << 10, (128, 128))])
def test_block_sizes_of_the_grouped_products(k, n, most, want):
    from sparknet_tpu.ops import pallas_moe
    tk, tn = pallas_moe._blocks(k, n, most)
    assert (tk, tn) == want
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0


def moe_paths(mark):
    return [(s["layer"], s["path"], s["reason"], s["combine"], s["segment"],
             s["tile"], s["rows_an_expert"], s["window"])
            for s in default_tracer().since(mark, "moe.path")]


@pytest.mark.parametrize("backend,widths,tile,path,reason", [
    ("cpu", (128, 128), 8, "xla", "the backend is cpu, not a TPU"),
    ("tpu", (64, 128), 8, "xla", "the width 64 is not a multiple of the "
                                 "lane width 128"),
    # a hidden width off the lane width is the kernels' to pad (PR 42)
    ("tpu", (128, 64), 8, "kernel", "backend, widths and tile_rows fit; "
     "the hidden width 64 padded by 64 zero columns in the cast copies"),
    ("tpu", (128, 128), 4, "xla", "tile_rows 4 is not a multiple of 8"),
    ("tpu", (128, 128), 8, "kernel", "backend, widths and tile_rows fit")])
def test_layer_takes_the_product_it_can_and_records_it(
        monkeypatch, backend, widths, tile, path, reason):
    """One `moe.path` record a trace of the layer, as `gdn.path`: the
    kernels on a TPU backend where widths and tile_rows allow, else XLA's
    ragged product over the same window, the combine's form beside it.
    The backend is the test's to pretend: the program has no option for
    it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    embed, hidden = widths
    name = f"moe_{backend}_{hidden}_{tile}"
    lp = dsl.MoELayer(name, ["x"], 8, hidden_dim=hidden, top_k=2,
                      experts_held=4, first_expert=0, tile_rows=tile)
    impl = get_layer(lp.type)(lp, [(1, 16, embed)], 0)
    blobs = [jax.ShapeDtypeStruct(s[0], jnp.float32)
             for s in impl.param_shapes()]
    before = default_tracer().mark()
    text = str(jax.make_jaxpr(
        lambda p, x: impl.apply(p, [x], True, None)[0])(
        blobs, jax.ShapeDtypeStruct((1, 16, embed), jnp.float32)))
    # the combine gathers, a token's at most min(top_k, held) = 2 rows of a
    # window added by segments; the tile the net named, the 16 x 2 / 8 = 4
    # rows an even routing sends an expert, and the window of 20 pairs in
    # whole tiles
    assert moe_paths(before) == [(name, path, reason, "gather", 2, tile, 4,
                                  -(-20 // tile) * tile)]
    # ... and the pass whose first window stands outside the loop over
    # windows (PR 48)
    assert [s["first_window"] for s in default_tracer().since(
        before, "moe.path")] == ["backward"]
    assert ("pallas_call" in text) == (path == "kernel")
    assert ("ragged_dot" in text) == (path == "xla")
    # one structure either way: a loop of dynamic length over windows, and
    # since PR 39 no scatter of float32 rows in its forward pass (what is
    # left are the kernels' group metadata, a few scalars)
    assert " while[" in text
    assert not re.search(r":f32\[\d+,\d+\] = scatter", text)
    assert "gather" in text and "sort" in text


@pytest.mark.parametrize("shape,top_k,experts,hidden,dtype,tile,window", [
    # the LFM2 cell: 3,072 rows an expert, blocks of (512, 1024, 896)
    pytest.param((3, 8192, 2048), 4, 32, 1792, jnp.bfloat16, 512, 30720,
                 id="lfm2"),
    # Nemotron's widths at batch 8, 3,072 rows an expert: the routing fits
    # 512 and the blocks do not, in float32 not 256 either
    pytest.param((8, 8192, 2688), 6, 128, 1856, jnp.bfloat16, 256, 30720,
                 id="nemotron_batch_8"),
    pytest.param((8, 8192, 2688), 6, 128, 1856, jnp.float32, 128, 30720,
                 id="nemotron_batch_8_float32")])
def test_layer_on_the_kernels_takes_a_tile_their_blocks_fit(
        monkeypatch, shape, top_k, experts, hidden, dtype, tile, window):
    """A trace of the layer at a zoo net's real widths on a pretended TPU
    backend (shapes only: nothing is allocated): `moe.path` carries the
    tile the kernels got, which is the routing's (`impl.tile`) brought
    down to what fits VMEM, and the window in whole tiles of it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lp = dsl.MoELayer("moe_fit", ["x"], experts, hidden_dim=hidden,
                      top_k=top_k, experts_held=8, first_expert=0)
    impl = get_layer(lp.type)(lp, [shape], 0)
    n = shape[0] * shape[1]
    assert impl.tile == moe_ops.fit_tile(n, top_k, 8, experts) == 512
    blobs = [jax.ShapeDtypeStruct(s[0], jnp.float32)
             for s in impl.param_shapes()]
    before = default_tracer().mark()
    text = str(jax.make_jaxpr(
        lambda p, x: impl.apply(p, [x], True, None)[0])(
        blobs, jax.ShapeDtypeStruct(shape, dtype)))
    (path,) = moe_paths(before)
    assert path[1] == "kernel"
    assert path[5:] == (tile, round(n * top_k / experts), window)
    assert "pallas_call" in text
