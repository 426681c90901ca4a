"""Pallas flash-attention kernel: forward and blockwise backward vs the
dense reference (interpret mode on the CPU mesh; the same kernels compile
on TPU — see bench/graft smoke)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.ops.pallas_attention import flash_attention
from sparknet_tpu.parallel.ring import dense_attention


def _rand_qkv(b, h, s, d, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, s, d) * 0.5, dtype)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _rand_qkv(2, 3, 256, 64)
    out = flash_attention(q, k, v, causal, None, 128, 128)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """The blockwise vjp (P re-derived from the saved LSE) must equal the
    dense autodiff gradient for all three operands."""
    q, k, v = _rand_qkv(1, 2, 256, 64, seed=1)
    tgt = jnp.asarray(np.random.RandomState(9).randn(1, 2, 256, 64),
                      jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, None, 128, 128)
        return jnp.sum((o - tgt) ** 2)

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, causal=causal)
        return jnp.sum((o - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_multi_block_recurrence():
    """More K blocks than one forces the m/l running rescale and the
    backward's cross-block accumulation."""
    q, k, v = _rand_qkv(1, 1, 512, 32, seed=2)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 128, 128) ** 2)

    def fd(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(float(f(q, k, v)), float(fd(q, k, v)),
                               rtol=1e-5)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(fd, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_bf16_inputs():
    q, k, v = _rand_qkv(1, 2, 256, 64, seed=3, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, False, None, 128, 128)
    assert out.dtype == jnp.bfloat16
    want = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=3e-2)


def test_flash_fits_blocks_to_indivisible_sequence():
    """Requested blocks that don't divide S auto-shrink to the largest
    (multiple-of-8) divisor; the result stays exact. Sequences with no
    usable divisor (e.g. prime) raise instead of near-hanging."""
    q, k, v = _rand_qkv(1, 1, 96, 32)
    out = flash_attention(q, k, v, False, None, 64, 64)   # 96 % 64 -> 48
    want = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    q, k, v = _rand_qkv(1, 1, 1031, 8)    # prime S > max block
    with pytest.raises(ValueError, match="usable flash block"):
        flash_attention(q, k, v, False)


@pytest.mark.parametrize("block,s,want", [
    (512, 1280, 256),       # 320 divides too, but is no whole lane row
    (512, 640, 128),
    (64, 96, 48),           # no lane-aligned divisor: the sublane-aligned one
    (512, 8192, 512)])
def test_fit_block_prefers_whole_lane_rows(block, s, want):
    """A query block is the lane dimension of a score tile: of the
    divisors of S the lane-aligned come first."""
    from sparknet_tpu.ops.pallas_attention import _fit_block
    assert _fit_block(block, s) == want


# -- shared key-value heads and head size 256 (the grouped-query form) -------

def _gqa(b, h, hkv, s, d, seed=3):
    rs = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rs.randn(b, n, s, d) * 0.5,   # noqa: E731
                               jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


# (8, 2, 64): four query heads a key-value head of 64, half a lane row
# (32, 2, 128): sixteen query heads a key-value head, the hybrid
# state-space model's ratio (4, 7 and 8 before PR 42)
@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (8, 1, 32), (4, 2, 256),
                                     (8, 2, 64), (32, 2, 128)])
def test_flash_shared_kv_heads_forward(h, hkv, d):
    """Query head i reads key-value head i // (H / Hkv) in place."""
    q, k, v = _gqa(2, h, hkv, 256, d)
    out = flash_attention(q, k, v, True, None, 128, 128)
    want = dense_attention(q, jnp.repeat(k, h // hkv, axis=1),
                           jnp.repeat(v, h // hkv, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (4, 2, 256), (6, 3, 32),
                                     (8, 2, 64), (32, 2, 128)])
def test_flash_shared_kv_heads_backward(h, hkv, d):
    """A shared head's gradient is the sum over its group, made inside the
    dK/dV kernel; dk and dv come back with the key-value heads' shape."""
    q, k, v = _gqa(1, h, hkv, 256, d, seed=4)
    tgt = jnp.asarray(np.random.RandomState(8).randn(1, h, 256, d),
                      jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum((flash_attention(q, k, v, True, None, 128, 128)
                        - tgt) ** 2)

    def loss_dense(q, k, v):
        o = dense_attention(q, jnp.repeat(k, h // hkv, axis=1),
                            jnp.repeat(v, h // hkv, axis=1), causal=True)
        return jnp.sum((o - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_rejects_heads_that_do_not_divide():
    q, k, v = _gqa(1, 4, 3, 128, 32)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, k, v, True, None, 128, 128)


# -- the sliding window: key j visible to query i iff i - window < j <= i ---

def _masked_dense(q, k, v, window):
    """Masked-softmax attention with the mask written out here (not the
    program's dense path): shared key-value heads repeated."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = q.shape[2]
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & (i - j < window if window else True)
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand_gqa(h, hkv, s, d, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rs.randn(2, n, s, d) * 0.5,  # noqa: E731
                               jnp.float32)
    return mk(h), mk(hkv), mk(hkv), mk(h)


# (S, window, block_q, block_k, heads, kv heads): S not a multiple of the
# window, window = one block, a window off every block edge, unlike block
# sizes either way, 7 queries a key-value head
WINDOW_CASES = {
    "s320_w128": (320, 128, 64, 64, 2, 1),
    "window_is_one_block": (256, 64, 64, 64, 4, 2),
    "window_off_the_blocks": (256, 100, 64, 32, 2, 2),
    "wide_key_blocks": (256, 37, 32, 64, 2, 1),
    "wide_query_blocks": (384, 64, 128, 64, 2, 1),
    "seven_queries_a_kv_head": (256, 96, 64, 64, 7, 1),
    # a window EQUAL to the block, so that every live tile is masked (the
    # diagonal's by the causal edge, the one before it by the window's), at
    # eight and at six query heads a key-value head
    "window_is_the_block_group_of_8": (256, 64, 64, 64, 8, 1),
    "window_is_the_block_group_of_6": (256, 64, 64, 64, 12, 2),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_window_kernels_match_masked_dense(case):
    s, window, bq, bk, h, hkv = WINDOW_CASES[case]
    q, k, v, cot = _rand_gqa(h, hkv, s, 32, seed=3)
    out = flash_attention(q, k, v, True, None, bq, bk, window)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_masked_dense(q, k, v, window)),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, bq, bk, window) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _masked_dense(q, k, v, window) * cot), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)
    # the program's dense path masks the same way
    rep = lambda a: jnp.repeat(a, h // hkv, axis=1)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(dense_attention(q, rep(k), rep(v), causal=True,
                                   window=window)),
        np.asarray(_masked_dense(q, k, v, window)), atol=2e-5, rtol=2e-5)


def _pallas_calls(fn, *args):
    """The pallas_call equations in fn's jaxpr, custom_vjp and all."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner))
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _kernel_calls(fn, *args):
    """[(name, grid)] of fn's pallas calls."""
    return [(eqn.params["name"], tuple(eqn.params["grid_mapping"].grid))
            for eqn in _pallas_calls(fn, *args)]


def test_window_grids_are_the_band_and_dead_blocks_are_skipped():
    """S 512, window 128, blocks of 64: a query block's band is 3 key
    blocks of the 8, a key block's 3 query blocks, and the kernels carry
    the window's names."""
    q, k, v, cot = _rand_gqa(4, 2, 512, 32)
    step = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64, 128) * cot), (0, 1, 2))
    assert _kernel_calls(step, q, k, v) == [
        ("flash_swa_fwd", (8, 8, 3)), ("flash_swa_dq", (8, 8, 3)),
        ("flash_swa_dkv", (4, 8, 2 * 3))]
    from sparknet_tpu.ops.pallas_attention import band_blocks
    assert band_blocks(512, 128, 64, 64) == (1 + 2 + 6 * 3, 36)
    # the cell's shape: under half of the causal half's blocks
    live, causal = band_blocks(16384, 4096)
    assert (live, causal) == (252, 528) and live / causal < 0.5
    assert band_blocks(16384, 0) == (528, 528)


@pytest.mark.parametrize("window", [0, 256, 1000])
def test_no_window_and_a_window_that_covers_the_sequence_are_the_causal_form(
        window):
    """The accepted cell's guard: window 0, and a window >= S, trace the
    three kernels as the causal form does (equal jaxprs: no window term,
    the square's grid, the same names), so the results are its results bit
    for bit."""
    q, k, v, cot = _rand_gqa(4, 2, 256, 32, seed=5)

    def step(window):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, None, 64, 64, window) * cot), (0, 1, 2))
    causal = jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64) * cot), (0, 1, 2))
    assert str(jax.make_jaxpr(step(window))(q, k, v)) == \
        str(jax.make_jaxpr(causal)(q, k, v))
    assert [n for n, _ in _kernel_calls(step(window), q, k, v)] == [
        "flash_fwd", "flash_dq", "flash_dkv"]
    got, want = step(window)(q, k, v), causal(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_needs_causal():
    q, k, v = _rand_qkv(1, 2, 128, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 64, 64, 32)


# -- a tile's mask and fetches, from its block indices alone (PR 37) ---------

# (S, window, block_q, block_k): the window cases, the causal form at equal
# and unlike blocks, a window smaller than a block, one that ends on a
# block edge
TILE_CASES = {
    **{name: case[:4] for name, case in WINDOW_CASES.items()},
    "causal": (256, 0, 64, 64),
    "causal_wide_key_blocks": (256, 0, 32, 64),
    "causal_wide_query_blocks": (384, 0, 128, 64),
    "window_under_a_block": (256, 24, 64, 64),
    "window_of_two_blocks": (512, 128, 64, 64),
}


@pytest.mark.parametrize("case", list(TILE_CASES))
def test_interior_tiles_have_no_hidden_pair_and_edge_tiles_have_one(case):
    """The kernels' scalar predicate against the (S, S) mask written out: a
    live block called interior has every pair visible, every other live
    block hides a pair, and `edge_blocks` counts the latter."""
    from sparknet_tpu.ops import pallas_attention as pa
    s, window, bq, bk = TILE_CASES[case]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    edges = 0
    for qi in range(s // bq):
        first, last = pa._key_band(qi, bq, bk, window, np)
        for kj in range(s // bk):
            tile = seen[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            assert tile.any() == (first <= kj <= last), (qi, kj)
            if not tile.any():
                continue
            inside = bool(pa._interior(qi, kj, bq, bk, window, np))
            assert inside == bool(tile.all()), (qi, kj)
            edges += not inside
    assert edges == pa.edge_blocks(s, window, bq, bk)
    assert 0 < edges <= pa.band_blocks(s, window, bq, bk)[0]


# the cells' shapes in blocks of 512: (masked, live) blocks a head
@pytest.mark.parametrize("s,window,want", [
    (16384, 4096, (56, 252)),       # SmallThinker's window layers
    (16384, 0, (32, 528)),          # its global layer
    (8192, 0, (16, 136)),           # the Qwen3-Next and LFM2 cells
    (8192, 512, (31, 31))])         # a window of one block: all masked
def test_masked_blocks_at_the_cells_shapes(s, window, want):
    from sparknet_tpu.ops.pallas_attention import band_blocks, edge_blocks
    assert (edge_blocks(s, window), band_blocks(s, window)[0]) == want


def _block_specs(fn, *args):
    """{kernel name: (grid, [in block mapping])} of fn's pallas calls."""
    maps = {}
    for eqn in _pallas_calls(fn, *args):
        gm = eqn.params["grid_mapping"]
        maps[eqn.params["name"]] = (
            tuple(gm.grid), list(gm.block_mappings[:gm.num_inputs]))
    return maps


def _index(mapping, *ids):
    """The block index a block mapping's index map gives at a grid point."""
    jaxpr = mapping.index_map_jaxpr
    return tuple(int(x) for x in jax.core.eval_jaxpr(
        jaxpr.jaxpr, jaxpr.consts, *(np.int32(i) for i in ids)))


# (S, block_q, block_k, heads, key-value heads) of the causal form
DEAD_STEP_CASES = {"square": (256, 64, 64, 2, 1),
                   "wide_key_blocks": (256, 32, 64, 2, 2),
                   "wide_query_blocks": (384, 128, 64, 4, 2)}


def _streamed(maps, *live_steps):
    """The operands whose block moves along the innermost grid axis."""
    one, other = live_steps
    found = [m for m in maps if _index(m, *one) != _index(m, *other)]
    assert len(found) >= 2
    return found


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
@pytest.mark.parametrize("case", list(DEAD_STEP_CASES))
def test_a_dead_causal_step_fetches_nothing(case, kernel):
    """In every dead step of the causal grids the index map of every
    streamed operand (a block of rows, or of the columns of a transposed
    operand or a row of statistics) gives the block of the neighbouring
    live step of the same row (a repeated index is not fetched again),
    and in every live step the step's own block."""
    s, bq, bk, h, hkv = DEAD_STEP_CASES[case]
    grp, nq, nk = h // hkv, s // bq, s // bk
    q, k, v, cot = _rand_gqa(h, hkv, s, 32)
    grid, maps = _block_specs(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, None, bq, bk) * cot), (0, 1, 2)),
        q, k, v)[kernel]
    dead = 0
    if kernel == "flash_dkv":       # (kv head, key block, group x query block)
        assert grid == (2 * hkv, nk, grp * nq)
        streamed = _streamed(maps, (1, 0, 0), (1, 0, 1))    # q, dO, lse, delta
        assert len(streamed) == 4
        for j in range(nk):
            first = (j * bk) // bq          # the diagonal's query block
            for t in range(grp * nq):
                head, i = grp + t // nq, max(t % nq, first)
                dead += t % nq < first
                for m in streamed:
                    assert _index(m, 1, j, t) in ((head, i, 0),
                                                  (head, 0, i)), (j, t)
        assert dead == grp * sum((j * bk) // bq for j in range(nk)) > 0
        return
    assert grid == (2 * h, nq, nk)
    streamed = _streamed(maps, (3, nq - 1, 0), (3, nq - 1, 1))  # of k, v
    for i in range(nq):
        last = (i * bq + bq - 1) // bk      # the diagonal's key block
        for j in range(nk):
            dead += j > last
            for m in streamed:
                assert _index(m, 3, i, j) in ((3 // grp, min(j, last), 0),
                                              (3 // grp, 0, min(j, last)))
    assert dead == sum(nk - 1 - (i * bq + bq - 1) // bk
                       for i in range(nq)) > 0


def test_without_a_mask_no_step_is_dead_and_no_index_is_held():
    q, k, v, cot = _rand_gqa(2, 1, 256, 32)
    for name, (grid, maps) in _block_specs(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, False, None, 64, 64) * cot),
            (0, 1, 2)), q, k, v).items():
        for m in _streamed(maps, (0, 0, 0), (0, 0, 1)):
            assert [max(_index(m, 0, 0, t)[1:]) for t in range(4)] == [
                0, 1, 2, 3], name


# what the three causal kernels trace to since PR 37 (sha256 of the jaxpr's
# text, forward and both backward kernels, bfloat16, S 256 in blocks of
# 128): the accepted cells' heads, with the LFM2 cell's 64. PR 37 changed
# the kernels on purpose (score tiles transposed and the statistics rows,
# dead steps hold their index, edge tiles alone are masked, scale on q's
# block, delta an operand in place of O) and brought these digests in place
# of PR 34's. A PR that changes the kernels on purpose says so and brings
# new ones; one that adds a head size does not.
CAUSAL_JAXPRS = {
    (4, 2, 128): "23bf00bd611a084a",
    (4, 1, 256): "f3cc40f40db72e79",
    (4, 1, 64): "30209550953bf34b"}


@pytest.mark.parametrize("shape", list(CAUSAL_JAXPRS))
def test_causal_kernels_at_the_accepted_heads_trace_as_before(shape):
    import hashlib
    h, hkv, d = shape
    q = jnp.zeros((1, h, 256, d), jnp.bfloat16)
    k = jnp.zeros((1, hkv, 256, d), jnp.bfloat16)
    step = jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 128, 128).astype(jnp.float32)), (0, 1, 2))
    text = str(jax.make_jaxpr(step)(q, k, k))
    assert "/root" not in text and "0x" not in text    # no path, no address
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        CAUSAL_JAXPRS[shape]


# The kernels of attention over an index-picked key set (PR 40) live in a
# file of their own, ops/pallas_dsa.py, and share two names with this one
# (`NEG_INF`, `_fit_block`): the window form's trace is held here beside the
# causal form's above, so that a change made for that model in the shared
# tile code (`_scores`, `_live_tiles`, `_block_maps`, `edge_blocks`) shows.
# (heads, kv heads, head size, window) at S 256 and blocks of 128.
WINDOW_JAXPRS = {
    (4, 2, 128, 64): "80c5671c70c04caf",
    (4, 1, 64, 96): "15ba2f84258a756c"}


@pytest.mark.parametrize("shape", list(WINDOW_JAXPRS))
def test_window_kernels_trace_as_before_the_index_picked_form(shape):
    import hashlib
    h, hkv, d, window = shape
    q = jnp.zeros((1, h, 256, d), jnp.bfloat16)
    k = jnp.zeros((1, hkv, 256, d), jnp.bfloat16)
    step = jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 128, 128, window).astype(jnp.float32)),
        (0, 1, 2))
    text = str(jax.make_jaxpr(step)(q, k, k))
    assert "/root" not in text and "0x" not in text    # no path, no address
    assert "flash_swa_fwd" in text and "flash_sparse" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        WINDOW_JAXPRS[shape]
