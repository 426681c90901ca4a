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


# -- shared key-value heads and head size 256 (the grouped-query form) -------

def _gqa(b, h, hkv, s, d, seed=3):
    rs = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rs.randn(b, n, s, d) * 0.5,   # noqa: E731
                               jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


# (8, 2, 64): four query heads a key-value head of 64, half a lane row
@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (8, 1, 32), (4, 2, 256),
                                     (8, 2, 64)])
def test_flash_shared_kv_heads_forward(h, hkv, d):
    """Query head i reads key-value head i // (H / Hkv) in place."""
    q, k, v = _gqa(2, h, hkv, 256, d)
    out = flash_attention(q, k, v, True, None, 128, 128)
    want = dense_attention(q, jnp.repeat(k, h // hkv, axis=1),
                           jnp.repeat(v, h // hkv, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (4, 2, 256), (6, 3, 32),
                                     (8, 2, 64)])
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


def _kernel_calls(fn, *args):
    """[(name, grid)] of the pallas calls in fn's jaxpr, custom_vjp and
    all."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              tuple(eqn.params["grid_mapping"].grid)))
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner))
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


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


# what the three causal kernels traced to at PR 34 (sha256 of the jaxpr's
# text, forward and both backward kernels, bfloat16, S 256 in blocks of
# 128): the accepted cells' heads. A PR that changes the kernels on purpose
# says so and brings new digests; one that adds a head size does not.
CAUSAL_JAXPRS = {
    (4, 2, 128): "e52ee4a1a0c597a6",
    (4, 1, 256): "693aa00dbe24f150"}


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
