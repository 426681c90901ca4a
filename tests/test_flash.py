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


@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (8, 1, 32), (4, 2, 256)])
def test_flash_shared_kv_heads_forward(h, hkv, d):
    """Query head i reads key-value head i // (H / Hkv) in place."""
    q, k, v = _gqa(2, h, hkv, 256, d)
    out = flash_attention(q, k, v, True, None, 128, 128)
    want = dense_attention(q, jnp.repeat(k, h // hkv, axis=1),
                           jnp.repeat(v, h // hkv, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("h,hkv,d", [(4, 2, 64), (4, 2, 256), (6, 3, 32)])
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
