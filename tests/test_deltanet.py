"""The gated delta rule of `ops/deltanet.py` (Qwen3-Next's linear-attention
mixer) in its XLA form: the chunked rule against the reference's token
recurrence (`benchmark/reference/qwen3_next.py`), the triangular inverse
it solves with, and the whole layer. The pallas kernel pair of the same
rule is `tests/test_pallas_deltanet.py`'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl
from sparknet_tpu.ops import deltanet
from tests import lm_family as lm
from tests.lm_family import close, fill, layer, ref  # noqa: F401  (fixture)

FAMILY = lm.QWEN3_NEXT
TOY = FAMILY.toy


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
    impl = layer(lp, [(2, 128, 32)])
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
