"""The four kernels of `ops/pallas_dsa.py` (attention over an index-picked
key set: the selection's threshold search over bit planes, the sparse
core, its one-kernel backward) in interpret mode against the plain form of
`ops/dsa.py`.

Where a kernel is held against the plain form, the index's operands are
small integers and its weights multiples of 1/64 (`lm_family.exact_index`):
every index score is then exact in float32 in both, so no key at a
threshold falls one way here and the other there (ties are kept in both,
by the rule). The layer and the whole model that call these kernels:
`tests/test_keye_vl2.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops import dsa
from tests.lm_family import close, exact_index, qkv


def _losses(fn, weights):
    def run(*args):
        o, kl = fn(*args)
        return weights[0] * jnp.sum(o * jnp.cos(
            0.1 * jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape))) \
            + weights[1] * kl
    return run


# the last two: the cell's 8 query heads a key-value head, four and more key
# blocks and `topk` under a key block, so that a key block's dk, dv and d kI
# take parts from several query rows through HBM and back
@pytest.mark.parametrize("tiles", [(64, 128), (64, 64)])
@pytest.mark.parametrize("shape", [(1, 4, 2, 256, 16, 4, 8, 32),
                                   (2, 4, 4, 128, 32, 2, 16, 8),
                                   (1, 8, 2, 384, 16, 3, 8, 130),
                                   (1, 8, 1, 512, 16, 4, 8, 40),
                                   (2, 16, 2, 512, 16, 2, 8, 200)])
def test_kernels_match_the_plain_form(monkeypatch, shape, tiles):
    """Value, L_I and all six gradients, several tiles a grid axis; at
    64 x 128 the first two query rows end on key block 0 (the backward
    keeps it in its slot), at 64 x 64 the diagonal tile is row 0's only
    live one and every later row names key block 0 after another block."""
    from sparknet_tpu.ops import pallas_dsa
    b, h, hk, s, d, hi, di, topk = shape
    monkeypatch.setattr(pallas_dsa, "blocks", lambda s, *a: tiles + (32, 64))
    q, k, v = qkv(jax.random.PRNGKey(9), b, h, hk, s, d)
    qi, ki, w = exact_index(jax.random.PRNGKey(10), b, hi, s, di)
    args = (q, k, v, qi, ki, w)
    kern = _losses(lambda *a: pallas_dsa.sparse_attention(*a, topk, "L"),
                   (1.0, 1.0))
    plain = _losses(lambda *a: dsa.sparse_attention_plain(*a, topk),
                    (1.0, 1.0))
    with jax.default_matmul_precision("highest"):
        o_k, kl_k = pallas_dsa.sparse_attention(*args, topk, "L")
        o_p, kl_p = dsa.sparse_attention_plain(*args, topk)
        close(o_k, o_p, tol=1e-5)
        close(kl_k, kl_p, tol=1e-5)
        gk = jax.grad(kern, range(6))(*args)
        gp = jax.grad(plain, range(6))(*args)
    for a, b_ in zip(gk, gp):
        assert float(jnp.abs(b_).max()) > 0
        close(a, b_, tol=2e-4)


def _index_case(name, key, b, hi, s, di):
    """(qI, kI, w) with exact index scores, bent to what a threshold
    search can get wrong."""
    qi, ki, w = exact_index(key, b, hi, s, di)
    if name == "zeros":
        # relu kills every index head for most keys: a query's row is a
        # run of exact zeros with a few scores above, the threshold 0.0
        qi, w = jnp.abs(qi) + 1, jnp.abs(w) + 1 / 64
        ki = jnp.where((jnp.arange(s) % 7 == 0)[None, :, None],
                       jnp.abs(ki) + 1, -jnp.abs(ki) - 1)
    elif name == "negative":
        # every weight below zero: no score above -0.0, most far below
        w = -jnp.abs(w) - 1 / 64
    elif name == "signed_zeros":
        # odd queries weigh every head below zero, even ones above: rows
        # of -0.0 beside rows of +0.0, one integer in the search
        qi = jnp.abs(qi) + 1
        ki = jnp.where((jnp.arange(s) % 5 == 0)[None, :, None],
                       jnp.abs(ki) + 1, -jnp.abs(ki) - 1)
        w = (jnp.abs(w) + 1 / 64) * jnp.where(jnp.arange(s) % 2, -1.0, 1.0)
    return qi, ki, w


def _one_bit_search(keys, topk):
    """PR 40's search in plain jnp: the answer's bits from the top, a bit
    kept where at least `topk` of a row's sortable integers are still at
    or above the candidate. keys (..., S, S) int32 -> (..., S) int32."""
    int_min = jnp.int32(-2 ** 31)
    prefix = jnp.full(keys.shape[:-1], int_min, jnp.int32)
    for i in range(32):
        cand = prefix ^ jnp.left_shift(jnp.int32(1), 31 - i)
        count = jnp.sum(keys >= cand[..., None], axis=-1)
        prefix = jnp.where(count >= topk, cand, prefix)
    return prefix


# (the scores, sequence, topk, query block, chunk): at 32 x 64 and 32 x 32
# the early query blocks leave their last chunks unseen (and a walk's step
# of four chunks ends past the last one), at 64 x 256 the scratch is one
# chunk of 32 whole slabs (the cell's form: the smaller chunks are filled
# up to that), at 32 x 16 a block walks up to sixteen
@pytest.mark.parametrize("case,s,topk,sq,sk", [
    ("plain", 256, 24, 32, 64),
    ("zeros", 256, 24, 32, 64),
    ("negative", 256, 24, 32, 64),
    ("signed_zeros", 256, 24, 32, 32),
    ("plain", 256, 1, 32, 64),
    ("plain", 256, 37, 64, 256),
    ("zeros", 256, 100, 32, 16),
    ("plain", 128, 128, 32, 64),
    ("plain", 128, 200, 32, 32),
    ("negative", 512, 130, 128, 128),
])
def test_the_threshold_kernel_is_the_topk_th_largest(case, s, topk, sq, sk):
    from sparknet_tpu.ops import pallas_dsa
    qi, ki, w = _index_case(case, jax.random.PRNGKey(11), 2, 4, s, 8)
    thr, lse = pallas_dsa._select(qi, ki, w[:, :, None, :], topk, sq, sk,
                                  True)
    scores = dsa.index_scores(qi, ki, w)
    want = dsa.threshold(scores, topk)
    assert np.array_equal(np.asarray(thr[:, 0]), np.asarray(want))
    if case == "zeros":             # the runs are there, at the threshold
        assert float(jnp.mean(want[:, topk:] == 0.0)) > 0.5
    sel = dsa.selected(scores, topk)
    close(lse[:, 0], jax.nn.logsumexp(
        jnp.where(sel, scores, -jnp.inf), axis=-1), tol=1e-6)


@pytest.mark.parametrize("rows", [256, 64, 40])
def test_a_bit_plane_holds_one_bit_of_every_slabs_key(rows):
    """Bit 31 - b of plane i is bit 31 - i of slab b's key (plane 0, the
    sign, inverted); a chunk that is not 32 slabs of 8 rows is filled up with
    INT_MIN first."""
    from sparknet_tpu.ops import pallas_dsa
    keys = jax.random.randint(jax.random.PRNGKey(13), (rows, 4),
                              -2 ** 31, 2 ** 31 - 1, jnp.int32)
    planes = np.stack([np.asarray(p) for p in pallas_dsa._bit_planes(keys)])
    m = pallas_dsa._plane_rows(rows)
    assert planes.shape == (32, m, 4) and m % 8 == 0 and 32 * m >= rows
    full = np.full((32 * m, 4), -2 ** 31, np.int64)
    full[:rows] = np.asarray(keys)
    slabs = (full ^ -2 ** 31).reshape(32, m, 4)     # the sign bit inverted
    for i in range(32):
        want = sum(((slabs[b] >> (31 - i)) & 1) << (31 - b)
                   for b in range(32))
        assert np.array_equal(planes[i].astype(np.int64) & 0xFFFFFFFF, want)


@pytest.mark.parametrize("case", ["plain", "zeros", "signed_zeros"])
def test_the_threshold_search_finds_the_one_bit_searchs_integer(case):
    """The kernel's search against PR 40's (32 passes, one bit each) in
    plain jnp, integer for integer."""
    from sparknet_tpu.ops import pallas_dsa
    s, topk = 256, 40
    qi, ki, w = _index_case(case, jax.random.PRNGKey(12), 2, 4, s, 8)
    thr, _ = pallas_dsa._select(qi, ki, w[:, :, None, :], topk, 32, 64, True)
    keys = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                     pallas_dsa._sortable(dsa.index_scores(qi, ki, w)),
                     pallas_dsa.INT_MIN)
    want = _one_bit_search(keys, topk)
    got = pallas_dsa._sortable(thr[:, 0])
    assert np.array_equal(np.asarray(got[:, topk:]),
                          np.asarray(want[:, topk:]))
    assert np.all(np.asarray(thr[:, 0, :topk]) == -np.inf)
