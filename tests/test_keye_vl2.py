"""Keye-VL-2.0's decoder — attention over an index-picked key set and the
whole 4-block model — against the plain reference
(`benchmark/reference/keye_vl2.py`, imported from where it lies, not
copied): small widths, seeded weights, float32 on the CPU.

The reference computes index scores and main scores a block of query rows
at a time, selects by an exact `jax.lax.top_k` and writes L_I with both
stop_gradients; the program goes through `Attention` with its index fields:
the plain form (ops/dsa.py) or the four kernels of ops/pallas_dsa.py in
interpret mode.

Where a kernel is held against the plain form, the index's operands are
small integers and its weights multiples of 1/64: every index score is
then exact in float32 in both, so no key at a threshold falls one way here
and the other there (ties are kept in both, by the rule).
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.ops import dsa
from sparknet_tpu.proto import Message, text_format
from sparknet_tpu.solver.solver import Solver

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def ref():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("reference.keye_vl2")


TOY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, rope_theta=1e7, rms_norm_eps=1e-6,
           indexer_num_heads=4, indexer_head_dim=8, indexer_topk=16,
           num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
           norm_topk_prob=True, vocab_size=64, num_hidden_layers=4,
           router_outputs=16, first_expert=0, seq_len=64)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def ref_dims(**over):
    return {**TOY, "selection": "index", **over}


def attn_layer(seq=64, batch=2, flash=False, topk=16, **over):
    lp = dsl.AttentionLayer(
        "attn", ["x"], 4, head_dim=16, causal=True, flash=flash,
        num_kv_heads=2, qk_norm=True, qk_norm_zero_centered=False,
        rotary_dim=16, rope_theta=1e7, index_heads=4, index_head_dim=8,
        index_topk=topk, **over)
    return get_layer(lp.type)(lp, [(batch, seq, 32)], 0)


def fill(impl, key, std=0.3):
    out = []
    for i, (shape, filler, *_) in enumerate(impl.param_shapes()):
        if len(shape) == 1:         # norm weights near 1, the bias near 0
            base = 0.0 if filler is None else 1.0
            out.append(base + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), shape))
        else:
            out.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return out


def exact_index(key, b, hi, s, di):
    """(qI, kI, w) whose index scores are exact in float32."""
    ks = jax.random.split(key, 3)
    return (jnp.round(2 * jax.random.normal(ks[0], (b, hi, s, di))),
            jnp.round(2 * jax.random.normal(ks[1], (b, s, di))),
            jnp.round(8 * jax.random.normal(ks[2], (b, hi, s))) / 64)


def qkv(key, b=1, h=4, hk=2, s=128, d=16):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, h, s, d)),
            jax.random.normal(ks[1], (b, hk, s, d)),
            jax.random.normal(ks[2], (b, hk, s, d)))


# --------------------------------------------------- index scores, selection

def test_index_scores_and_their_loss_match_reference(ref):
    """The layer's L_I and its gradient on the five indexer blobs against
    the reference's, from the same blobs."""
    impl = attn_layer()
    assert [s[0] for s in impl.param_shapes()] == [
        (64, 32), (32, 32), (32, 32), (32, 64), (16,), (16,),
        (32, 32), (8, 32), (4, 32), (8,), (8,)]
    blobs = fill(impl, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 32))
    d = ref_dims()

    def mine(blobs):
        return impl.apply(blobs, [x], True, None)[1]

    def theirs(blobs):
        return sum(ref.attention(x[b], blobs, d)[1] for b in range(2)) / 2
    close(mine(blobs), theirs(blobs))
    assert float(mine(blobs)) > 1e-3
    gm, gt = jax.grad(mine)(blobs), jax.grad(theirs)(blobs)
    for j in range(6, 11):
        assert float(jnp.abs(gt[j]).max()) > 0, j
        close(gm[j], gt[j], tol=1e-3)


def test_index_scores_are_the_written_sum():
    qi, ki, w = exact_index(jax.random.PRNGKey(3), 1, 3, 12, 4)
    want = np.zeros((12, 12))
    for t in range(12):
        for s in range(12):
            want[t, s] = sum(
                float(w[0, j, t]) * max(float(qi[0, j, t] @ ki[0, s]), 0.0)
                for j in range(3))
    assert np.array_equal(np.asarray(dsa.index_scores(qi, ki, w)[0]), want)


@pytest.mark.parametrize("topk", [1, 5, 16, 40])
def test_selection_is_the_reference_top_k(topk):
    """Every key while t < topk, the topk largest after, never a key
    ahead; on distinct scores exactly jax.lax.top_k's set."""
    s = 40
    scores = jax.random.normal(jax.random.PRNGKey(4), (2, s, s))
    sel = np.asarray(dsa.selected(scores, topk))
    for b in range(2):
        for t in range(s):
            assert not sel[b, t, t + 1:].any()
            want = set(np.argsort(-np.asarray(scores[b, t, :t + 1]))[:topk])
            assert set(np.flatnonzero(sel[b, t])) == want, (b, t)
            assert sel[b, t].sum() == min(t + 1, topk)


def test_ties_at_the_threshold_are_all_kept():
    scores = jnp.asarray([[[0.0] * 6] * 6]).at[0, :, 0].set(1.0)
    sel = np.asarray(dsa.selected(scores, 2))
    # the 2nd largest of a row is 0 (or 1 alone at t = 0): every seen key
    assert [int(r.sum()) for r in sel[0]] == [1, 2, 3, 4, 5, 6]


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_sparse_is_dense_when_topk_covers_the_sequence(form):
    q, k, v = qkv(jax.random.PRNGKey(5))
    qi, ki, w = exact_index(jax.random.PRNGKey(6), 1, 4, 128, 8)
    causal = jnp.tril(jnp.ones((128, 128), bool))[None]
    dense, _ = dsa.sparse_attention_plain(q, k, v, qi, ki, w, 128,
                                          mask=causal)
    if form == "plain":
        got, _ = dsa.sparse_attention_plain(q, k, v, qi, ki, w, 128)
    else:
        from sparknet_tpu.ops import pallas_dsa
        got, _ = pallas_dsa.sparse_attention(q, k, v, qi, ki, w, 128)
    close(got, dense, tol=1e-5)


def test_sparse_differs_from_dense_and_from_a_window_when_topk_is_short():
    q, k, v = qkv(jax.random.PRNGKey(7))
    qi, ki, w = exact_index(jax.random.PRNGKey(8), 1, 4, 128, 8)
    back = jnp.arange(128)[:, None] - jnp.arange(128)[None, :]
    causal = (back >= 0)[None]
    got, _ = dsa.sparse_attention_plain(q, k, v, qi, ki, w, 16)
    dense, _ = dsa.sparse_attention_plain(q, k, v, qi, ki, w, 16,
                                          mask=causal)
    window, _ = dsa.sparse_attention_plain(q, k, v, qi, ki, w, 16,
                                           mask=causal & (back < 16)[None])
    # the first 16 queries see all their keys in all three forms
    close(got[:, :, :16], dense[:, :, :16], tol=1e-5)
    close(got[:, :, :16], window[:, :, :16], tol=1e-5)
    for other in (dense, window):
        gap = jnp.abs(got - other)[:, :, 16:].max(axis=(0, 1, 3))
        assert float(jnp.mean(gap > 0.05)) > 0.9


@pytest.mark.parametrize("topk,same", [(64, True), (16, False)])
def test_the_reference_can_pick_the_set_as_a_bfloat16_indexer_does(
        ref, topk, same):
    """`selection` "index_bf16" (benchmark/control_selection.py --forms):
    the set from bfloat16 index operands, every other number float32. It IS
    the reference where every key is taken; where the set is short it
    moves the output a little (a key or two at a threshold), and L_I's
    gradient still reaches the indexer's blobs through float32 scores."""
    impl = attn_layer(topk=topk)
    blobs = fill(impl, jax.random.PRNGKey(21))
    x = jax.random.normal(jax.random.PRNGKey(22), (64, 32))
    true = ref.attention(x, blobs, ref_dims(indexer_topk=topk))
    low = ref.attention(x, blobs, ref_dims(indexer_topk=topk,
                                           selection="index_bf16"))
    gap = float(jnp.abs(true[0] - low[0]).max())
    if same:
        assert gap == 0.0 and float(true[1]) == float(low[1])
        return
    assert 0.0 < gap
    # most queries keep their set: their rows are the reference's to the bit
    rows = jnp.abs(true[0] - low[0]).max(axis=1)
    assert float(jnp.mean(rows == 0.0)) > 0.5
    g = jax.grad(lambda b: ref.attention(
        x, b, ref_dims(indexer_topk=topk, selection="index_bf16"))[1])(blobs)
    assert all(float(jnp.abs(g[j]).max()) > 0 for j in range(6, 11))


# ------------------------------------------------------------- the kernels

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


@pytest.mark.parametrize("which", ["main", "index"])
def test_the_two_losses_train_disjoint_blobs(which):
    """Exactly zero, not small: L_LM (here any function of the layer's
    first top) on the five indexer blobs, L_I on the six others and on the
    layer's input."""
    for flash in (False, True):
        impl = attn_layer(seq=128, batch=1, flash=flash)
        blobs = fill(impl, jax.random.PRNGKey(12))
        x = jax.random.normal(jax.random.PRNGKey(13), (1, 128, 32))

        def loss(blobs, x):
            o, kl = impl.apply(blobs, [x], True, None)
            return jnp.sum(o * o) if which == "main" else kl
        g, gx = jax.grad(loss, (0, 1))(blobs, x)
        dead = range(6, 11) if which == "main" else range(6)
        live = range(6) if which == "main" else range(6, 11)
        for j in dead:
            assert not np.asarray(g[j]).any(), (flash, j)
        for j in live:
            assert np.asarray(g[j]).any(), (flash, j)
        assert bool(np.asarray(gx).any()) == (which == "main")


# ------------------------------------------------------------ the MoE's share

def test_eight_shares_of_sixteen_experts_add_up_to_the_whole_layer(ref):
    """The deployment's cut: 8 chips x 16 of 128 experts, router at 128,
    top-8; the parts add up to the reference's uncut layer."""
    e, f, n = 32, 16, 48
    key = jax.random.PRNGKey(14)

    def build(held, first):
        lp = dsl.MoELayer("moe", ["x"], 128, hidden_dim=f, top_k=8,
                          experts_held=held, first_expert=first,
                          norm_topk_prob=True)
        return get_layer(lp.type)(lp, [(1, n, e)], 0)
    whole = build(128, 0)
    blobs = [0.3 * jax.random.normal(jax.random.fold_in(key, i), shape)
             for i, (shape, *_) in enumerate(whole.param_shapes())]
    g = jax.random.normal(jax.random.fold_in(key, 9), (1, n, e))
    total = None
    for chip in range(8):
        lo = 16 * chip
        part = build(16, lo).apply(
            [blobs[0]] + [w[lo:lo + 16] for w in blobs[1:4]], [g], True,
            None)[0]
        total = part if total is None else total + part
    close(total, whole.apply(blobs, [g], True, None)[0], tol=5e-4)
    d = ref_dims(num_experts=128, router_outputs=128, num_experts_per_tok=8,
                 hidden_size=e, moe_intermediate_size=f)
    close(total.reshape(n, e), ref.moe(g.reshape(n, e), blobs, d), tol=5e-4)


# ---------------------------------------------------------- the whole model

def toy_net(**over):
    d = dict(TOY, **over)
    held = d.pop("num_experts")
    return zoo.keye_vl2(batch_size=2, num_experts=d.pop("router_outputs"),
                        experts_held=held, **d)


SOLVER = dict(type="Adam", base_lr=1e-3, lr_policy="fixed", momentum=0.9,
              momentum2=0.95, delta=1e-8, weight_decay=0.1)


def toy_config(**args):
    config = {k: v for k, v in TOY.items()
              if k not in ("router_outputs", "first_expert", "seq_len",
                           "indexer_num_heads", "indexer_head_dim",
                           "indexer_topk")}
    config.update(
        sa_config={"indexer_num_heads": 4, "indexer_head_dim": 8,
                   "indexer_num_kv_heads": 1, "topk": 16},
        published={"num_experts": 16},
        builder_args=dict({"seq_len": 64}, **args))
    return config


def tokens(seed=0):
    draw = np.random.RandomState(seed).randint(0, 64, (2, 65))
    return draw[:, :-1].astype(np.int32), draw[:, 1:].astype(np.int32)


def seeded(solver, reference, seed=0):
    sys.path.insert(0, BENCH)
    import weights
    w0 = weights.make_weights(reference.specs, seed)
    assert set(w0) == set(solver.params)
    for name, blobs in w0.items():
        assert [b.shape for b in blobs] == \
            [p.shape for p in solver.params[name]], name
        solver.params[name] = [jnp.array(b) for b in blobs]
    return w0


def grads_of(solver, batch):
    net = solver.net
    return jax.grad(lambda p: net.loss_fn(p, solver.state, batch)[0])(
        solver.params)


def test_the_reference_reads_the_config_and_its_sa_config(ref):
    d = ref.dims(toy_config())
    assert {k: d[k] for k in TOY} == TOY
    assert d["selection"] == "index"


def test_net_is_the_published_layout():
    net = zoo.keye_vl2(batch_size=1, seq_len=128, experts_held=16)
    by_name = {lp.name: lp for lp in net.layer}
    assert sum(1 for lp in net.layer if lp.type == "Attention") == 48
    ap = by_name["block47/attn"].attention_param
    assert (ap.num_heads, ap.num_kv_heads, ap.head_dim, ap.rotary_dim) == \
        (32, 4, 128, 128)
    assert (ap.index_heads, ap.index_head_dim, ap.index_topk) == \
        (16, 64, 2048)
    assert ap.qk_norm and not ap.qk_norm_zero_centered and ap.causal
    assert abs(ap.rope_theta - 1e7) < 1 and not ap.window
    assert list(by_name["block0/attn"].top) == ["block0/attn",
                                                "block0/attn_kl"]
    assert list(by_name["block0/attn"].loss_weight) == [0.0, 1.0]
    mp = by_name["block0/moe"].moe_param
    assert (mp.num_experts, mp.top_k, mp.experts_held, mp.hidden_dim) == \
        (128, 8, 16, 768)
    assert mp.norm_topk_prob and not mp.has("shared_hidden_dim")
    assert by_name["lm_head"].inner_product_param.num_output == 151936
    again = text_format.loads(text_format.dumps(net), "NetParameter")
    assert again == net


@pytest.mark.parametrize("flash", [False, True])
def test_whole_model_three_adam_steps_match_reference(ref, flash):
    reference = ref.build(toy_config(), 2)
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    if flash:       # two blocks where the kernels run in interpret mode
        over = dict(flash=True, seq_len=128, num_hidden_layers=2)
        solver = Solver(sp, net_param=toy_net(**over), log_fn=None)
        reference = ref.build(dict(toy_config(seq_len=128),
                                   num_hidden_layers=2), 2)
    else:
        solver = Solver(sp, net_param=toy_net(), log_fn=None)
    for name, blobs in reference.specs:
        assert solver.updater.mults[name] == [b[2] for b in blobs], name
    w0 = seeded(solver, reference)
    step = reference.make_step(SOLVER, block_rows=1)
    draw = np.random.RandomState(0).randint(
        0, 64, (2, reference.seq + 1)).astype(np.int32)
    data, labels = draw[:, :-1], draw[:, 1:]
    params, history = w0, None
    for i in range(3):
        got = float(solver.train_step({"data": data, "label": labels}))
        params, history, want, grads = step(params, history, data, labels,
                                            None)
        assert abs(got - float(want)) <= 5e-5 * abs(float(want)), i
        if i == 0:
            # the first gradient, out of Adam's first moment
            for name, blobs in grads.items():
                for j, g in enumerate(blobs):
                    decay = dict(reference.specs)[name][j][2][1]
                    m1 = solver.history[name][j][0]
                    close(m1 / 0.1 - 0.1 * decay * w0[name][j], g,
                          tol=5e-3)
    for name, blobs in params.items():
        for j, w in enumerate(blobs):
            got = np.asarray(solver.params[name][j] - w0[name][j])
            want = np.asarray(w - w0[name][j])
            assert np.linalg.norm(got - want) <= \
                0.05 * np.linalg.norm(want) + 1e-12, (name, j)


def test_the_step_loss_is_the_cross_entropy_plus_every_layers_index_loss(ref):
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(), log_fn=None)
    solver.set_scan("off")
    seeded(solver, ref.build(toy_config(), 2))
    data, labels = tokens(3)
    batch = {"data": jnp.asarray(data), "label": jnp.asarray(labels)}
    total, (blobs, _) = solver.net.loss_fn(solver.params, solver.state,
                                           batch)
    kls = [float(blobs[f"block{i}/attn_kl"]) for i in range(4)]
    assert all(k > 0 for k in kls)
    assert abs(float(total) - float(blobs["loss"]) - sum(kls)) < 1e-5


_BASELINE = {}


def _unscanned_gradients(ref, flash):
    """(batch, reference, gradients without remat or scan), once a form:
    two blocks where the kernels run in interpret mode."""
    if flash not in _BASELINE:
        sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
        seq, layers = (128, 2) if flash else (64, 2)
        draw = np.random.RandomState(1).randint(0, 64, (2, seq + 1))
        batch = {"data": jnp.asarray(draw[:, :-1], jnp.int32),
                 "label": jnp.asarray(draw[:, 1:], jnp.int32)}
        over = dict(flash=flash, seq_len=seq, num_hidden_layers=layers)
        plain = Solver(sp, net_param=toy_net(**over), log_fn=None)
        plain.set_scan("off")
        config = dict(toy_config(seq_len=seq), num_hidden_layers=layers)
        reference = ref.build(config, 2)
        seeded(plain, reference)
        _BASELINE[flash] = (batch, reference, over, grads_of(plain, batch))
    return _BASELINE[flash]


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
@pytest.mark.parametrize("flash", [False, True])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan, flash):
    """The backward reads the forward's set: a replay that selected again
    would run the selection twice, and one that selected from other
    scores would move the gradients."""
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    batch, reference, over, want = _unscanned_gradients(ref, flash)
    knobbed = Solver(sp, net_param=toy_net(**over), log_fn=None,
                     remat=remat)
    knobbed.set_scan(scan)
    assert [r["n"] for r in knobbed.net._scan_runs()] == \
        [over["num_hidden_layers"]]
    seeded(knobbed, reference)
    got = grads_of(knobbed, batch)
    for name in want:
        for a, b in zip(got[name], want[name]):
            close(a, b, tol=1e-4)


def test_the_four_blocks_scan_as_one_run_and_carry_their_losses_out():
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(), log_fn=None)
    runs = solver.net._scan_runs()
    assert [(r["n"], r["glen"], r["entry"], r["out"], r["losses"])
            for r in runs] == [(4, 6, "tok_embed", "block3/res2", [(1, 1)])]
    solver.set_scan("on")
    data, labels = tokens(2)
    batch = {"data": jnp.asarray(data), "label": jnp.asarray(labels)}
    blobs, _ = solver.net.apply(solver.params, solver.state, batch,
                                train=True)
    assert all(blobs[f"block{i}/attn_kl"].shape == () for i in range(4))
    assert "block1/attn" not in blobs
    # with the statistics' top a block has two boundary tops: no scan
    stats = Solver(sp, net_param=toy_net(index_stats=True), log_fn=None)
    assert stats.net._scan_runs() == []


def test_paths_selection_and_kept_arrays_are_recorded():
    from sparknet_tpu.obs.trace import Tracer
    ring = default_tracer()
    mark = ring.mark()
    tracer = Tracer()
    sp = Message("SolverParameter", display=1, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(flash=True, seq_len=128,
                                          index_stats=True,
                                          num_hidden_layers=2),
                    log_fn=None, tracer=tracer, remat="full")
    draw = np.random.RandomState(2).randint(0, 64, (2, 129)).astype(np.int32)
    solver.step(1, iter([{"data": draw[:, :-1], "label": draw[:, 1:]}]))
    paths = ring.since(mark, "attn.path")
    assert {r["layer"] for r in paths} == {"block0/attn", "block1/attn"}
    assert all(r["path"] == "kernel" and "index tile" in r["core"]
               and "counting" in r["select"] and r["live_blocks"] == 1
               and r["backward"].startswith("one kernel: dq in VMEM")
               and r["backward_kernels"] == 1 for r in paths)
    picks = ring.since(mark, "dsa.select")
    assert picks and all(
        r["topk"] == 16 and r["tiles_visited"] == r["tiles_causal"] == 1
        and abs(r["mean_keys"] - (136 + 112 * 16) / 128) < 1e-9
        and r["select_passes"] == 33 and r["bits_a_pass"] == 1
        and "bit planes" in r["select"] for r in picks)
    kept = ring.since(mark, "remat.kept")
    assert {r["array"] for r in kept if r["layer"] == "block0/attn"} == \
        {"thr", "lse_i", "o", "lse"}
    window = tracer.spans("dsa.window")
    assert {r["layer"] for r in window} == {"block0/attn", "block1/attn"}
    assert all(0.0 < r["window_share"] <= 1.0 and r["mean_keys"] >= 14.9
               for r in window)


@pytest.mark.parametrize("field,why", [
    (dict(window=8), "a window"), (dict(causal=False), "no causal mask"),
    (dict(output_gate=True), "an output gate"),
    (dict(index_topk=0), "at least 1"),
    (dict(index_heads=0), "at least 1"),
    (dict(index_head_dim=6), "multiple of 4")])
def test_an_index_refuses_what_has_no_meaning(field, why):
    kw = dict(head_dim=16, causal=True, num_kv_heads=2, index_heads=4,
              index_head_dim=8, index_topk=16)
    kw.update(field)
    lp = dsl.AttentionLayer("blk/attn", ["x"], 4, **kw)
    with pytest.raises(ValueError, match="blk/attn") as err:
        get_layer(lp.type)(lp, [(1, 32, 32)], 0)
    assert why in str(err.value)


def test_an_index_needs_the_grouped_query_form_and_all_three_sizes():
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True)
    lp.attention_param.index_topk = 4
    with pytest.raises(ValueError, match="a: an index needs index_heads"):
        get_layer(lp.type)(lp, [(1, 32, 32)], 0)
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True)
    for k in ("index_heads", "index_head_dim", "index_topk"):
        setattr(lp.attention_param, k, 4)
    with pytest.raises(ValueError, match="no num_kv_heads"):
        get_layer(lp.type)(lp, [(1, 32, 32)], 0)
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True, ring=True)
    for k in ("index_heads", "index_head_dim", "index_topk"):
        setattr(lp.attention_param, k, 4)
    with pytest.raises(ValueError, match="ring"):
        get_layer(lp.type)(lp, [(1, 32, 32)], 0)


def test_every_operation_of_the_layer_has_a_part_in_the_closed_ledger():
    """The benchmark's ledger (benchmark/step_parts.py, whose `INNER` set
    does not know the index's scopes): what runs under `dsa_index_proj`,
    `dsa_select` and `dsa_kl` counts under the layer's part `attn`, the
    core under `attn_core`, backward and recomputation too; nothing of the
    layer is `unscoped`, and the backward's kernels lie under `attn_core`."""
    from sparknet_tpu.obs.trace import Tracer
    sys.path.insert(0, BENCH)
    import step_parts
    tracer = Tracer(None)
    sp = Message("SolverParameter", display=0, random_seed=0, **SOLVER)
    solver = Solver(sp, net_param=toy_net(flash=True, seq_len=128,
                                          num_hidden_layers=1),
                    log_fn=None, tracer=tracer, remat="full")
    draw = np.random.RandomState(3).randint(0, 64, (2, 129)).astype(np.int32)
    batch = {"data": draw[:, :-1], "label": draw[:, 1:]}
    table = step_parts.Parts(tracer.spans("net.parts")[-1]["parts"])
    # (XLA joins the paths of operations it merged with a semicolon)
    paths = [q for p in solver.op_scopes(batch).values()
             for q in p.split(";")
             if q.startswith("jit(") and "block0/attn" in q]
    by_scope = {}
    for p in paths:
        for scope in ("dsa_index_proj", "dsa_select", "dsa_kl", "attn_core",
                      "attn_proj_in", "attn_proj_out", "rope"):
            if f"/{scope}/" in p + "/":
                by_scope.setdefault(scope, set()).add(table.part_of("x", p))
    assert by_scope["dsa_index_proj"] == by_scope["dsa_select"] == \
        by_scope["dsa_kl"] == {"attn"}
    for scope in ("attn_core", "attn_proj_in", "attn_proj_out", "rope"):
        assert by_scope[scope] == {scope}
    assert "unscoped" not in {table.part_of("x", p) for p in paths}
    # no scope of the list opened inside another
    names = ("dsa_index_proj", "dsa_select", "dsa_kl", "attn_core",
             "attn_proj_in", "attn_proj_out", "rope")
    for p in paths:
        assert sum(f"/{n}/" in p + "/" for n in names) <= 1, p
    assert any("transpose" in p and "/attn_core/" in p for p in paths)
