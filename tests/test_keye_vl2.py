"""Keye-VL-2.0's decoder — attention over an index-picked key set and the
whole 4-block model — against the plain reference
(`benchmark/reference/keye_vl2.py`): small widths, seeded weights, float32
on the CPU. The family's record and the bodies of the tests every family
has are in `tests/lm_family.py`.

The reference computes index scores and main scores a block of query rows
at a time, selects by an exact `jax.lax.top_k` and writes L_I with both
stop_gradients; the program goes through `Attention` with its index fields:
the plain form (ops/dsa.py) or the four kernels of ops/pallas_dsa.py in
interpret mode (the kernels alone against the plain form:
`tests/test_pallas_dsa.py`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import Tracer
from sparknet_tpu.ops import dsa
from tests import lm_family as lm
from tests.lm_family import (close, exact_index, layer, qkv,
                             ref)  # noqa: F401  (a fixture)

FAMILY = lm.KEYE_VL2
TOY = FAMILY.toy


def ref_dims(**over):
    return {**TOY, "selection": "index", **over}


def attn_layer(seq=64, batch=2, flash=False, topk=16, **over):
    lp = dsl.AttentionLayer(
        "attn", ["x"], 4, head_dim=16, causal=True, flash=flash,
        num_kv_heads=2, qk_norm=True, qk_norm_zero_centered=False,
        rotary_dim=16, rope_theta=1e7, index_heads=4, index_head_dim=8,
        index_topk=topk, **over)
    return layer(lp, [(batch, seq, 32)])


def attn_blobs(impl, key, std=0.3):
    """Seeded blobs: gaussian matrices, norm weights near 1, the bias near
    0."""
    out = []
    for i, (shape, filler, *_) in enumerate(impl.param_shapes()):
        if len(shape) == 1:
            base = 0.0 if filler is None else 1.0
            out.append(base + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), shape))
        else:
            out.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return out


# --------------------------------------------------- index scores, selection

def test_index_scores_and_their_loss_match_reference(ref):
    """The layer's L_I and its gradient on the five indexer blobs against
    the reference's, from the same blobs."""
    impl = attn_layer()
    assert [s[0] for s in impl.param_shapes()] == [
        (64, 32), (32, 32), (32, 32), (32, 64), (16,), (16,),
        (32, 32), (8, 32), (4, 32), (8,), (8,)]
    blobs = attn_blobs(impl, jax.random.PRNGKey(1))
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
    blobs = attn_blobs(impl, jax.random.PRNGKey(21))
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


# ----------------------------------------------------- the two losses

@pytest.mark.parametrize("which", ["main", "index"])
def test_the_two_losses_train_disjoint_blobs(which):
    """Exactly zero, not small: L_LM (here any function of the layer's
    first top) on the five indexer blobs, L_I on the six others and on the
    layer's input."""
    for flash in (False, True):
        impl = attn_layer(seq=128, batch=1, flash=flash)
        blobs = attn_blobs(impl, jax.random.PRNGKey(12))
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
        return layer(dsl.MoELayer(
            "moe", ["x"], 128, hidden_dim=f, top_k=8, experts_held=held,
            first_expert=first, norm_topk_prob=True), [(1, n, e)])
    whole = build(128, 0)
    blobs = lm.fill(whole, key)
    g = jax.random.normal(jax.random.fold_in(key, 9), (1, n, e))
    (total,) = lm.sum_of_shares(build, 8, 16, blobs, [g])
    close(total, whole.apply(blobs, [g], True, None)[0], tol=5e-4)
    d = ref_dims(num_experts=128, router_outputs=128, num_experts_per_tok=8,
                 hidden_size=e, moe_intermediate_size=f)
    close(total.reshape(n, e), ref.moe(g.reshape(n, e), blobs, d), tol=5e-4)


# ---------------------------------------------------------- the whole model

def form(flash, layers=2):
    """(the net's overrides, the configuration file) of a short net in one
    form: two blocks where the kernels run in interpret mode, at the
    sequence length they need."""
    seq = 128 if flash else 64
    return (dict(flash=flash, seq_len=seq, num_hidden_layers=layers),
            dict(FAMILY.config(seq_len=seq), num_hidden_layers=layers))


def test_the_reference_reads_the_config_and_its_sa_config(ref):
    d = ref.dims(FAMILY.config())
    assert {k: d[k] for k in TOY} == TOY
    assert d["selection"] == "index"


def test_net_is_the_published_layout():
    net = zoo.keye_vl2(batch_size=1, seq_len=128, experts_held=16)
    by_name = lm.layout(net)
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


@pytest.mark.parametrize("flash", [False, True])
def test_whole_model_three_adam_steps_match_reference(ref, flash):
    lm.three_adam_steps(FAMILY, *(form(True) if flash else ()))


def test_the_step_loss_is_the_cross_entropy_plus_every_layers_index_loss(ref):
    solver = FAMILY.solver()
    solver.set_scan("off")
    lm.seeded(solver, ref.build(FAMILY.config(), 2))
    batch = lm.batch_of(3)
    total, (blobs, _) = solver.net.loss_fn(solver.params, solver.state,
                                           batch)
    kls = [float(blobs[f"block{i}/attn_kl"]) for i in range(4)]
    assert all(k > 0 for k in kls)
    assert abs(float(total) - float(blobs["loss"]) - sum(kls)) < 1e-5


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
@pytest.mark.parametrize("flash", [False, True])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan, flash):
    """The backward reads the forward's set: a replay that selected again
    would run the selection twice, and one that selected from other
    scores would move the gradients."""
    lm.remat_and_scan(FAMILY, remat, scan, *form(flash), runs=[dict(n=2)])


def test_the_four_blocks_scan_as_one_run_and_carry_their_losses_out():
    solver = FAMILY.solver()
    runs = solver.net._scan_runs()
    assert [(r["n"], r["glen"], r["entry"], r["out"], r["losses"])
            for r in runs] == [(4, 6, "tok_embed", "block3/res2", [(1, 1)])]
    solver.set_scan("on")
    blobs, _ = solver.net.apply(solver.params, solver.state, lm.batch_of(2),
                                train=True)
    assert all(blobs[f"block{i}/attn_kl"].shape == () for i in range(4))
    assert "block1/attn" not in blobs
    # with the statistics' top a block has two boundary tops: no scan
    stats = FAMILY.solver(dict(index_stats=True))
    assert stats.net._scan_runs() == []


def test_paths_selection_and_kept_arrays_are_recorded():
    tracer, since = lm.traced_steps(
        FAMILY, 1, dict(form(True)[0], index_stats=True), remat="full")
    paths = since("attn.path")
    assert {r["layer"] for r in paths} == {"block0/attn", "block1/attn"}
    assert all(r["path"] == "kernel" and "index tile" in r["core"]
               and "counting" in r["select"] and r["live_blocks"] == 1
               and r["backward"].startswith("one kernel: dq in VMEM")
               and r["backward_kernels"] == 1 for r in paths)
    picks = since("dsa.select")
    assert picks and all(
        r["topk"] == 16 and r["tiles_visited"] == r["tiles_causal"] == 1
        and abs(r["mean_keys"] - (136 + 112 * 16) / 128) < 1e-9
        and r["select_passes"] == 33 and r["bits_a_pass"] == 1
        and "bit planes" in r["select"] for r in picks)
    kept = since("remat.kept")
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
        layer(lp, [(1, 32, 32)])
    assert why in str(err.value)


def test_an_index_needs_the_grouped_query_form_and_all_three_sizes():
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True)
    lp.attention_param.index_topk = 4
    with pytest.raises(ValueError, match="a: an index needs index_heads"):
        layer(lp, [(1, 32, 32)])
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True)
    for k in ("index_heads", "index_head_dim", "index_topk"):
        setattr(lp.attention_param, k, 4)
    with pytest.raises(ValueError, match="no num_kv_heads"):
        layer(lp, [(1, 32, 32)])
    lp = dsl.AttentionLayer("a", ["x"], 4, causal=True, ring=True)
    for k in ("index_heads", "index_head_dim", "index_topk"):
        setattr(lp.attention_param, k, 4)
    with pytest.raises(ValueError, match="ring"):
        layer(lp, [(1, 32, 32)])


def test_every_operation_of_the_layer_has_a_part_in_the_closed_ledger():
    """The benchmark's ledger (benchmark/step_parts.py, whose `INNER` set
    does not know the index's scopes): what runs under `dsa_index_proj`,
    `dsa_select` and `dsa_kl` counts under the layer's part `attn`, the
    core under `attn_core`, backward and recomputation too; nothing of the
    layer is `unscoped`, and the backward's kernels lie under `attn_core`."""
    tracer = Tracer(None)
    solver = FAMILY.solver(form(True, layers=1)[0], tracer=tracer,
                           remat="full")
    data, labels = lm.tokens(3, 128)
    batch = {"data": data, "label": labels}
    table = lm.bench("step_parts").Parts(
        tracer.spans("net.parts")[-1]["parts"])
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
