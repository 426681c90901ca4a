"""LFM2-MoE's layers and the whole 5-block model against the plain
reference (`benchmark/reference/lfm2_moe.py`): small widths, seeded
weights, float32 on the CPU. The family's record and the bodies of the
tests every family has are in `tests/lm_family.py`.

The reference computes the short convolution by its padded sum, attention
as a masked softmax a block of rows at a time, the dense feed-forward by
its equation and the MoE as a loop over the held experts with a mask; the
program goes through `ShortConv`, the dense or the flash path, three
InnerProducts with a Sigmoid and a product, and ragged groups a window of
rows at a time. The head reads the embedding's own table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.solver.solver import Solver
from tests import lm_family as lm
from tests.lm_family import (close, fill, layer, ref,  # noqa: F401  (fixture)
                             same_value_and_grads)

FAMILY = lm.LFM2_MOE
TOY = FAMILY.toy
STAGE = TOY["layer_types"]


# ------------------------------------------------------ the short convolution

@pytest.mark.parametrize("taps", [3, 4, 1])
def test_short_conv_matches_reference(ref, taps):
    impl = layer(dsl.ShortConvLayer("mixer", ["x"], kernel=taps),
                 [(2, 24, 32)])
    assert [s[0] for s in impl.param_shapes()] == [(96, 32), (32, taps),
                                                   (32, 32)]
    blobs = fill(impl, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    d = dict(TOY, conv_L_cache=taps)

    def mine(x, blobs):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(x, blobs):
        return jnp.stack([ref.short_conv(x[b], blobs, d) for b in range(2)])
    same_value_and_grads(mine, theirs, (x, blobs), cot)


def test_short_conv_is_the_written_sum_and_the_first_positions_see_zeros(ref):
    """Token by token from the equations: [B | C | u] in that order, z = B *
    u, c_t = sum_j w[:, j] z_{t-2+j} with z before the sequence zero."""
    impl = layer(dsl.ShortConvLayer("mixer", ["x"]), [(1, 8, 32)])
    w_in, taps, w_out = blobs = fill(impl, jax.random.PRNGKey(4))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (8, 32)))
    bcu = x @ np.asarray(w_in).T
    z = bcu[:, :32] * bcu[:, 64:]
    want = np.zeros((8, 32))
    for t in range(8):
        c = sum(np.asarray(taps)[:, j] * z[t - 2 + j]
                for j in range(3) if t - 2 + j >= 0)
        want[t] = (bcu[t, 32:64] * c) @ np.asarray(w_out).T
    close(impl.apply(blobs, [jnp.asarray(x)[None]], True, None)[0][0], want)
    close(ref.short_conv(jnp.asarray(x), blobs, TOY), want)
    # causal: token 5 is moved by tokens 3, 4 and 5 and by no other
    base = impl.apply(blobs, [jnp.asarray(x)[None]], True, None)[0][0, 5]
    for moved, same in ((2, True), (3, False), (5, False), (6, True)):
        out = impl.apply(blobs, [jnp.asarray(x).at[moved].add(1.0)[None]],
                         True, None)[0][0, 5]
        assert bool(jnp.allclose(out, base, atol=1e-6)) == same, moved


def test_short_conv_taps_are_filled_uniform_and_the_path_is_recorded():
    impl = layer(dsl.ShortConvLayer("blockX/mixer", ["x"]), [(1, 8, 32)])
    taps = impl.param_shapes()[1][1]
    assert taps.type == "uniform"
    assert abs(taps.max - 3 ** -0.5) < 1e-6 and taps.min == -taps.max
    tracer = default_tracer()
    mark = tracer.mark()
    x = jnp.ones((1, 8, 32))
    text = str(jax.make_jaxpr(lambda x, p: impl.apply(p, [x], True, None))(
        x, fill(impl, jax.random.PRNGKey(0))))
    (rec,) = tracer.since(mark, "shortconv.path")
    assert (rec["layer"], rec["kernel"], rec["channels"]) == \
        ("blockX/mixer", 3, 32) and rec["form"].startswith("xla")
    lowered = jax.jit(lambda x, p: impl.apply(p, [x], True, None)).lower(
        x, fill(impl, jax.random.PRNGKey(0))).as_text(debug_info=True)
    for scope in ("shortconv_in", "shortconv_mix", "shortconv_out"):
        assert scope in lowered, scope
    assert "pallas" not in text


# ---------------------------------------------------------------- attention

def attention_layer(flash, seq, heads=4, kv=2, head=16, plain=True):
    lp = dsl.AttentionLayer("attn", ["x"], heads, head_dim=head, causal=True,
                            flash=flash, num_kv_heads=kv, qk_norm=True,
                            qk_norm_zero_centered=not plain, rotary_dim=head,
                            rope_theta=1e6, norm_eps=1e-5)
    return layer(lp, [(2, seq, 32)])


@pytest.mark.parametrize("flash,seq,heads,kv,head", [
    (False, 48, 4, 2, 16),
    (True, 128, 4, 2, 16),
    (True, 256, 4, 1, 64),          # the model's head size, the kernels
    (False, 40, 4, 1, 64),          # and the dense core
])
def test_attention_with_the_plain_head_norms_matches_reference(
        ref, flash, seq, heads, kv, head):
    impl = attention_layer(flash, seq, heads, kv, head)
    shapes = impl.param_shapes()
    assert [s[0] for s in shapes] == [
        (heads * head, 32), (kv * head, 32), (kv * head, 32),
        (32, heads * head), (head,), (head,)]
    assert shapes[4][1].value == 1.0 and shapes[5][1].value == 1.0
    blobs = fill(impl, jax.random.PRNGKey(3))
    # norm weights round 1, and not equal: the two are told apart
    blobs[4:] = [1.0 + b for b in blobs[4:]]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, seq, 32))
    cot = jax.random.normal(jax.random.PRNGKey(5), (2, seq, 32))
    d = dict(TOY, num_attention_heads=heads, num_key_value_heads=kv,
             head_dim=head)
    tracer = default_tracer()
    mark = tracer.mark()

    def mine(x, blobs):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(x, blobs):
        return jnp.stack([ref.attention(x[b], blobs, d, rows=8)
                          for b in range(2)])
    same_value_and_grads(mine, theirs, (x, blobs), cot)
    rec = tracer.since(mark, "attn.path")[0]
    assert rec["head_dim"] == head
    assert rec["path"] == ("kernel" if flash else "dense")
    if flash and head == 64:
        assert "not paired" in rec["reason"]


def test_the_plain_head_norm_is_not_the_zero_centred_one():
    plain, centred = (attention_layer(False, 16, plain=p)
                      for p in (True, False))
    assert centred.param_shapes()[4][1] is None        # filled with 0
    blobs = fill(plain, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 32))
    a = plain.apply(blobs, [x], True, None)[0]
    shifted = blobs[:4] + [b - 1.0 for b in blobs[4:]]
    close(centred.apply(shifted, [x], True, None)[0], a)
    assert not np.allclose(centred.apply(blobs, [x], True, None)[0], a,
                           atol=1e-3)


# ---------------------------------------------------------------------- MoE

def moe_layer(held, first, outputs=16, top_k=2, bias=True, scaling=None,
              tile=4):
    lp = dsl.MoELayer("moe", ["g"], outputs, hidden_dim=16, top_k=top_k,
                      experts_held=held, first_expert=first,
                      score_function="sigmoid", selection_bias=bias,
                      topk_eps=1e-6, routed_scaling_factor=scaling,
                      tile_rows=tile)
    return layer(lp, [(2, 24, 32)])


def test_the_bias_blob_is_a_buffer():
    impl = moe_layer(4, 0)
    shapes = impl.param_shapes()
    assert [s[0] for s in shapes] == [(16, 32), (4, 16, 32), (4, 16, 32),
                                      (4, 32, 16), (16,)]
    assert shapes[4][1:] == (None, 0.0, 0.0)   # zeros; no rate, no decay
    assert len(moe_layer(4, 0, bias=False).param_shapes()) == 4
    with pytest.raises(ValueError, match="score_function"):
        layer(dsl.MoELayer("moe", ["g"], 16, top_k=2,
                           score_function="tanh"), [(2, 24, 32)])
    with pytest.raises(ValueError, match="no-drop form"):
        lp = dsl.MoELayer("moe", ["g"], 16)
        lp.moe_param.score_function = "sigmoid"
        layer(lp, [(2, 24, 32)])


def test_route_picks_by_the_biased_score_and_weighs_by_the_unbiased(ref):
    impl = moe_layer(4, 0, scaling=2.5)
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (16, 32))
    g = jax.random.normal(jax.random.PRNGKey(9), (48, 32))
    bias = 0.4 * jax.random.normal(jax.random.PRNGKey(10), (16,))
    idx, top = impl.route(g, router, bias)
    score = np.asarray(jax.nn.sigmoid(g @ router.T), np.float64)
    want_idx = np.argsort(-(score + np.asarray(bias)), axis=1)[:, :2]
    assert (np.asarray(idx) == want_idx).all()
    picked = np.take_along_axis(score, want_idx, 1)
    close(top, 2.5 * picked / (picked.sum(1, keepdims=True) + 1e-6),
          tol=1e-5)
    # the two orders differ: some token's biased choice is not its two
    # largest scores, and its weights are still those experts' scores
    plain_idx = np.argsort(-score, axis=1)[:, :2]
    assert (np.sort(want_idx, 1) != np.sort(plain_idx, 1)).any()
    # a bias of zeros picks by the score itself
    idx0, top0 = impl.route(g, router, jnp.zeros(16))
    assert (np.sort(np.asarray(idx0), 1) == np.sort(plain_idx, 1)).all()
    # the reference's route is the same
    d = dict(TOY, routed_scaling_factor=2.5)
    ridx, rtop = ref.route(g, router, bias, d)
    assert (np.asarray(ridx) == np.asarray(idx)).all()
    close(rtop, top, tol=1e-5)
    # one expert lifted far enough is chosen by every token, and weighs
    # what it scores: little
    lifted = jnp.zeros(16).at[11].set(10.0)
    idx1, top1 = impl.route(g, router, lifted)
    assert (np.asarray(idx1)[:, 0] == 11).all()
    other = np.take_along_axis(score, np.asarray(idx1)[:, 1:], 1)[:, 0]
    close(top1[:, 0], 2.5 * score[:, 11] / (score[:, 11] + other + 1e-6),
          tol=1e-5)


@pytest.mark.parametrize("held,first", [(4, 0), (4, 8), (16, 0)])
def test_moe_held_share_matches_reference_with_a_bias(ref, held, first):
    impl = moe_layer(held, first)
    (g,), blobs, cot = lm.held_share(
        FAMILY, impl, [(2, 24, 32)],
        dict(TOY, num_experts=held, first_expert=first), 11)
    # no gradient trains the bias
    grad = jax.grad(lambda p: jnp.sum(
        impl.apply(p, [g], True, None)[0] * cot))(blobs)
    assert not np.asarray(grad[4]).any()


def test_moe_shares_add_up_to_the_uncut_layer(ref):
    """The share test: 4 chips' shares of 8 experts each, the router and
    the bias whole on every one, add up to the uncut 32-expert layer —
    output and the input's gradient — in the program and against the
    reference given all 32."""
    def build(held, first):
        return layer(dsl.MoELayer(
            "moe", ["g"], 32, hidden_dim=16, top_k=4,
            experts_held=held, first_expert=first, score_function="sigmoid",
            selection_bias=True, topk_eps=1e-6, tile_rows=4),
            [(2, 24, 32)])
    whole = build(None, None)
    blobs = fill(whole, jax.random.PRNGKey(14))
    g = jax.random.normal(jax.random.PRNGKey(15), (2, 24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(16), (2, 24, 32))
    total = lm.sum_of_shares(build, 4, 8, blobs, [g], cot)
    for a, b in zip(total, lm.out_and_input_grads(whole, blobs, [g], cot)):
        close(a, b, tol=5e-4)
    d = dict(TOY, num_experts=32, router_outputs=32, num_experts_per_tok=4,
             first_expert=0)
    close(total[0].reshape(48, 32),
          ref.moe(g.reshape(48, 32), blobs, d), tol=5e-4)


# ---------------------------------------------------------- the whole model

def biased(bias):
    """An edit of seeded weights: the expert biases a seeded draw of that
    size (0: as filled, zeros)."""
    def edit(w0):
        for i, name in enumerate(sorted(w0)):
            if bias and name.endswith("/moe"):
                w0[name][4] = bias * jax.random.normal(
                    jax.random.PRNGKey(100 + i), w0[name][4].shape)
    return edit


def test_the_reference_reads_its_stage_from_the_published_list(ref):
    d = ref.dims(FAMILY.config())
    assert d["layer_types"] == STAGE and d["num_dense_layers"] == 1
    assert d["num_hidden_layers"] == 5 and d["router_outputs"] == 16
    assert {k: d[k] for k in TOY} == TOY


def test_net_is_the_published_layout():
    by_name = lm.layout(zoo.lfm2_moe(batch_size=1, seq_len=128,
                                     experts_held=8))
    kinds = [by_name[f"block{i}/mixer"].type for i in range(24)]
    assert [i for i, k in enumerate(kinds) if k == "Attention"] == \
        [2, 6, 10, 14, 18, 21]
    assert kinds.count("ShortConv") == 18
    assert by_name["block3/mixer"].short_conv_param.kernel == 3
    ap = by_name["block2/mixer"].attention_param
    assert (ap.num_heads, ap.num_kv_heads, ap.head_dim, ap.rotary_dim) == \
        (32, 8, 64, 64)
    assert ap.qk_norm and not ap.qk_norm_zero_centered
    assert abs(ap.norm_eps - 1e-5) < 1e-12 and ap.rope_theta == 1e6
    for i in range(24):
        assert (f"block{i}/ff_gate" in by_name) == (i < 2), i
        assert (f"block{i}/moe" in by_name) == (i >= 2), i
    assert by_name["block0/ff_gate"].inner_product_param.num_output == 7168
    mp = by_name["block2/moe"].moe_param
    assert (mp.num_experts, mp.top_k, mp.experts_held, mp.hidden_dim) == \
        (32, 4, 8, 1792)
    assert mp.score_function == "sigmoid" and mp.selection_bias
    assert abs(mp.topk_eps - 1e-6) < 1e-12 and mp.norm_topk_prob
    # the head reads the embedding's table
    assert by_name["lm_head"].param[0].name == \
        by_name["tok_embed"].param[0].name != ""


def test_the_head_is_tied_to_the_embedding(ref):
    """One blob, owned by the embedding; its gradient is the sum of what
    the embedding's lookup and the head's product each give."""
    reference = ref.build(FAMILY.config(), 2)
    solver = FAMILY.solver()
    assert "lm_head" not in solver.params
    assert "lm_head" not in dict(reference.specs)
    assert solver.net.param_refs["lm_head"] == [("tok_embed", 0)]
    w0 = lm.seeded(solver, reference)
    batch = lm.batch_of(4)
    got = lm.grads_of(solver, batch)["tok_embed"][0]
    want = jax.grad(lambda p: ref.forward_loss(
        p, batch["data"], batch["label"], reference.d) / 128)(w0)
    close(got, want["tok_embed"][0], tol=2e-3)
    # untied, the same table in two blobs: the two gradients add up to it
    untied = FAMILY.net()
    for lp in untied.layer:
        if lp.name in ("tok_embed", "lm_head"):
            lp.param[0].name = ""
    other = Solver(solver.param, net_param=untied, log_fn=None)
    seeded_params = dict(solver.params,
                         lm_head=[solver.params["tok_embed"][0]])
    parts = jax.grad(lambda p: other.net.loss_fn(p, other.state, batch)[0])(
        seeded_params)
    close(parts["tok_embed"][0] + parts["lm_head"][0], got, tol=1e-4)
    assert np.abs(np.asarray(parts["lm_head"][0])).max() > 0


@pytest.mark.parametrize("bias", [0.0, 0.3])
def test_whole_model_three_adam_steps_match_reference(ref, bias):
    solver, w0 = lm.three_adam_steps(FAMILY, edit=biased(bias))
    # three steps of Adam with decay leave the bias where it was
    for name in w0:
        if name.endswith("/moe"):
            assert np.array_equal(np.asarray(solver.params[name][4]),
                                  np.asarray(w0[name][4])), name


def test_dense_and_moe_feed_forward_in_one_net(ref):
    """The dense block's feed-forward (three InnerProducts, a Sigmoid and
    a product) is the reference's SwiGLU, beside the MoE blocks."""
    reference = ref.build(FAMILY.config(), 2)
    solver = FAMILY.solver()
    w0 = lm.seeded(solver, reference, seed=2)
    blobs, _ = solver.net.apply(solver.params, solver.state, lm.batch_of(5),
                                train=True)
    g = blobs["block0/ln2"]
    want = jnp.stack([ref.dense_ff(g[b], [w0[f"block0/{n}"][0] for n in (
        "ff_gate", "ff_up", "ff_down")]) for b in range(2)])
    close(blobs["block0/ff_down"], want)
    want = ref.moe(blobs["block1/ln2"].reshape(128, 32), w0["block1/moe"],
                   reference.d)
    close(blobs["block1/moe"].reshape(128, 32), want)


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan):
    lm.remat_and_scan(FAMILY, remat, scan, edit=biased(0.3))


def test_two_periods_scan_as_conv_runs_between_attention_blocks():
    solver = FAMILY.solver(dict(
        num_hidden_layers=10, num_dense_layers=2,
        layer_types=["conv", "conv"] + ["full_attention", "conv", "conv",
                                        "conv"] * 2))
    # the two leading dense conv blocks are alike too
    assert [(r["n"], r["entry"]) for r in solver.net._scan_runs()] == \
        [(2, "tok_embed"), (3, "block2/res2"), (3, "block6/res2")]


def test_paths_and_load_are_recorded():
    tracer, since = lm.traced_steps(FAMILY, 2, dict(moe_stats=True))
    lm.held_loads(tracer, [f"block{i}/moe" for i in range(1, 5)])
    moe = since("moe.path")
    assert moe and all(r["score"] == "sigmoid" and r["selection_bias"]
                       and r["activation"] == "silu" for r in moe)
    attn = since("attn.path")
    assert attn and all(r["layer"] == "block1/mixer"
                        and r["head_dim"] == 16 for r in attn)
    conv = since("shortconv.path")
    assert {r["layer"] for r in conv} == {f"block{i}/mixer"
                                          for i in (0, 2, 3, 4)}
    assert all(r["kernel"] == 3 and r["channels"] == 32 for r in conv)
