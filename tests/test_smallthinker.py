"""SmallThinker's layers and the whole 4-block model against the plain
reference (`benchmark/reference/smallthinker.py`): small widths, seeded
weights, float32 on the CPU. The family's record and the bodies of the
tests every family has are in `tests/lm_family.py`.

The reference computes attention as a masked softmax a block of rows at a
time and the MoE as a loop over the held experts with a mask, the router
reading the pre-attention norm; the program goes through the dense or the
flash path (the window kernels over the band alone) and over ragged groups
a window of rows at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import default_tracer
from tests import lm_family as lm
from tests.lm_family import close, fill, layer, ref  # noqa: F401  (fixture)

FAMILY = lm.SMALLTHINKER
TOY = FAMILY.toy


# ---------------------------------------------------------------- attention

def attention_layer(flash, seq, rotary, window, heads=4, kv=2):
    lp = dsl.AttentionLayer("attn", ["x"], heads, head_dim=16, causal=True,
                            flash=flash, num_kv_heads=kv,
                            rotary_dim=16 if rotary else 0,
                            rope_theta=1.5e6, window=window)
    return layer(lp, [(2, seq, 32)])


@pytest.mark.parametrize("flash,seq,rotary,window,heads,kv", [
    (False, 48, 0, 0, 4, 2),        # global, no positional encoding
    (False, 48, 1, 24, 4, 2),       # window, rotary on the whole head
    (True, 128, 0, 0, 4, 2),
    (True, 256, 1, 100, 4, 2),      # the window kernels, S not a multiple
    (True, 128, 1, 40, 7, 1),       # seven queries a key-value head
    (True, 128, 1, 500, 4, 2),      # a window that covers the sequence
])
def test_attention_matches_reference(ref, flash, seq, rotary, window, heads,
                                     kv):
    impl = attention_layer(flash, seq, rotary, window, heads, kv)
    assert [s[0] for s in impl.param_shapes()] == [
        (heads * 16, 32), (kv * 16, 32), (kv * 16, 32), (32, heads * 16)]
    blobs = fill(impl, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, seq, 32))
    cot = jax.random.normal(jax.random.PRNGKey(5), (2, seq, 32))
    d = dict(TOY, num_attention_heads=heads, num_key_value_heads=kv)

    def mine(x, blobs):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(x, blobs):
        return jnp.stack([ref.attention(x[b], blobs, d, rotary, window,
                                        rows=16) for b in range(2)])
    close(mine(x, blobs), theirs(x, blobs))
    gm = jax.grad(lambda x, p: jnp.sum(mine(x, p) * cot), (0, 1))(x, blobs)
    gt = jax.grad(lambda x, p: jnp.sum(theirs(x, p) * cot), (0, 1))(x, blobs)
    for a, b in zip(jax.tree_util.tree_leaves(gm),
                    jax.tree_util.tree_leaves(gt)):
        close(a, b, tol=5e-4)


def test_the_reference_window_is_the_written_mask(ref):
    """The reference reads a slice of the keys for a window layer: against
    the mask written out over all keys, i - window < j <= i."""
    d = dict(TOY)
    impl = attention_layer(False, 64, 1, 24)
    blobs = fill(impl, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))
    wq, wk, wv, wo = blobs
    q = ref.rope((x @ wq.T).reshape(64, 4, 16), 1.5e6)
    k = ref.rope((x @ wk.T).reshape(64, 2, 16), 1.5e6)
    v = (x @ wv.T).reshape(64, 2, 16)
    k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    i, j = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
    sc = jnp.einsum("qhd,khd->hqk", q, k) / 4.0
    mix = jax.nn.softmax(jnp.where((j <= i) & (i - j < 24), sc, -jnp.inf), -1)
    want = jnp.einsum("hqk,khd->qhd", mix, v).reshape(64, 64) @ wo.T
    for rows in (8, 16, 64):
        close(ref.attention(x, blobs, d, 1, 24, rows=rows), want)
    # token 30 sees tokens 7..30 and no other
    base = ref.attention(x, blobs, d, 1, 24, rows=8)[30]
    for moved, same in ((6, True), (7, False), (30, False), (31, True)):
        out = ref.attention(x.at[moved].add(1.0), blobs, d, 1, 24,
                            rows=8)[30]
        assert bool(jnp.allclose(out, base, atol=1e-6)) == same, moved


def test_window_belongs_to_causal_attention():
    with pytest.raises(ValueError, match="window needs causal"):
        lp = dsl.AttentionLayer("a", ["x"], 4, num_kv_heads=2, window=8)
        layer(lp, [(2, 16, 32)])


def test_attn_path_records_say_which_core_ran():
    tracer = default_tracer()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 32))
    for flash, seq, window in ((True, 256, 64), (True, 256, 0),
                               (False, 256, 64), (True, 200, 64)):
        impl = attention_layer(flash, seq, 1, window)
        mark = tracer.mark()
        impl.apply(fill(impl, jax.random.PRNGKey(1)), [x[:, :seq]], True,
                   None)
        (rec,) = tracer.since(mark, "attn.path")
        assert rec["layer"] == "attn" and rec["window"] == window
        assert rec["rope"] == "plain" and rec["rope_form"] == \
            "one pass: rotate-half as a product"
        if flash and seq % 128 == 0:
            assert rec["path"] == "kernel"
            # one block of 256 at this size: the band is the block
            assert rec["live_blocks"] == rec["causal_blocks"] == 1
        else:
            assert rec["path"] == "dense" and rec["live_blocks"] == 0
            assert ("128 does not divide" in rec["reason"]) == flash


# ---------------------------------------------------------------------- MoE

def moe_layer(held, first, outputs=16, top_k=3, tile=4):
    lp = dsl.MoELayer("moe", ["g", "h"], outputs, hidden_dim=16,
                      top_k=top_k, experts_held=held, first_expert=first,
                      expert_activation="relu", tile_rows=tile)
    return layer(lp, [(2, 24, 32), (2, 24, 32)])


@pytest.mark.parametrize("held,first", [(4, 0), (4, 8), (16, 0)])
def test_moe_held_share_matches_reference(ref, held, first):
    lm.held_share(FAMILY, moe_layer(held, first), [(2, 24, 32)] * 2,
                  dict(TOY, moe_num_primary_experts=held,
                       first_expert=first), 8)


def test_moe_shares_add_up_to_the_uncut_layer(ref):
    """The share test: 8 chips' shares of 8 experts each, the router whole
    on every one, add up to the uncut 64-expert layer — output and the
    gradients of both inputs — in the program and against the reference
    given all 64."""
    def build(held, first):
        return layer(dsl.MoELayer(
            "moe", ["g", "h"], 64, hidden_dim=16, top_k=6,
            experts_held=held, first_expert=first, expert_activation="relu",
            tile_rows=4), [(2, 24, 32)] * 2)
    whole = build(None, None)
    blobs = fill(whole, jax.random.PRNGKey(12))
    g = jax.random.normal(jax.random.PRNGKey(13), (2, 24, 32))
    h = jax.random.normal(jax.random.PRNGKey(14), (2, 24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(15), (2, 24, 32))
    total = lm.sum_of_shares(build, 8, 8, blobs, [g, h], cot)
    # every chip computes the router's gradient on h from its own pairs:
    # the shares' sum is the whole layer's, for g, for h and for the output
    for a, b in zip(total, lm.out_and_input_grads(whole, blobs, [g, h],
                                                  cot)):
        close(a, b, tol=5e-4)
    d = dict(TOY, moe_num_primary_experts=64, router_outputs=64,
             moe_num_active_primary_experts=6, first_expert=0)
    uncut = ref.moe(g.reshape(48, 32), h.reshape(48, 32), blobs, d)
    close(total[0].reshape(48, 32), uncut, tol=5e-4)
    # top-6 of 64 as the softmax over the six largest logits
    idx, top = ref.route(h.reshape(48, 32), blobs[0], d)
    logits = h.reshape(48, 32) @ blobs[0].T
    close(top, jax.nn.softmax(jnp.take_along_axis(logits, idx, 1), -1))


# ---------------------------------------------------------- the whole model

def test_net_is_the_published_layout():
    by_name = lm.layout(zoo.smallthinker(
        batch_size=1, seq_len=128, num_hidden_layers=8, vocab_size=64,
        hidden_size=32, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, moe_ffn_hidden_size=16, experts_held=8))
    for i in range(8):
        ap = by_name[f"block{i}/attn"].attention_param
        assert (int(ap.window), int(ap.rotary_dim)) == \
            ((0, 0) if i % 4 == 0 else (4096, 16)), i
        mp = by_name[f"block{i}/moe"]
        assert list(mp.bottom) == [f"block{i}/ln2", f"block{i}/ln1"]
        assert mp.moe_param.expert_activation == "relu"
        assert (mp.moe_param.num_experts, mp.moe_param.top_k,
                mp.moe_param.experts_held) == (64, 6, 8)
        assert not by_name[f"block{i}/ln1"].rms_norm_param.zero_centered


def test_whole_model_three_adam_steps_match_reference(ref):
    lm.three_adam_steps(FAMILY)


def test_the_router_reads_the_first_norm_not_the_second(ref):
    """The router's weight gradient in the whole model is the reference's
    with the router on the pre-attention norm, and is not what a router on
    the post-attention norm (the experts' input) would get."""
    reference = ref.build(FAMILY.config(), 2)
    solver = FAMILY.solver()

    # a small embedding, so that attention's output is a visible part of
    # the residual and the two norms differ
    def small_embedding(w0):
        w0["tok_embed"] = [0.02 * w0["tok_embed"][0]]
    w0 = lm.seeded(solver, reference, seed=1, edit=small_embedding)
    batch = lm.batch_of(3)

    def reference_grad():
        return jax.grad(lambda p: ref.forward_loss(
            p, batch["data"], batch["label"], reference.d) / 128)(
                w0)["block1/moe"][0]
    got = lm.grads_of(solver, batch)["block1/moe"][0]
    close(got, reference_grad(), tol=2e-3)
    whole = ref.moe
    try:
        ref.moe = lambda g, h, blobs, d, store=lambda a: a: \
            whole(g, g, blobs, d, store)
        other = reference_grad()
    finally:
        ref.moe = whole
    assert np.linalg.norm(np.asarray(other - got)) > \
        0.05 * np.linalg.norm(np.asarray(got))


@pytest.mark.parametrize("remat,scan", [("full", "off"), ("none", "on"),
                                        ("full", "on")])
def test_remat_and_scan_leave_the_gradients_alone(ref, remat, scan):
    lm.remat_and_scan(FAMILY, remat, scan)


def test_two_periods_scan_as_window_runs_between_global_blocks():
    solver = FAMILY.solver(dict(
        num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
        sliding_window_layout=[0, 1, 1, 1] * 2))
    assert [(r["n"], r["entry"]) for r in solver.net._scan_runs()] == \
        [(3, "block0/res2"), (3, "block4/res2")]


def test_moe_load_is_recorded_where_the_solver_fetches_a_loss():
    # this test's records alone: the ring is the process's, and a worker
    # that ran the Nemotron tests first holds their `relu2` paths
    tracer, since = lm.traced_steps(FAMILY, 2, dict(moe_stats=True))
    lm.held_loads(tracer, [f"block{i}/moe" for i in range(4)])
    paths = [r for r in since("moe.path") if r.get("activation")]
    assert paths and {r["activation"] for r in paths} <= {"relu", "silu"}
    assert paths[-1]["activation"] == "relu"
