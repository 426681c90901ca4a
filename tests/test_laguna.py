"""Laguna — window and full attention at two head counts on shared
key-value heads, the per-head output gate, the two rotary tables (YaRN on
half the head in the full layers, the plain table on the whole head in the
window layers), the leading dense block and the sigmoid-routed MoE with its
ungated shared expert — against the plain reference
(`benchmark/reference/laguna.py`): small widths, seeded weights, float32 on
the CPU. The family's record and the bodies of the tests every family has
are in `tests/lm_family.py`.
"""

import importlib
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.graph.compiler import CompiledNet
from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import Tracer, default_tracer
from sparknet_tpu.ops import attention as attn_ops
from tests import lm_family as lm
from tests.lm_family import close, layer, ref  # noqa: F401  (a fixture)

FAMILY = lm.LAGUNA
TOY = FAMILY.toy
FULL, WINDOW = "full_attention", "sliding_attention"
YARN = dict(rope_type="yarn", rope_factor=4, rope_original_positions=64,
            rope_beta_fast=4, rope_beta_slow=1,
            rope_scale=TOY["rope_parameters"][FULL]["attention_factor"])


def attention_layer(kind, seq=64, flash=False, gate="head", heads=None,
                    **over):
    """A layer of the toy's `kind`: 6 query heads with YaRN on half the
    head, or 8 with the plain table on the whole head and a window of 24,
    on one key-value head of 16."""
    full = kind == FULL
    fields = dict(
        head_dim=16, causal=True, flash=flash, num_kv_heads=1,
        rotary_dim=8 if full else 16, rope_theta=10000 if full else 100,
        rope=YARN if full else {}, gate=gate, window=0 if full else 24)
    fields.update(over)
    lp = dsl.AttentionLayer("attn", ["x"], heads or (6 if full else 8),
                            **fields)
    return layer(lp, [(2, seq, 32)])


# ---------------------------------------------------------------- attention

@pytest.mark.parametrize("kind,flash,seq", [
    (FULL, False, 48), (WINDOW, False, 48), (FULL, True, 128),
    (WINDOW, True, 128)])
def test_attention_matches_reference(ref, kind, flash, seq):
    """Both kinds of layer against the reference's attention, the dense
    path and the flash kernels (interpret mode): the output, the gradients
    of the five blobs — the gate's W_g among them — and the input's."""
    impl = attention_layer(kind, seq, flash)
    heads = 6 if kind == FULL else 8
    assert [p[0] for p in impl.param_shapes()] == [
        (heads * 16, 32), (16, 32), (16, 32), (32, heads * 16), (heads, 32)]
    key = jax.random.PRNGKey(seq + heads)
    blobs = lm.fill(impl, key)
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, seq, 32))
    probe = jax.random.normal(jax.random.fold_in(key, 10), (2, seq, 32))
    mark = default_tracer().mark()

    def mine(blobs, x):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(blobs, x):
        return jnp.stack([ref.attention(x[r], blobs, TOY, heads, kind,
                                        rows=16) for r in range(2)])
    close(mine(blobs, x), theirs(blobs, x), tol=5e-4)
    assert default_tracer().since(mark, "attn.path")[0]["path"] == \
        ("kernel" if flash else "dense")
    got = jax.grad(lambda b, x: jnp.sum(mine(b, x) * probe), (0, 1))(blobs, x)
    want = jax.grad(lambda b, x: jnp.sum(theirs(b, x) * probe),
                    (0, 1))(blobs, x)
    for i, (a, b) in enumerate(zip(got[0] + [got[1]], want[0] + [want[1]])):
        assert float(jnp.max(jnp.abs(b))) > 0, i
        close(a, b, tol=2e-3)


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_a_gate_of_zero_weights_halves_the_attention_output(kind):
    """`gate="head"` with W_g = 0 is sigmoid(0) = 1/2 on every head: half
    the output of the same layer without a gate; and one row of W_g moves
    its own head alone (the out projection the identity on 6 x 16, so a
    head's output can be read)."""
    gated = attention_layer(kind, heads=6, gate="head")
    bare = attention_layer(kind, heads=6, gate=None)
    assert len(bare.param_shapes()) == 4
    blobs = lm.fill(gated, jax.random.PRNGKey(2))
    blobs[4] = jnp.zeros_like(blobs[4])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32))
    whole = bare.apply(blobs[:4], [x], True, None)[0]
    assert float(jnp.max(jnp.abs(whole))) > 0
    close(gated.apply(blobs, [x], True, None)[0], 0.5 * whole, tol=1e-6)
    wide = layer(dsl.AttentionLayer(
        "attn", ["x"], 6, head_dim=16, causal=True, num_kv_heads=1,
        gate="head"), [(2, 64, 96)])
    blobs = lm.fill(wide, jax.random.PRNGKey(4))
    blobs[3], blobs[4] = jnp.eye(96), jnp.zeros_like(blobs[4])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 96))

    def by_head(blobs):
        return np.asarray(wide.apply(blobs, [x], True, None)[0]
                          ).reshape(2, 64, 6, 16)
    base = by_head(blobs)
    opened = list(blobs)
    opened[4] = blobs[4].at[2].set(0.3)             # head 2's row of W_g
    moved = np.abs(by_head(opened) - base).max(axis=(0, 1, 3))
    assert moved[2] > 1e-3 and (np.delete(moved, 2) == 0).all(), moved


def test_yarn_table_and_factor_at_the_published_numbers():
    """d 64, base 500,000, factor 64 over 4,096 positions, beta 64 and 1:
    `low` 5 and `high` 16 as integers, the ramp between them, the blend of
    the interpolated and the extrapolated frequencies, and cos and sin
    times 0.1 ln 64 + 1 = 1.4158883 on the turned dimensions alone."""
    d, base, factor, positions = 64, 500000.0, 64.0, 4096
    low = math.floor(d * math.log(positions / (64 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(positions / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (5, 16)
    assert attn_ops.yarn_range(d, base, positions, 64, 1) == (5, 16)
    i = np.arange(d // 2)
    extra = base ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / factor * ramp + extra * (1 - ramp)
    got = np.asarray(attn_ops.rope_table(d, base, (factor, positions, 64, 1)))
    assert got.dtype == np.float32 and got.shape == (32,)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the fastest five pairs extrapolate, the pairs from 16 on interpolate
    np.testing.assert_allclose(got[:6], extra[:6], rtol=2e-6)
    np.testing.assert_allclose(got[16:], extra[16:] / 64, rtol=2e-6)
    scale = 0.1 * math.log(64) + 1
    assert scale == pytest.approx(1.4158883083359672, rel=1e-12)
    lp = dsl.AttentionLayer(
        "attn", ["x"], 48, head_dim=128, causal=True, num_kv_heads=8,
        rotary_dim=64, rope_theta=base, gate="head", rope=dict(
            rope_type="yarn", rope_factor=64, rope_original_positions=4096,
            rope_beta_fast=64, rope_beta_slow=1))
    impl = layer(lp, [(1, 16, 2048)])
    assert impl.yarn == (64.0, 4096, 64.0, 1.0)
    assert impl.rope_scale == pytest.approx(scale, rel=1e-12)   # unasked
    # position 3 of a head of 128: the first 64 turned and scaled, the
    # other 64 as they were
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    y = np.asarray(attn_ops.rotary(x, 64, base, impl.yarn, impl.rope_scale))
    x = np.asarray(x)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    ang = 3 * want
    turned = x[0, 3, :, :64]
    rot = np.concatenate([-turned[:, 32:], turned[:, :32]], -1)
    np.testing.assert_allclose(
        y[0, 3, :, :64], scale * (turned * np.tile(np.cos(ang), 2)
                                  + rot * np.tile(np.sin(ang), 2)),
        rtol=2e-5, atol=2e-6)


# a YaRN table as the full layers' (the toy's fields) with its scale
YARN_TABLE = (4.0, 64, 4.0, 1.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d,rotary_dim,offset,theta,yarn,scale", [
    (64, 16, 0, 1e4, None, 1.0), (64, 8, 0, 1.5e6, None, 1.0),
    (64, 64, 0, 1e7, None, 1.0), (128, 64, 0, 5e5, None, 1.0),
    (128, 128, 0, 1e4, None, 1.0), (256, 64, 192, 1e6, None, 1.0),
    (128, 64, 0, 5e5, YARN_TABLE, 1.1386294361119891)])
def test_a_plain_table_is_bit_for_bit_the_rotary_of_before(
        d, rotary_dim, offset, theta, yarn, scale, dtype):
    """`rotary` as it stood before it was one pass, written out here with
    its slices and joins (an `offset` was the latent form's slice and join
    round the call): the product with the matrix of 0 and +-1 and the
    full-width tables give the same BITS, values and gradients, in both
    dtypes, at the head widths and turned spans the cells run. Operation
    by operation, as the tests' CPU runs a layer outside `jit`: inside it
    XLA:CPU contracts `a b + c d` into one of two fused multiply-adds,
    not the same one in both programs (1 ulp; none with
    `--xla_cpu_max_isa=AVX`)."""
    def before(x):
        lead, x = x[..., :offset], x[..., offset:]
        s = x.shape[1]
        inv = attn_ops.rope_table(rotary_dim, theta, yarn)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
        if scale != 1.0:
            cos, sin = cos * scale, sin * scale
        xr = x[..., :rotary_dim].astype(jnp.float32)
        half = rotary_dim // 2
        rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
        out = (xr * cos + rot * sin).astype(x.dtype)
        return jnp.concatenate([lead, out, x[..., rotary_dim:]], -1)

    def now(x):
        return attn_ops.rotary(x, rotary_dim, theta, yarn, scale, offset)
    x, cot = (jax.random.normal(jax.random.PRNGKey(k), (2, 96, 3, d))
              .astype(dtype) for k in (1, 2))
    got, want = now(x), before(x)
    assert got.dtype == want.dtype == dtype
    assert np.asarray(want[..., offset:offset + rotary_dim]
                      != x[..., offset:offset + rotary_dim]).mean() > 0.3
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def grad(f):
        return jax.grad(lambda x: jnp.sum(
            f(x).astype(jnp.float32) * cot.astype(jnp.float32)))(x)
    g_got, g_want = grad(now), grad(before)
    assert g_got.dtype == dtype and np.asarray(g_want != cot).mean() > 0.05
    np.testing.assert_array_equal(np.asarray(g_got), np.asarray(g_want))
    if yarn is None:
        np.testing.assert_array_equal(
            np.asarray(attn_ops.rope_table(rotary_dim, theta)),
            np.asarray(theta ** (-jnp.arange(0, rotary_dim, 2,
                                             dtype=jnp.float32)
                                 / rotary_dim)))


@pytest.mark.parametrize("fields,why", [
    (dict(output_gate=True), "one gate"),
    (dict(num_kv_heads=None), "head_gate need num_kv_heads"),
    (dict(rope_type="ntk"), "plain or yarn"),
    (dict(rope_type="plain", rope_factor=4.0), "belong to rope_type yarn"),
    (dict(rotary_dim=0), "needs rotary_dim"),
    (dict(rope_original_positions=0), "rope_original_positions"),
    (dict(rope_original_positions=None), "rope_original_positions"),
    (dict(index_heads=2, index_head_dim=8, index_topk=4), "output gate")])
def test_the_gate_and_the_table_refuse_what_has_no_meaning(fields, why):
    lp = dsl.AttentionLayer("blk/attn", ["x"], 6, head_dim=16, causal=True,
                            num_kv_heads=1, rotary_dim=8, gate="head",
                            rope=YARN)
    for key, value in fields.items():
        if value is None:
            lp.attention_param.clear(key)
        else:
            setattr(lp.attention_param, key, value)
    with pytest.raises(ValueError, match=why) as err:
        layer(lp, [(1, 16, 32)])
    assert "blk/attn" in str(err.value)
    with pytest.raises(ValueError, match="elementwise or head"):
        dsl.AttentionLayer("a", ["x"], 4, num_kv_heads=2, gate="heads")


def test_attn_path_says_the_heads_the_gate_and_the_table():
    """The new fields on both kinds of layer, through the kernel and the
    dense path; and on layers that set none of them — a rotary layer
    without a gate, a layer with the elementwise gate and no rotary —
    beside what they recorded before."""
    ring = default_tracer()

    def record(impl, seq):
        mark = ring.mark()
        impl.apply(lm.fill(impl, jax.random.PRNGKey(1)),
                   [jnp.ones((2, seq, 32))], True, None)
        (rec,) = ring.since(mark, "attn.path")
        return rec
    for flash, seq in ((True, 128), (False, 64)):
        rec = record(attention_layer(FULL, seq, flash), seq)
        assert (rec["heads"], rec["kv_heads"], rec["gate"], rec["rope"],
                rec["rope_factor"], rec["window"]) == \
            (6, 1, "head", "yarn", 4.0, 0)
        assert rec["rope_scale"] == pytest.approx(1.1386294361119891)
        assert rec["rope_form"] == attn_ops.ROPE_FORM
        rec = record(attention_layer(WINDOW, seq, flash), seq)
        assert (rec["heads"], rec["kv_heads"], rec["gate"], rec["rope"],
                rec["rope_factor"], rec["rope_scale"], rec["window"]) == \
            (8, 1, "head", "plain", 1.0, 1.0, 24)
        assert rec["path"] == ("kernel" if flash else "dense")
    old = layer(dsl.AttentionLayer(
        "attn", ["x"], 4, head_dim=16, causal=True, flash=True,
        num_kv_heads=2, rotary_dim=16, rope_theta=1.5e6, window=64),
        [(2, 256, 32)])
    rec = record(old, 256)
    assert (rec["path"], rec["window"], rec["head_dim"], rec["live_blocks"],
            rec["causal_blocks"], rec["masked_blocks"]) == \
        ("kernel", 64, 16, 1, 1, 1)
    assert (rec["heads"], rec["kv_heads"], rec["gate"], rec["rope"],
            rec["rope_factor"], rec["rope_scale"]) == \
        (4, 2, "none", "plain", 1.0, 1.0)
    gated = layer(dsl.AttentionLayer(
        "attn", ["x"], 4, head_dim=16, causal=True, num_kv_heads=2,
        output_gate=True), [(2, 64, 32)])
    rec = record(gated, 64)
    assert (rec["gate"], rec["rope"], rec["rope_form"], rec["path"]) == \
        ("elementwise", "none", "none", "dense")


# ----------------------------------------------------- the MoE's shares

def moe_layer(held=16, first=0, n=48):
    lp = dsl.MoELayer("moe", ["x"], 256, hidden_dim=16, top_k=8,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=16, norm_topk_prob=True,
                      score_function="sigmoid", topk_eps=1e-20,
                      routed_scaling_factor=2.5, shared_gate=False)
    return layer(lp, [(1, n, 32)])


@pytest.mark.parametrize("held,first", [(16, 0), (16, 48), (256, 0)])
def test_moe_held_share_matches_reference(ref, held, first):
    impl = moe_layer(held, first)
    assert impl.blob_names() == ["router", "w_gate", "w_up", "w_down",
                                 "ws_gate", "ws_up", "ws_down"]
    lm.held_share(FAMILY, impl, [(1, 48, 32)],
                  dict(TOY, num_experts=held, router_outputs=256,
                       num_experts_per_tok=8, first_expert=first), 21)


def test_sixteen_shares_of_sixteen_experts_add_up_to_the_whole_layer(ref):
    """The deployment's cut: 16 chips x 16 of 256 experts, the router at
    256, top-8; the routed parts of all the shares, with the shared expert
    — which every chip computes alike — counted ONCE, add up to the uncut
    layer and to the reference's."""
    n, e = 48, 32
    whole = moe_layer(held=256, n=n)
    blobs = lm.fill(whole, jax.random.PRNGKey(14))
    g = jax.random.normal(jax.random.PRNGKey(15), (1, n, e))
    zeros = [jnp.zeros_like(b) for b in blobs[4:7]]
    (total,) = lm.sum_of_shares(
        lambda per, lo: moe_layer(held=per, first=lo, n=n), 16, 16,
        blobs[:4] + zeros, [g])
    total = total + ref.gated_ff(g, *blobs[4:7])
    close(total, whole.apply(blobs, [g], True, None)[0], tol=5e-4)
    d = dict(TOY, num_experts=256, router_outputs=256, num_experts_per_tok=8)
    close(total.reshape(n, e), ref.moe(g.reshape(n, e), blobs, d), tol=5e-4)
    # the weights: sigmoid scores of the 8 largest, over their sum, x 2.5
    idx, top = ref.route(g.reshape(n, e), blobs[0], d)
    s = jax.nn.sigmoid(g.reshape(n, e) @ blobs[0].T)
    picked = jnp.take_along_axis(s, idx, 1)
    close(top, 2.5 * picked / jnp.sum(picked, -1, keepdims=True))
    close(jnp.sum(top, -1), jnp.full((n,), 2.5), tol=1e-6)


# ---------------------------------------------------------- the whole model

def test_the_reference_reads_the_config(ref):
    d = ref.dims(FAMILY.config())
    assert d == dict(TOY, output_gate=True, yarn_rope=True)
    # the per-layer lists are read at their first num_hidden_layers entries
    d = ref.dims(FAMILY.config(num_hidden_layers=2))
    assert (d["layer_types"], d["num_attention_heads_per_layer"],
            d["mlp_layer_types"]) == ([FULL, WINDOW], [6, 8],
                                      ["dense", "sparse"])
    names = [n for n, _ in ref.layer_specs(d)]
    assert names == ["tok_embed", "block0/ln1", "block0/attn", "block0/ln2",
                     "block0/ff_gate", "block0/ff_up", "block0/ff_down",
                     "block1/ln1", "block1/attn", "block1/ln2", "block1/moe",
                     "ln_f", "lm_head"]


def test_net_is_the_published_layout():
    """The chip's share at the published widths, counted from the built
    net: 490,297,344 parameters; head counts, windows and both rotary
    settings by the layer's type; the leading dense block; layers 1-3 one
    scan run, layers 0 and 4 bodies of their own."""
    net = zoo.laguna(vocab_size=12544, num_hidden_layers=5, experts_held=16)
    by_name = lm.layout(net)
    blocks = lm.stack_contract(net)
    assert blocks == [f"block{i}" for i in range(5)]
    assert [l.type for l in net.layer if l.name.startswith("block0/")] == [
        "RMSNorm", "Attention", "Eltwise", "RMSNorm", "InnerProduct",
        "InnerProduct", "Sigmoid", "Eltwise", "InnerProduct", "Eltwise"]
    for p in blocks[1:]:
        assert [l.type for l in net.layer if l.name.startswith(p + "/")] == \
            ["RMSNorm", "Attention", "Eltwise", "RMSNorm", "MoE", "Eltwise"]
    for i, kind in enumerate([FULL, WINDOW, WINDOW, WINDOW, FULL]):
        ap = by_name[f"block{i}/attn"].attention_param
        full = kind == FULL
        assert (int(ap.num_heads), int(ap.num_kv_heads), int(ap.head_dim),
                int(ap.window), int(ap.rotary_dim), float(ap.rope_theta),
                str(ap.rope_type), bool(ap.head_gate),
                bool(ap.output_gate)) == (
            48 if full else 64, 8, 128, 0 if full else 512,
            64 if full else 128, 5e5 if full else 1e4,
            "yarn" if full else "plain", True, False), i
        if full:
            assert (float(ap.rope_factor), int(ap.rope_original_positions),
                    float(ap.rope_beta_fast), float(ap.rope_beta_slow),
                    float(ap.rope_scale)) == (64, 4096, 64, 1,
                                              1.4158883083359672)
        else:
            assert not ap.has("rope_factor") and not ap.has("rope_scale")
        assert len(by_name[f"block{i}/attn"].param) == 5
    moe = by_name["block4/moe"].moe_param
    assert (int(moe.num_experts), int(moe.top_k), int(moe.hidden_dim),
            int(moe.shared_hidden_dim), int(moe.experts_held),
            str(moe.score_function), bool(moe.selection_bias),
            float(moe.routed_scaling_factor), bool(moe.shared_gate)) == \
        (256, 8, 512, 512, 16, "sigmoid", False, 2.5, False)
    assert int(by_name["block0/ff_gate"].inner_product_param.num_output) \
        == 8192
    compiled = CompiledNet(net)
    count = sum(int(np.prod(shape))
                for shape, *_ in compiled.param_meta.values())
    assert count == 490_297_344
    runs = compiled._scan_runs()
    assert [(r["n"], r["entry"], r["out"]) for r in runs] == \
        [(3, "block0/res2", "block3/res2")]
    groups = compiled._remat_groups()
    names = [lp.name for lp, *_ in compiled.layers]
    assert [names[lo].split("/")[0] for lo in sorted(groups)] == blocks
    # the whole model: forty layers, ten full ones, one dense
    whole = lm.layout(zoo.laguna())
    kinds = [int(whole[f"block{i}/attn"].attention_param.num_heads)
             for i in range(40)]
    assert kinds == [48, 64, 64, 64] * 10
    assert [n for n in whole if n.endswith("/ff_down")] == ["block0/ff_down"]
    with pytest.raises(ValueError, match="3 entries for 5 layers"):
        zoo.laguna(num_hidden_layers=5, layer_types=[FULL, WINDOW, FULL])
    with pytest.raises(ValueError, match="chunked_attention"):
        zoo.laguna(num_hidden_layers=1, layer_types=["chunked_attention"])


@pytest.mark.parametrize("remat", ["none", "full"])
def test_whole_model_three_adam_steps_match_reference(ref, remat):
    """Three steps against the reference's own Adam: every loss, the first
    gradients — W_g's of both kinds of layer among them — and the steps'
    change."""
    solver, _ = lm.three_adam_steps(FAMILY, remat=remat)
    assert [{k: r[k] for k in FAMILY.runs[0]}
            for r in solver.net._scan_runs()] == list(FAMILY.runs)
    assert [solver.params[f"block{i}/attn"][4].shape
            for i in range(5)] == [(6, 32), (8, 32), (8, 32), (8, 32),
                                   (6, 32)]


@pytest.mark.parametrize("remat,scan", [("full", "on"), ("none", "on"),
                                        ("full", "off")])
def test_remat_and_scan_leave_the_gradients_alone(remat, scan):
    lm.remat_and_scan(FAMILY, remat, scan)


def test_two_periods_scan_as_window_runs_between_full_blocks():
    period = [FULL, WINDOW, WINDOW, WINDOW]
    solver = FAMILY.solver(dict(
        num_hidden_layers=8, layer_types=period * 2,
        num_attention_heads_per_layer=[6, 8, 8, 8] * 2,
        mlp_layer_types=["dense"] + ["sparse"] * 7))
    assert [(r["n"], r["entry"]) for r in solver.net._scan_runs()] == \
        [(3, "block0/res2"), (3, "block4/res2")]


def test_paths_and_load_are_recorded():
    """`moe.load` where the solver fetches a loss; `attn.path` of the five
    layers with the two rotary settings on the right layers; `moe.path`
    with the fitted tile, the rows an expert expects and the window."""
    tracer, since = lm.traced_steps(FAMILY, 2, dict(moe_stats=True))
    lm.held_loads(tracer, [f"block{i}/moe" for i in range(1, 5)])
    last = {r["layer"]: r for r in since("attn.path")}
    assert [(last[f"block{i}/attn"][k]) for i in range(5)
            for k in ("heads", "rope", "window")] == [
        6, "yarn", 0, 8, "plain", 24, 8, "plain", 24, 8, "plain", 24,
        6, "yarn", 0]
    assert {r["gate"] for r in last.values()} == {"head"}
    assert {r["kv_heads"] for r in last.values()} == {1}
    paths = [r for r in since("moe.path") if r["layer"].startswith("block")]
    assert paths and {r["score"] for r in paths} == {"sigmoid"}
    # 2 x 64 tokens x 2 of 16 on 4 held experts: 16 rows an expert
    assert {(r["tile"], r["rows_an_expert"]) for r in paths} == {(128, 16)}
    assert all(r["window"] >= 128 and not r["selection_bias"]
               and not r["shared_gate"] for r in paths)


def test_the_controls_are_other_models(ref):
    """`output_gate` and `yarn_rope` false in the reference (controls,
    never the program's): the gradients next to what was taken out move,
    and the program's net refuses both."""
    reference = ref.build(FAMILY.config(num_hidden_layers=2), 2)
    w0 = lm.bench("weights").make_weights(reference.specs, 1)
    # scores that tell the keys apart: the attention matrices at 0.3
    for name in ("block0/attn", "block1/attn"):
        w0[name] = [15.0 * w for w in w0[name]]
    data, labels = lm.tokens(1, 64)

    def grads(d):
        return jax.grad(lambda p: ref.forward_loss(
            p, data, labels, d) / 128)(w0)
    g1 = grads(reference.d)
    # (the flag, a blob that must move: W_o of the window layer under the
    # gate, W_q of the full layer under the rotary)
    for flag, name, blob in (("output_gate", "block1/attn", 3),
                             ("yarn_rope", "block0/attn", 0)):
        g0 = grads(dict(reference.d, **{flag: False}))
        with_, without = g1[name][blob], g0[name][blob]
        assert float(jnp.linalg.norm(with_ - without)) > \
            0.05 * float(jnp.linalg.norm(with_)), flag
        if flag == "output_gate":       # nothing reaches W_g without it
            assert float(jnp.max(jnp.abs(g0[name][4]))) == 0.0
            assert float(jnp.max(jnp.abs(g1[name][4]))) > 0.0
        with pytest.raises(SystemExit, match="reference's control"):
            sys.modules.pop("laguna_net", None)
            importlib.import_module("laguna_net").net(2, **{flag: False})


def test_the_gate_counts_under_the_projections_in_the_closed_ledger():
    """The benchmark's ledger (benchmark/step_parts.py, whose `INNER` set
    does not know `attn_gate`): W_g's product and sigmoid count under
    `attn_proj_in`, the multiply under `attn_proj_out`, which they lie
    inside, backward and recomputation too; nothing of the layer is
    `unscoped`, and it opens the four scopes of every attention."""
    tracer = Tracer(None)
    solver = FAMILY.solver(dict(num_hidden_layers=2), tracer=tracer,
                           remat="full")
    data, labels = lm.tokens(3, 64)
    parts = tracer.spans("net.parts")[-1]["parts"]
    assert parts["block0/attn"] == parts["block1/attn"] == "attn"
    table = lm.bench("step_parts").Parts(parts)
    paths = [q for p in solver.op_scopes({"data": data, "label": labels}
                                         ).values()
             for q in p.split(";")
             if q.startswith("jit(") and "/attn" in q and "block" in q]
    under = {}
    for p in paths:
        if "/attn_gate/" in p + "/":
            assert "/rope/" not in p and "/attn_core/" not in p, p
            under.setdefault(table.part_of("x", p), []).append(p)
    assert set(under) == {"attn_proj_in", "attn_proj_out"}
    assert all("/attn_proj_in/" in p for p in under["attn_proj_in"])
    assert all("/attn_proj_out/" in p for p in under["attn_proj_out"])
    assert {table.part_of("x", p) for p in paths} == {
        "attn_proj_in", "rope", "attn_core", "attn_proj_out"}
    assert any("rematted_computation" in p and "attn_gate" in p
               for p in paths)
    assert any("transpose(" in p and "attn_gate" in p for p in paths)
