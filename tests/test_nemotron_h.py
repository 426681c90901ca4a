"""The Nemotron-H tower — the Mamba-2 mixer's chunked scan, the two-matrix
squared-ReLU experts with their ungated shared expert, attention at 16
query heads a key-value head, and the whole net from a pattern string —
against the plain reference (`benchmark/reference/nemotron_h.py`): small
widths, seeded weights, float32 on the CPU. The family's record and the
bodies of the tests every family has are in `tests/lm_family.py`.

The reference computes the state-space layer as the RECURRENCE, token by
token; the program in chunks. So every comparison of the two is a test of
the chunked form: of its decay masks, its chunk states and its carry.
"""

import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import Tracer, default_tracer
from sparknet_tpu.ops import mamba2 as m2
from tests import lm_family as lm
from tests.lm_family import close, layer, ref  # noqa: F401  (a fixture)

FAMILY = lm.NEMOTRON_H
TOY = FAMILY.toy


def mamba_layer(seq, batch=2, chunk=16, **over):
    lp = dsl.Mamba2Layer("ssm", ["x"], 4, 8, 16, 2, conv_kernel=4,
                         chunk=chunk, norm_eps=1e-5, **over)
    return layer(lp, [(batch, seq, 32)])


def mamba_blobs(impl, key):
    """Blobs away from their fillers' symmetric points, so that every
    gradient is something: A_log in [0, log 16), dt_bias in its range, D
    and the norm near 1."""
    out = []
    for i, (shape, *_) in enumerate(impl.param_shapes()):
        k = jax.random.fold_in(key, i)
        out.append({
            3: lambda: jax.random.uniform(k, shape, maxval=np.log(16.0)),
            4: lambda: 1.0 + 0.2 * jax.random.normal(k, shape),
            5: lambda: jax.random.uniform(k, shape, minval=-4.0,
                                          maxval=-1.0),
            6: lambda: 1.0 + 0.2 * jax.random.normal(k, shape),
        }.get(i, lambda: 0.3 * jax.random.normal(k, shape))())
    return out


# ------------------------------------------------------- the chunked scan

@pytest.mark.parametrize("seq", [16, 32, 80, 37])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(ref, seq):
    """The layer (chunks of 16: one chunk, two, five, and lengths that are
    no whole number of chunks, which the layer pads) against the
    reference's token-by-token recurrence: the output and the gradient of
    every blob, A_log, D, dt_bias and the conv's bias among them."""
    impl = mamba_layer(seq)
    assert [s[0] for s in impl.param_shapes()] == [
        (132, 32), (96, 4), (96,), (4,), (4,), (4,), (32,), (32, 32)]
    blobs = mamba_blobs(impl, jax.random.PRNGKey(seq))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 32))
    probe = jax.random.normal(jax.random.PRNGKey(2), (2, seq, 32))

    def mine(blobs, x):
        return jnp.sum(impl.apply(blobs, [x], True, None)[0] * probe)

    def theirs(blobs, x):
        return jnp.sum(jnp.stack([ref.mamba2(x[r], blobs, TOY)
                                  for r in range(2)]) * probe)
    close(impl.apply(blobs, [x], True, None)[0],
          jnp.stack([ref.mamba2(x[r], blobs, TOY) for r in range(2)]))
    got, want = (jax.jit(jax.grad(f, (0, 1)))(blobs, x)
                 for f in (mine, theirs))
    for i, (g, w) in enumerate(zip(got[0], want[0])):
        assert float(jnp.max(jnp.abs(w))) > 0, i
        close(g, w, tol=1e-3)
    close(got[1], want[1], tol=1e-3)


def scan_inputs(key, b=2, s=48, h=4, p=8, g=2, n=16):
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jax.random.uniform(ks[1], (b, s, h), minval=0.01, maxval=0.3),
            -jax.random.uniform(ks[2], (h,), minval=0.5, maxval=4.0),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)))


def test_the_carry_matters():
    """A state-space layer is no window of one chunk: the output of the
    second chunk differs from a scan restarted there, and agrees with it
    once the first chunk's decay is total; the last state is the
    recurrence's."""
    x, dt, a, b, c = scan_inputs(jax.random.PRNGKey(3), s=32)
    whole, last, survive = m2.ssd_chunked(x, dt, a, b, c, 16)
    restarted, _, _ = m2.ssd_chunked(x[:, 16:], dt[:, 16:], a, b[:, 16:],
                                     c[:, 16:], 16)
    gap = float(jnp.max(jnp.abs(whole[:, 16:] - restarted)))
    assert gap > 0.05 * float(jnp.max(jnp.abs(restarted)))
    assert 0.0 < float(survive) < 1.0
    close(whole[:, :16], m2.ssd_chunked(x[:, :16], dt[:, :16], a, b[:, :16],
                                        c[:, :16], 16)[0])
    # the same tokens once the carried state dies at the chunk's first
    # token (a_16 = exp(50 A) < 1e-10): then, and only then, they agree
    dead = dt.at[:, 16].set(50.0)
    whole_dead, _, _ = m2.ssd_chunked(x, dead, a, b, c, 16)
    close(whole_dead[:, 16:], m2.ssd_chunked(
        x[:, 16:], dead[:, 16:], a, b[:, 16:], c[:, 16:], 16)[0])
    # the state after the last token, by the recurrence
    state = np.zeros((2, 4, 8, 16))
    for t in range(32):
        state = np.exp(np.asarray(dt[:, t] * a))[..., None, None] * state \
            + np.einsum("bh,bhp,bhn->bhpn", dt[:, t], x[:, t],
                        np.repeat(b[:, t], 2, axis=1))
    close(last, state)


def test_heads_of_one_group_share_b_and_c():
    """Head i reads group i // (H / G): the scan with 2 groups is the scan
    with every head its own group and the groups' B and C repeated; and two
    heads of one group with the same x, delta and A give the same y."""
    x, dt, a, b, c = scan_inputs(jax.random.PRNGKey(4))
    grouped, _, _ = m2.ssd_chunked(x, dt, a, b, c, 16)
    spread, _, _ = m2.ssd_chunked(x, dt, a, jnp.repeat(b, 2, axis=2),
                                  jnp.repeat(c, 2, axis=2), 16)
    close(grouped, spread)
    x = x.at[:, :, 1].set(x[:, :, 0])
    dt, a = dt.at[:, :, 1].set(dt[:, :, 0]), a.at[1].set(a[0])
    y, _, _ = m2.ssd_chunked(x, dt, a, b, c, 16)
    close(y[:, :, 1], y[:, :, 0], tol=1e-6)
    assert float(jnp.max(jnp.abs(y[:, :, 2] - y[:, :, 0]))) > 0.1


def test_the_gated_norm_gates_first_and_norms_each_group():
    y = jax.random.normal(jax.random.PRNGKey(5), (3, 32))
    z = jax.random.normal(jax.random.PRNGKey(6), (3, 32))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(7), (32,))
    got = m2.gated_group_norm(y, z, w, 4, 1e-5)
    g = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 32) * np.asarray(w)
    close(got, want, tol=1e-5)
    # neither the norm before the gate, nor one norm over the whole width
    yn = np.asarray(y, np.float64).reshape(3, 4, 8)
    before = (yn / np.sqrt((yn * yn).mean(-1, keepdims=True) + 1e-5)
              ).reshape(3, 32) * np.asarray(jax.nn.silu(z) * w)
    assert np.abs(np.asarray(got) - before).max() > 0.1
    assert np.abs(np.asarray(got) - np.asarray(
        m2.gated_group_norm(y, z, w, 1, 1e-5))).max() > 0.1


def test_the_survival_statistic_and_the_path_are_recorded():
    tracer, since = lm.traced_steps(
        FAMILY, 1, dict(ssm_stats=True, moe_stats=True, flash=True,
                        seq_len=128), remat="full")
    stats = tracer.spans("ssm.stats")
    assert {r["layer"] for r in stats} == {"block0/mixer"}
    assert all(0.0 < r["chunk_survival"] < 1.0 for r in stats)
    paths = since("ssm.path")
    assert paths and all(
        r["layer"] == "block0/mixer" and r["path"] == "chunked"
        and (r["heads"], r["head_dim"], r["state"], r["groups"],
             r["chunk"]) == (4, 8, 16, 2, 16) for r in paths)
    moe = since("moe.path")
    assert {r["layer"] for r in moe} == {"block1/mixer", "block3/mixer"}
    assert all(r["matrices"] == 2 and r["activation"] == "relu2"
               and r["shared_gate"] is False and r["score"] == "sigmoid"
               and r["selection_bias"] for r in moe)
    assert {r["layer"] for r in tracer.spans("moe.load")} == \
        {"block1/mixer", "block3/mixer"}
    attn = since("attn.path")
    assert attn and all(r["layer"] == "block2/mixer"
                        and r["path"] == "kernel" for r in attn)
    # what the backward reuses: the flash pass's output and logsumexp; at
    # the toy heads (8 wide, state 16) the scan is XLA's form and names
    # nothing (tests/test_pallas_ssd.py has the kernels' three)
    kept = since("remat.kept")
    assert {(r["layer"], r["array"]) for r in kept} == {
        ("block2/mixer", "o"), ("block2/mixer", "lse")}


def test_a_three_matrix_net_records_what_it_recorded():
    """The fields that PR 42 adds to `moe.path` read 3 and the gate's
    presence on a net that leaves them unset; the others are unchanged."""
    lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=16, top_k=2,
                      experts_held=4, shared_hidden_dim=16)
    impl = layer(lp, [(1, 16, 32)])
    assert impl.blob_names() == ["router", "w_gate", "w_up", "w_down",
                                 "ws_gate", "ws_up", "ws_down",
                                 "shared_gate"]
    ring = default_tracer()
    mark = ring.mark()
    blobs = [jnp.zeros(s[0]) for s in impl.param_shapes()]
    impl.apply(blobs, [jnp.zeros((1, 16, 32))], True, None)
    (rec,) = ring.since(mark, "moe.path")
    assert (rec["activation"], rec["score"], rec["selection_bias"],
            rec["combine"], rec["segment"], rec["matrices"],
            rec["shared_gate"]) == ("silu", "softmax", False, "gather", 2,
                                    3, True)


# --------------------------------------- the experts, the route, the shares

def moe_layer(held=4, first=0, n=48, embed=32, hidden=24, shared=40,
              experts=16, top_k=2, **over):
    lp = dsl.MoELayer("moe", ["x"], experts, hidden_dim=hidden, top_k=top_k,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=shared, norm_topk_prob=True,
                      score_function="sigmoid", selection_bias=True,
                      topk_eps=1e-20, routed_scaling_factor=2.5,
                      expert_activation="relu2", expert_gate_matrix=False,
                      shared_gate=False, **over)
    return layer(lp, [(1, n, embed)])


def moe_blobs(impl, key, bias=True):
    blobs = lm.fill(impl, key)
    if not bias:
        blobs[-1] = jnp.zeros_like(blobs[-1])
    return blobs


@pytest.mark.parametrize("path,embed,tile", [("xla", 32, 8),
                                             ("kernel", 128, 8)])
@pytest.mark.parametrize("remat", [False, True])
def test_two_matrix_experts_and_ungated_shared_match_reference(
        ref, monkeypatch, path, embed, tile, remat):
    """W_down relu(W_up h)^2 over the held experts plus the ungated shared
    expert, at a hidden width (24) that is no multiple of the row tile nor
    of the lane width: forward, the replay under jax.checkpoint and the
    backward (every blob's gradient and the input's), through XLA's ragged
    product and — the layer's choice made for it, as on a TPU — through
    the kernels in interpret mode, which see the width padded to 128 in
    the cast copies while the blobs keep 24."""
    from sparknet_tpu.ops import moe as moe_ops
    if path == "kernel":
        monkeypatch.setattr(moe_ops.MoE, "_why_xla", lambda self, dt: None)
    impl = moe_layer(embed=embed, tile_rows=tile)
    assert [s[0] for s in impl.param_shapes()] == [
        (16, embed), (4, 24, embed), (4, embed, 24), (40, embed),
        (embed, 40), (16,)]
    assert impl.param_shapes()[-1][2:] == (0.0, 0.0)
    blobs = moe_blobs(impl, jax.random.PRNGKey(8))
    g = jax.random.normal(jax.random.PRNGKey(9), (1, 48, embed))
    probe = jax.random.normal(jax.random.PRNGKey(10), (48, embed))
    d = dict(TOY, hidden_size=embed)
    mark = default_tracer().mark()

    def mine(blobs, g):
        return impl.apply(blobs, [g], True, None)[0].reshape(48, embed)
    if remat:
        mine = jax.checkpoint(mine)
    close(mine(blobs, g), ref.moe(g[0], blobs, d), tol=5e-4)
    (rec,) = default_tracer().since(mark, "moe.path")[:1]
    assert rec["path"] == path
    assert ("padded by 104" in rec["reason"]) == (path == "kernel")
    got = jax.grad(lambda b, g: jnp.sum(mine(b, g) * probe), (0, 1))(blobs, g)
    want = jax.grad(lambda b, g: jnp.sum(ref.moe(g[0], b, d) * probe),
                    (0, 1))(blobs, g)
    for i, (a, b) in enumerate(zip(got[0][:-1], want[0][:-1])):
        assert a.shape == blobs[i].shape
        close(a, b, tol=2e-3)
    close(got[1], want[1], tol=2e-3)
    assert float(jnp.max(jnp.abs(got[0][-1]))) == 0.0   # the bias: a buffer


def test_the_route_is_sigmoid_with_a_bias_that_picks_and_does_not_weigh(ref):
    impl = moe_layer()
    router = jax.random.normal(jax.random.PRNGKey(11), (16, 32))
    g = jax.random.normal(jax.random.PRNGKey(12), (48, 32))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(13), (16,))
    idx, top = impl.route(g, router, bias)
    ridx, rtop = ref.route(g, router, bias, TOY)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    close(top, rtop, tol=1e-6)
    # by hand: the largest of s + b, weighed by s / (sum + 1e-20) x 2.5
    s = 1.0 / (1.0 + np.exp(-np.asarray(g @ router.T, np.float64)))
    picked = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    assert np.array_equal(np.sort(picked, -1), np.sort(np.asarray(idx), -1))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    close(top, 2.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20),
          tol=1e-5)
    # the bias changed the picks and no weight of a pick they share
    plain, ptop = impl.route(g, router, jnp.zeros(16))
    assert not np.array_equal(np.asarray(plain), np.asarray(idx))
    close(jnp.sum(top, -1), jnp.full(48, 2.5), tol=1e-5)
    close(jnp.sum(ptop, -1), jnp.full(48, 2.5), tol=1e-5)


def test_sixteen_shares_of_eight_experts_add_up_to_the_whole_layer(ref):
    """The deployment's cut: 16 chips x 8 of 128 experts, the router at
    128 with its bias, top-6; the routed parts of all the shares, with the
    shared expert — which every chip computes alike — counted ONCE, add up
    to the reference's uncut 128-expert layer."""
    e, f, fs, n = 32, 24, 40, 48
    key = jax.random.PRNGKey(14)
    whole = moe_layer(held=128, experts=128, top_k=6, n=n)
    blobs = moe_blobs(whole, key)
    router, w_up, w_down, ws_up, ws_down, bias = blobs
    g = jax.random.normal(jax.random.fold_in(key, 9), (1, n, e))
    (total,) = lm.sum_of_shares(
        lambda per, lo: moe_layer(held=per, first=lo, experts=128, top_k=6,
                                  n=n),
        16, 8, [router, w_up, w_down, jnp.zeros_like(ws_up),
                jnp.zeros_like(ws_down), bias], [g], routed=2)
    total = total + jnp.square(jax.nn.relu(g @ ws_up.T)) @ ws_down.T
    close(total, whole.apply(blobs, [g], True, None)[0], tol=5e-4)
    d = dict(TOY, n_routed_experts=128, router_outputs=128,
             num_experts_per_tok=6, moe_intermediate_size=f,
             moe_shared_expert_intermediate_size=fs)
    close(total.reshape(n, e), ref.moe(g.reshape(n, e), blobs, d), tol=5e-4)


@pytest.mark.parametrize("fields,why", [
    (dict(expert_activation="relu2"), "two-matrix"),
    (dict(expert_activation="gelu", expert_gate_matrix=False), "want silu"),
    (dict(shared_gate=False), "names none"),
    (dict(top_k=None, expert_gate_matrix=False), "no-drop form")])
def test_the_moe_refuses_what_has_no_meaning(fields, why):
    args = dict(top_k=2, experts_held=4)
    args.update(fields)
    if args["top_k"] is None:           # the Switch form: fields by hand
        lp = dsl.MoELayer("blk/moe", ["x"], 8, hidden_dim=16)
        lp.moe_param.expert_gate_matrix = False
    else:
        lp = dsl.MoELayer("blk/moe", ["x"], 8, hidden_dim=16, **args)
    with pytest.raises(ValueError, match=why) as err:
        layer(lp, [(1, 16, 32)])
    assert "blk/moe" in str(err.value)


# ------------------------------------------------------------- attention

@pytest.mark.parametrize("flash", [False, True])
def test_attention_at_sixteen_query_heads_a_key_value_head(ref, flash):
    """Causal grouped-query attention without positions, 16 query heads on
    ONE key-value head: the dense path and the flash kernels (interpret
    mode, the shared head read in place) against the reference, forward
    and backward."""
    s = 128
    lp = dsl.AttentionLayer("attn", ["x"], 16, head_dim=8, causal=True,
                            flash=flash, num_kv_heads=1)
    impl = layer(lp, [(2, s, 32)])
    assert [p[0] for p in impl.param_shapes()] == [
        (128, 32), (8, 32), (8, 32), (32, 128)]
    key = jax.random.PRNGKey(15)
    blobs = lm.fill(impl, key)
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, s, 32))
    probe = jax.random.normal(jax.random.fold_in(key, 10), (2, s, 32))
    mark = default_tracer().mark()

    def mine(blobs, x):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(blobs, x):
        return jnp.stack([ref.attention(x[r], blobs, TOY, rows=32)
                          for r in range(2)])
    close(mine(blobs, x), theirs(blobs, x), tol=5e-4)
    assert default_tracer().since(mark, "attn.path")[0]["path"] == \
        ("kernel" if flash else "dense")
    got = jax.grad(lambda b, x: jnp.sum(mine(b, x) * probe), (0, 1))(blobs, x)
    want = jax.grad(lambda b, x: jnp.sum(theirs(b, x) * probe),
                    (0, 1))(blobs, x)
    for a, b in zip(got[0] + [got[1]], want[0] + [want[1]]):
        close(a, b, tol=2e-3)


# ---------------------------------------------------------- the whole model

def test_the_reference_reads_the_config(ref):
    d = ref.dims(FAMILY.config())
    assert d == dict(TOY, seq_len=48)
    # a stage of a longer model keeps the whole model's pattern
    cut = dict(FAMILY.config(), hybrid_override_pattern="ME")
    assert ref.dims(cut)["whole_pattern"] == "ME*E"
    assert ref.dims(cut)["pattern"] == "ME"


def test_net_is_one_mixer_a_block_from_the_pattern():
    net = zoo.nemotron_h(experts_held=8, vocab_size=16384, layers=(0, 7))
    lm.layout(net)
    kinds = [(l.name, l.type) for l in net.layer]
    assert [n for n, _ in kinds[:3]] == ["data", "label", "tok_embed"]
    mixers = [t for n, t in kinds if n.endswith("/mixer")]
    assert mixers == ["Mamba2", "MoE", "Mamba2", "MoE", "Mamba2",
                      "Attention", "MoE"]
    for i in range(7):
        assert [n for n, _ in kinds[3 + 3 * i:6 + 3 * i]] == [
            f"block{i}/ln", f"block{i}/mixer", f"block{i}/res"]
    assert zoo.NEMOTRON_H_PATTERN == (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    assert [zoo.NEMOTRON_H_PATTERN.count(c) for c in "ME*"] == [23, 23, 6]
    # a later stage is named by its place in the whole pattern
    later = zoo.nemotron_h(experts_held=8, layers=(35, 44))
    assert [l.name for l in later.layer if l.name.endswith("/mixer")][0] \
        == "block35/mixer"
    moe = next(l for l in net.layer if l.name == "block1/mixer").moe_param
    assert (int(moe.num_experts), int(moe.top_k), int(moe.hidden_dim),
            int(moe.shared_hidden_dim), int(moe.experts_held)) == \
        (128, 6, 1856, 3712, 8)
    assert abs(float(moe.down_filler.std) - 0.02 / 52 ** 0.5) < 1e-9


@pytest.mark.parametrize("args,why", [
    (dict(pattern="MEXE"), "letters"),
    (dict(layers=(0, 53)), "outside"),
    (dict(layers=(7, 7)), "outside"),
    (dict(pattern="ME", layers=(1, 3)), "outside")])
def test_the_builder_refuses_a_letter_or_a_range_it_does_not_know(args, why):
    with pytest.raises(ValueError, match=why):
        zoo.nemotron_h(**args)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_whole_model_three_adam_steps_match_reference(ref, remat):
    solver, _ = lm.three_adam_steps(FAMILY, remat=remat)
    assert solver.net._scan_runs() == list(FAMILY.runs) == []
    # the route's bias is a buffer: nothing moved it
    assert float(jnp.max(jnp.abs(solver.params["block1/mixer"][-1]))) == 0.0


def test_the_carry_dropped_control_is_another_model(ref):
    """`carry` false in the reference (a control, never the program's):
    every chunk from a zero state. Its gradients differ from the model's,
    and most on the state-space blobs (at these toy widths, a state of 16
    and delta near 0.01, the scan is small beside the D skip and the loss
    itself moves by less than float32 shows: the chip's control reads the
    published widths)."""
    reference = ref.build(FAMILY.config(pattern="M"), 2)    # one mixer alone
    w0 = lm.bench("weights").make_weights(reference.specs, 1)
    data, labels = lm.tokens(1, 48)

    def loss(d):
        return jax.value_and_grad(lambda p: ref.forward_loss(
            p, data, labels, d) / 96)(w0)
    (l1, g1), (l0, g0) = loss(reference.d), loss(dict(reference.d,
                                                      carry=False))
    assert abs(float(l1) - float(l0)) < 1e-4 * abs(float(l1))
    for blob, least in ((3, 0.2), (5, 0.05)):       # A_log, dt_bias
        with_, without = g1["block0/mixer"][blob], g0["block0/mixer"][blob]
        assert float(jnp.linalg.norm(with_ - without)) > \
            least * float(jnp.linalg.norm(with_)), blob
    with pytest.raises(SystemExit, match="carries its state"):
        sys.modules.pop("nemotron_h_net", None)
        importlib.import_module("nemotron_h_net").net(2, carry=False)


def test_every_operation_of_the_mixer_has_a_part_in_the_closed_ledger():
    """The benchmark's ledger (benchmark/step_parts.py, whose `INNER` set
    does not know the state-space scopes): what runs under the five
    `ssm_*` scopes counts under the layer's part `ssm`, backward and
    recomputation too, nothing of the layer is `unscoped`, and none of the
    five opens inside another."""
    tracer = Tracer(None)
    solver = FAMILY.solver(dict(pattern="M", whole_pattern="M"),
                           tracer=tracer, remat="full")
    data, labels = lm.tokens(3, 48)
    batch = {"data": data, "label": labels}
    parts = tracer.spans("net.parts")[-1]["parts"]
    assert parts["block0/mixer"] == "ssm"
    table = lm.bench("step_parts").Parts(parts)
    paths = [q for p in solver.op_scopes(batch).values()
             for q in p.split(";")
             if q.startswith("jit(") and "block0/mixer" in q]
    names = ("ssm_proj_in", "ssm_conv", "ssm_scan", "ssm_gate_norm",
             "ssm_proj_out")
    by_scope = {}
    for p in paths:
        inside = [n for n in names if f"/{n}/" in p + "/"]
        assert len(inside) <= 1, p
        for n in inside:
            by_scope.setdefault(n, set()).add(table.part_of("x", p))
    assert by_scope == {n: {"ssm"} for n in names}
    assert {table.part_of("x", p) for p in paths} == {"ssm"}
    assert any("rematted_computation" in p and "ssm_scan" in p
               for p in paths)
