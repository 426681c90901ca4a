"""GLM-4.7-Flash — multi-head latent attention (queries and keys-values
through normed latents, ONE rotary key a token shared by every head, a
value head wider than the key's non-rotary part), the leading dense block,
the sigmoid-routed MoE with its ungated shared expert, and the
multi-token-prediction module that `models/zoo.py:_lm_stack` builds —
against the plain reference (`benchmark/reference/glm4_moe_lite.py`): small
widths, seeded weights, float32 on the CPU. The family's record and the
bodies of the tests every family has are in `tests/lm_family.py`.
"""

import importlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.graph.compiler import CompiledNet
from sparknet_tpu.models import dsl, zoo
from sparknet_tpu.obs.trace import Tracer, default_tracer
from tests import lm_family as lm
from tests.lm_family import close, layer, ref  # noqa: F401  (a fixture)

FAMILY = lm.GLM4_MOE_LITE
TOY = FAMILY.toy
MTP = dict(num_nextn_predict_layers=1)


def latent_layer(seq=64, embed=32, flash=False, **over):
    sizes = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
                 qk_rope_head_dim=4, v_head_dim=16, rope_theta=1e6,
                 norm_eps=1e-5)
    sizes.update(over)
    lp = dsl.AttentionLayer("attn", ["x"], 4, causal=True, flash=flash,
                            **sizes)
    return layer(lp, [(2, seq, embed)])


# ------------------------------------------------------- latent attention

@pytest.mark.parametrize("flash,seq", [(False, 48), (True, 128)])
def test_latent_attention_matches_reference(ref, flash, seq):
    """The layer against the reference's attention, the dense path and the
    flash kernels (interpret mode): the output, the gradient of all seven
    blobs (both latent norms among them) and the input's."""
    impl = latent_layer(seq, flash=flash)
    assert [p[0] for p in impl.param_shapes()] == [
        (24, 32), (24,), (64, 24), (20, 32), (16,), (112, 16), (32, 64)]
    key = jax.random.PRNGKey(seq)
    blobs = lm.fill(impl, key)
    blobs[1], blobs[4] = 1.0 + blobs[1], 1.0 + blobs[4]     # norms near 1
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, seq, 32))
    probe = jax.random.normal(jax.random.fold_in(key, 10), (2, seq, 32))
    mark = default_tracer().mark()

    def mine(blobs, x):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(blobs, x):
        return jnp.stack([ref.attention(x[r], blobs, TOY, rows=16)
                          for r in range(2)])
    close(mine(blobs, x), theirs(blobs, x), tol=5e-4)
    assert default_tracer().since(mark, "attn.path")[0]["path"] == \
        ("kernel" if flash else "dense")
    got = jax.grad(lambda b, x: jnp.sum(mine(b, x) * probe), (0, 1))(blobs, x)
    want = jax.grad(lambda b, x: jnp.sum(theirs(b, x) * probe),
                    (0, 1))(blobs, x)
    for i, (a, b) in enumerate(zip(got[0] + [got[1]], want[0] + [want[1]])):
        assert float(jnp.max(jnp.abs(b))) > 0, i
        close(a, b, tol=2e-3)


def test_the_one_rotary_key_reaches_every_head():
    """k_pe has no head axis: a change of ONE rotary row of W_kva moves the
    scores, and so the output, of all four heads, where a change of one of
    head 0's own key rows in W_kvb moves head 0 alone. (The out projection
    is the identity on the 4 x 16 value heads, so a head's output can be
    read.)"""
    impl = latent_layer(embed=64)
    blobs = lm.fill(impl, jax.random.PRNGKey(3))
    blobs[6] = jnp.eye(64)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))

    def heads(blobs):
        return np.asarray(impl.apply(blobs, [x], True, None)[0]
                          ).reshape(2, 64, 4, 16)
    base = heads(blobs)
    shared = list(blobs)
    shared[3] = blobs[3].at[16 + 1].add(0.5)        # k_pe's second row
    moved = np.abs(heads(shared) - base).max(axis=(0, 1, 3))
    assert (moved > 1e-3).all(), moved
    own = list(blobs)
    own[5] = blobs[5].at[2].add(0.5)                # head 0's k_nope row 2
    moved = np.abs(heads(own) - base).max(axis=(0, 1, 3))
    assert moved[0] > 1e-3 and (moved[1:] == 0).all(), moved


def test_the_rotary_turns_the_last_part_of_a_query_head_alone():
    """With the rotary part's rows zero in W_qb and W_kva, positions play
    no part: the output of a sequence reversed in time below a query is
    the same. With them, it is not."""
    impl = latent_layer()
    blobs = lm.fill(impl, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 32))
    swapped = x.at[:, :63].set(x[:, :63][:, ::-1])  # the last query's past

    def last(blobs, x):
        return np.asarray(impl.apply(blobs, [x], True, None)[0][:, -1])
    assert np.abs(last(blobs, x) - last(blobs, swapped)).max() > 1e-3
    flat = list(blobs)
    rows = (np.arange(64) % 16) >= 12               # q_pe of every head
    flat[2] = blobs[2] * (~rows)[:, None]
    flat[3] = blobs[3].at[16:].set(0.0)             # k_pe
    close(last(flat, x), last(flat, swapped), tol=1e-5)


def test_a_value_head_of_another_width_takes_the_dense_path():
    """The flash kernel takes one head width; GLM's value head is as wide
    as its whole key (256 = 192 + 64). Another value width runs dense, and
    the record says why."""
    impl = latent_layer(128, flash=True, v_head_dim=24)
    assert impl.param_shapes()[5][0] == (4 * 36, 16)
    assert impl.param_shapes()[6][0] == (32, 96)
    blobs = lm.fill(impl, jax.random.PRNGKey(7))
    mark = default_tracer().mark()
    out = impl.apply(blobs, [jnp.ones((2, 128, 32))], True, None)[0]
    assert out.shape == (2, 128, 32)
    (rec,) = default_tracer().since(mark, "attn.path")
    assert rec["path"] == "dense" and "value head of 24" in rec["reason"]
    assert (rec["form"], rec["v_dim"], rec["head_dim"]) == ("latent", 24, 16)


@pytest.mark.parametrize("fields,why", [
    (dict(window=8), "window"), (dict(ring=True), "ring"),
    (dict(index_heads=2, index_head_dim=8, index_topk=4), "index_heads"),
    (dict(output_gate=True), "output_gate"), (dict(qk_norm=True), "qk_norm"),
    (dict(num_kv_heads=2), "num_kv_heads"),
    (dict(rotary_dim=4), "rotary_dim"), (dict(causal=False), "causal"),
    (dict(q_lora_rank=None), "needs q_lora_rank"),
    (dict(qk_rope_head_dim=3), "odd"), (dict(head_dim=32), "head_dim 32")])
def test_the_latent_form_refuses_what_has_no_meaning(fields, why):
    lp = dsl.AttentionLayer("blk/attn", ["x"], 4, causal=True,
                            q_lora_rank=24, kv_lora_rank=16,
                            qk_nope_head_dim=12, qk_rope_head_dim=4,
                            v_head_dim=16)
    for key, value in fields.items():
        if value is None:
            lp.attention_param.clear(key)
        else:
            setattr(lp.attention_param, key, value)
    with pytest.raises(ValueError, match=why) as err:
        layer(lp, [(1, 16, 32)])
    assert "blk/attn" in str(err.value)


# ----------------------------------------------------- the MoE's shares

def moe_layer(held=8, first=0, n=48):
    lp = dsl.MoELayer("moe", ["x"], 64, hidden_dim=16, top_k=4,
                      experts_held=held, first_expert=first,
                      shared_hidden_dim=16, norm_topk_prob=True,
                      score_function="sigmoid", selection_bias=True,
                      topk_eps=1e-20, routed_scaling_factor=1.8,
                      shared_gate=False)
    return layer(lp, [(1, n, 32)])


def test_eight_shares_of_eight_experts_add_up_to_the_whole_layer(ref):
    """The deployment's cut: 8 chips x 8 of 64 experts, the router at 64
    with its bias, top-4; the routed parts of all the shares, with the
    shared expert — which every chip computes alike — counted ONCE, add up
    to the uncut layer and to the reference's."""
    n, e = 48, 32
    whole = moe_layer(held=64, n=n)
    assert whole.blob_names() == ["router", "w_gate", "w_up", "w_down",
                                  "ws_gate", "ws_up", "ws_down", "bias"]
    blobs = lm.fill(whole, jax.random.PRNGKey(14))
    g = jax.random.normal(jax.random.PRNGKey(15), (1, n, e))
    zeros = [jnp.zeros_like(b) for b in blobs[4:7]]
    (total,) = lm.sum_of_shares(
        lambda per, lo: moe_layer(held=per, first=lo, n=n), 8, 8,
        blobs[:4] + zeros + blobs[7:], [g])
    total = total + ref.gated_ff(g, *blobs[4:7])
    close(total, whole.apply(blobs, [g], True, None)[0], tol=5e-4)
    d = dict(TOY, n_routed_experts=64, router_outputs=64,
             num_experts_per_tok=4)
    close(total.reshape(n, e), ref.moe(g.reshape(n, e), blobs, d), tol=5e-4)


# ----------------------------------------- the multi-token-prediction module

@pytest.mark.parametrize("offset", [1, -2, 0, 70])
def test_shift_moves_along_an_axis_and_fills_what_it_vacates(offset):
    impl = layer(dsl.ShiftLayer("s", ["x"], offset=offset, fill=-1), [(2, 64)])
    x = np.arange(128, dtype=np.int32).reshape(2, 64)
    (y,) = impl.apply([], [jnp.asarray(x)], True, None)
    want = np.full_like(x, -1)
    for i in range(64):
        if 0 <= i + offset < 64:
            want[:, i] = x[:, i + offset]
    assert y.dtype == jnp.int32 and np.array_equal(np.asarray(y), want)


def test_the_modules_target_is_the_label_moved_one_place(ref):
    """The module embeds `label` (t_{i+1}) with the main model's table and
    is scored against `label` moved one place left; its last place holds
    the ignored label and carries no loss: the loss top is the mean over
    the other 2 x 63 places, and no logit of the last place has a
    gradient."""
    net = CompiledNet(FAMILY.net(**MTP))
    params, state = net.init(jax.random.PRNGKey(0))
    batch = lm.batch_of(5)
    blobs, _ = net.apply(params, state, batch, train=False)
    labels = np.asarray(batch["label"])
    target = np.asarray(blobs["mtp1_label"])
    assert np.array_equal(target[:, :-1], labels[:, 1:])
    assert (target[:, -1] == zoo.IGNORE_LABEL).all()
    close(blobs["mtp1_embed"], params["tok_embed"][0][labels], tol=1e-6)
    logp = jax.nn.log_softmax(blobs["mtp1_lm_head"], axis=-1)
    picked = np.take_along_axis(np.asarray(logp)[:, :-1],
                                labels[:, 1:, None], axis=-1)
    close(blobs["mtp1_loss"], -picked.mean(), tol=1e-5)
    assert net.loss_weights["mtp1_loss"] == [pytest.approx(0.3)]
    assert net.loss_weights["loss"] == [1.0]
    close(net.total_loss(blobs), blobs["loss"] + 0.3 * blobs["mtp1_loss"],
          tol=1e-6)

    def loss_of_logits(logits):
        impl = dict((lp.name, i) for lp, i, _, _ in net.layers)["mtp1_loss"]
        return impl.apply([], [logits, blobs["mtp1_label"]], True, None)[0]
    g = np.asarray(jax.grad(loss_of_logits)(blobs["mtp1_lm_head"]))
    assert np.abs(g[:, -1]).max() == 0.0 and np.abs(g[:, :-1]).max() > 0


def test_the_module_shares_the_table_and_the_head():
    """One table and one head matrix in the whole net: the module's Embed
    and its head own nothing, and each shared blob's gradient is the sum
    of both uses (the main loss's alone and the module's alone add up to
    the whole)."""
    net = CompiledNet(FAMILY.net(**MTP))
    params, state = net.init(jax.random.PRNGKey(1))
    assert "mtp1_embed" not in params and "mtp1_lm_head" not in params
    assert net.param_refs["mtp1_embed"] == [("tok_embed", 0)]
    assert net.param_refs["mtp1_lm_head"] == [("lm_head", 0)]
    assert net.shared_params() == ["lm_head_table", "tok_embed_table"]
    assert net.prediction_depths() == [("mtp1_loss", pytest.approx(0.3))]
    batch = lm.batch_of(6)

    def part(top):
        return jax.grad(lambda p: net.apply(p, state, batch)[0][top])(params)
    whole = jax.grad(lambda p: net.loss_fn(p, state, batch)[0])(params)
    main, module = part("loss"), part("mtp1_loss")
    for name in ("tok_embed", "lm_head"):
        assert float(jnp.linalg.norm(module[name][0])) > 0
        close(whole[name][0], main[name][0] + 0.3 * module[name][0],
              tol=1e-5)
    # a net without the module names no blob, and says no depth
    plain = CompiledNet(FAMILY.net())
    assert plain.shared_params() == [] and plain.prediction_depths() == []
    assert CompiledNet(lm.LFM2_MOE.net()).prediction_depths() == []


# ---------------------------------------------------------- the whole model

def test_the_reference_reads_the_config(ref):
    d = ref.dims(FAMILY.config())
    assert d == dict(TOY, mtp_loss_weight=0.3, shared_rope_key=True,
                     kv_latent_norm=True)
    assert ref.dims(FAMILY.config(**MTP))["num_nextn_predict_layers"] == 1
    names = [n for n, _ in ref.layer_specs(ref.dims(FAMILY.config(**MTP)))]
    assert names[-9:] == ["lm_head", "mtp1_ln_e", "mtp1_ln_h", "mtp1_proj",
                          "block_mtp1/ln1", "block_mtp1/attn",
                          "block_mtp1/ln2", "block_mtp1/moe", "mtp1_ln_f"]
    assert "mtp1_embed" not in names and "mtp1_lm_head" not in names


@pytest.mark.parametrize("mtp", [0, 1])
def test_net_is_a_dense_block_then_moe_blocks_and_the_module(mtp):
    """The chip's share at the published widths, counted from the built
    net: 591,294,976 parameters, and 115,223,872 more with the module."""
    net = zoo.glm4_moe_lite(vocab_size=19360, num_hidden_layers=5,
                            experts_held=8, num_nextn_predict_layers=mtp)
    by_name = lm.layout(net)
    blocks = lm.stack_contract(net)
    assert blocks == [f"block{i}" for i in range(5)] + ["block_mtp1"] * mtp
    assert [l.type for l in net.layer if l.name.startswith("block0/")] == [
        "RMSNorm", "Attention", "Eltwise", "RMSNorm", "InnerProduct",
        "InnerProduct", "Sigmoid", "Eltwise", "InnerProduct", "Eltwise"]
    for p in blocks[1:]:
        assert [l.type for l in net.layer if l.name.startswith(p + "/")] == \
            ["RMSNorm", "Attention", "Eltwise", "RMSNorm", "MoE", "Eltwise"]
    attn = by_name["block1/attn"].attention_param
    assert (int(attn.num_heads), int(attn.q_lora_rank),
            int(attn.kv_lora_rank), int(attn.qk_nope_head_dim),
            int(attn.qk_rope_head_dim), int(attn.v_head_dim),
            float(attn.rope_theta), float(attn.norm_eps)) == \
        (20, 768, 512, 192, 64, 256, 1e6, pytest.approx(1e-5))
    assert [float(s.decay_mult) for s in by_name["block1/attn"].param] == \
        [1, 0, 1, 1, 0, 1, 1]
    moe = by_name["block4/moe"].moe_param
    assert (int(moe.num_experts), int(moe.top_k), int(moe.hidden_dim),
            int(moe.shared_hidden_dim), int(moe.experts_held),
            float(moe.routed_scaling_factor), bool(moe.shared_gate)) == \
        (64, 4, 1536, 1536, 8, pytest.approx(1.8), False)
    assert int(by_name["block0/ff_gate"].inner_product_param.num_output) \
        == 10240
    compiled = CompiledNet(net)
    count = sum(int(np.prod(shape))
                for shape, *_ in compiled.param_meta.values())
    assert count == 591_294_976 + mtp * 115_223_872
    runs = compiled._scan_runs()
    assert [(r["n"], r["entry"], r["out"]) for r in runs] == \
        [(4, "block0/res2", "block4/res2")]
    groups = compiled._remat_groups()
    names = [lp.name for lp, *_ in compiled.layers]
    assert [names[lo].split("/")[0] for lo in sorted(groups)] == blocks
    if mtp:
        assert [l.name for l in net.layer][-16:-11] == [
            "loss", "mtp1_embed", "mtp1_ln_e", "mtp1_ln_h", "mtp1_cat"]
        assert names[sorted(groups)[-1] - 1] == "mtp1_proj"


def test_two_depths_chain_their_labels_and_their_streams():
    net = FAMILY.net(num_nextn_predict_layers=2)
    by_name = lm.layout(net)
    assert list(by_name["mtp2_embed"].bottom) == ["mtp1_label"]
    assert list(by_name["mtp2_label"].bottom) == ["mtp1_label"]
    assert list(by_name["mtp2_ln_h"].bottom) == ["block_mtp1/res2"]
    assert list(by_name["mtp2_loss"].bottom) == ["mtp2_lm_head",
                                                 "mtp2_label"]
    assert [n for n, _ in CompiledNet(net).prediction_depths()] == \
        ["mtp1_loss", "mtp2_loss"]


@pytest.mark.parametrize("mtp,remat", [(0, "none"), (1, "full")])
def test_whole_model_three_adam_steps_match_reference(ref, mtp, remat):
    """Three steps against the reference's own Adam, the module off and on:
    every loss (with the module's 0.3 share), the first gradients — the
    shared table's and head's among them, each the sum of both uses — and
    the steps' change."""
    over = dict(num_nextn_predict_layers=mtp)
    solver, _ = lm.three_adam_steps(FAMILY, over, FAMILY.config(**over),
                                    remat=remat)
    assert [{k: r[k] for k in FAMILY.runs[0]}
            for r in solver.net._scan_runs()] == list(FAMILY.runs)
    # the route's bias is a buffer: nothing moved it
    assert float(jnp.max(jnp.abs(solver.params["block1/moe"][-1]))) == 0.0
    assert ("block_mtp1/moe" in solver.params) == bool(mtp)


@pytest.mark.parametrize("remat,scan", [("full", "on"), ("none", "on"),
                                        ("full", "off")])
def test_remat_and_scan_leave_the_gradients_unchanged(remat, scan):
    """With the module: layers 1-4 are one scan run, layer 0 and the
    module's block stand outside it, each block a remat segment."""
    lm.remat_and_scan(FAMILY, remat, scan, MTP, FAMILY.config(**MTP))


def test_the_controls_are_other_models(ref):
    """`shared_rope_key` and `kv_latent_norm` false in the reference
    (controls, never the program's): the gradients of the blobs next to
    what was taken out move (at these toy widths the loss itself moves by
    less than float32 shows: the chip's control reads the published
    widths), and the program's net refuses both."""
    reference = ref.build(FAMILY.config(num_hidden_layers=2), 2)
    w0 = lm.bench("weights").make_weights(reference.specs, 1)
    # scores that tell the keys apart: the attention matrices at 0.3
    w0["block0/attn"] = [w if w.ndim == 1 else 15.0 * w
                         for w in w0["block0/attn"]]
    data, labels = lm.tokens(1, 64)

    def loss(d):
        return jax.value_and_grad(lambda p: ref.forward_loss(
            p, data, labels, d) / 128)(w0)
    _, g1 = loss(reference.d)
    for flag, blob in (("shared_rope_key", 3), ("kv_latent_norm", 5)):
        _, g0 = loss(dict(reference.d, **{flag: False}))
        with_, without = g1["block0/attn"][blob], g0["block0/attn"][blob]
        assert float(jnp.linalg.norm(with_ - without)) > \
            0.05 * float(jnp.linalg.norm(with_)), flag
        with pytest.raises(SystemExit, match="reference's control"):
            sys.modules.pop("glm4_moe_lite_net", None)
            importlib.import_module("glm4_moe_lite_net").net(
                2, **{flag: False})


def test_every_operation_of_the_layer_has_a_part_in_the_closed_ledger():
    """The benchmark's ledger (benchmark/step_parts.py, whose `INNER` set
    does not know the three `mla_*` scopes): what runs under them counts
    under `attn_proj_in`, which they lie inside, backward and recomputation
    too; nothing of the layer is `unscoped`, and it opens the four scopes
    of every attention."""
    tracer = Tracer(None)
    solver = FAMILY.solver(dict(num_hidden_layers=1), tracer=tracer,
                           remat="full")
    data, labels = lm.tokens(3, 64)
    parts = tracer.spans("net.parts")[-1]["parts"]
    assert parts["block0/attn"] == "attn"
    table = lm.bench("step_parts").Parts(parts)
    paths = [q for p in solver.op_scopes({"data": data, "label": labels}
                                         ).values()
             for q in p.split(";")
             if q.startswith("jit(") and "block0/attn" in q]
    by_scope = {}
    for p in paths:
        for n in ("mla_q_latent", "mla_kv_latent", "mla_k_assemble"):
            if f"/{n}/" in p + "/":
                assert "/attn_proj_in/" in p and "/rope/" not in p, p
                by_scope.setdefault(n, set()).add(table.part_of("x", p))
    assert by_scope == {n: {"attn_proj_in"} for n in (
        "mla_q_latent", "mla_kv_latent", "mla_k_assemble")}
    assert {table.part_of("x", p) for p in paths} == {
        "attn_proj_in", "rope", "attn_core", "attn_proj_out"}
    assert any("rematted_computation" in p and "mla_k_assemble" in p
               for p in paths)
