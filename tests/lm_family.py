"""What the language-model family files (`test_qwen3_next.py`,
`test_smallthinker.py`, `test_lfm2_moe.py`, `test_keye_vl2.py`,
`test_nemotron_h.py`, `test_glm4_moe_lite.py`, `test_laguna.py`) share;
pytest collects nothing here.

A family is a `Family` record: its builder in `models/zoo.py`, its plain
reference (`benchmark/reference/<family>.py`, imported from where it lies,
not copied), the toy sizes both are built at, and the tolerances and scan
runs its whole-model tests hold it to. The bodies of the tests that every
family has are functions of that record; a family's file keeps the test
names and parametrisations (so the ids stay), one call into a body each,
and what is its own. Kernel cases live in `tests/test_pallas_<kernel>.py`.
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import zoo
from sparknet_tpu.obs.trace import Tracer, default_tracer
from sparknet_tpu.proto import Message, text_format
from sparknet_tpu.solver.solver import Solver

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

SOLVER = dict(type="Adam", base_lr=1e-3, lr_policy="fixed", momentum=0.9,
              momentum2=0.95, delta=1e-8, weight_decay=0.1)


def bench(name):
    """A module of benchmark/, from where it lies."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(name)


@pytest.fixture(scope="module")
def ref(request):
    """The plain reference of the test file's `FAMILY`."""
    return request.module.FAMILY.ref()


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def layer(lp, shapes):
    return get_layer(lp.type)(lp, shapes, 0)


def fill(impl, key, std=0.3):
    """Seeded gaussian blobs for a layer: no norm weight or decay at a
    special value."""
    return [std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
            for i, (shape, *_) in enumerate(impl.param_shapes())]


def same_value_and_grads(mine, theirs, args, cot, tol=5e-4):
    close(mine(*args), theirs(*args))
    which = tuple(range(len(args)))
    gm = jax.grad(lambda *a: jnp.sum(mine(*a) * cot), which)(*args)
    gt = jax.grad(lambda *a: jnp.sum(theirs(*a) * cot), which)(*args)
    for a, b in zip(jax.tree_util.tree_leaves(gm),
                    jax.tree_util.tree_leaves(gt)):
        close(a, b, tol=tol)


def tokens(seed=0, seq=64):
    """(ids, next ids) of two rows inside the toys' 64-row vocabulary."""
    draw = np.random.RandomState(seed).randint(0, 64, (2, seq + 1))
    return draw[:, :-1].astype(np.int32), draw[:, 1:].astype(np.int32)


def batch_of(seed, seq=64):
    data, labels = tokens(seed, seq)
    return {"data": jnp.asarray(data), "label": jnp.asarray(labels)}


def seeded(solver, reference, seed=0, edit=None):
    """The reference's fillers into the program's solver; `edit(w0)`
    changes the draw before the solver takes it."""
    w0 = bench("weights").make_weights(reference.specs, seed)
    assert set(w0) == set(solver.params)
    if edit:
        edit(w0)
    for name, blobs in w0.items():
        assert [b.shape for b in blobs] == \
            [p.shape for p in solver.params[name]], name
        solver.params[name] = [jnp.array(b) for b in blobs]
    return w0


def grads_of(solver, batch):
    net = solver.net
    return jax.grad(lambda p: net.loss_fn(p, solver.state, batch)[0])(
        solver.params)


def qkv(key, b=1, h=4, hk=2, s=128, d=16):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, h, s, d)),
            jax.random.normal(ks[1], (b, hk, s, d)),
            jax.random.normal(ks[2], (b, hk, s, d)))


def exact_index(key, b, hi, s, di):
    """(qI, kI, w) whose index scores are exact in float32: small integers
    and weights that are multiples of 1/64, so that no key at a threshold
    falls one way in a kernel and the other in the plain form."""
    ks = jax.random.split(key, 3)
    return (jnp.round(2 * jax.random.normal(ks[0], (b, hi, s, di))),
            jnp.round(2 * jax.random.normal(ks[1], (b, s, di))),
            jnp.round(8 * jax.random.normal(ks[2], (b, hi, s))) / 64)


# ------------------------------------------------------------- the families

@dataclasses.dataclass(frozen=True)
class Family:
    reference: str              # benchmark/reference/<reference>.py
    builder: object             # its builder in models/zoo.py
    toy: dict                   # the reference's sizes (`dims`) at the toy
    experts: str                # `toy`'s key that counts the experts HELD
    config: object              # (**builder_args) -> a configuration file
    over: dict = dataclasses.field(default_factory=dict)    # net only
    adapt: object = None        # the toy's keys into the builder's, in place
    # three Adam steps: the loss, the first gradient, the steps' change
    # (relative, and what is added to its bound)
    step_tol: tuple = (2e-5, 2e-3, 0.0)
    knob_tol: float = 1e-4      # remat and scan against the plain gradients
    runs: tuple = ()            # the scan runs of the toy: fields to match

    def ref(self):
        return bench(f"reference.{self.reference}")

    def net(self, **over):
        """The toy net: two rows, the router at its `router_outputs`, the
        toy's count of experts held."""
        d = {**self.toy, **self.over, **over}
        held = d.pop(self.experts)
        d.update({self.experts: d.pop("router_outputs"),
                  "experts_held": held})
        if self.adapt:
            self.adapt(d)
        return self.builder(batch_size=2, **d)

    def solver(self, over=None, display=0, **solver_args):
        sp = Message("SolverParameter", display=display, random_seed=0,
                     **SOLVER)
        return Solver(sp, net_param=self.net(**(over or {})), log_fn=None,
                      **solver_args)


def _config(toy, drop, **keys):
    drop = set(drop) | {"router_outputs", "first_expert", "seq_len"}
    return dict({k: v for k, v in toy.items() if k not in drop}, **keys)


_QWEN3_NEXT = dict(
    hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_experts=8, num_experts_per_tok=4,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    norm_topk_prob=True, vocab_size=64, router_outputs=32, first_expert=0,
    seq_len=64)

#: one period: the three DeltaNet blocks are a run, the fourth is not
QWEN3_NEXT = Family(
    "qwen3_next", zoo.qwen3_next, _QWEN3_NEXT, "num_experts",
    lambda **args: _config(_QWEN3_NEXT, (), published={"num_experts": 32},
                           builder_args=dict({"seq_len": 64}, **args)),
    over=dict(flash=False), knob_tol=1e-3, runs=(dict(n=3, glen=6),))

_SMALLTHINKER = dict(
    hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1.5e6,
    rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], sliding_window_size=24,
    moe_num_primary_experts=4, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=16, norm_topk_prob=True, vocab_size=64,
    router_outputs=16, first_expert=0, seq_len=64)

#: one period: the global block is a body of its own (same shapes, other
#: settings), the three window blocks are a run; each block's MoE reads two
#: blobs of its own block, from different depths
SMALLTHINKER = Family(
    "smallthinker", zoo.smallthinker, _SMALLTHINKER,
    "moe_num_primary_experts",
    lambda **args: _config(
        _SMALLTHINKER, (), published={"moe_num_primary_experts": 16},
        builder_args=dict({"seq_len": 64}, **args)),
    runs=(dict(n=3, glen=6, entry="block0/res2"),))

_LFM2_MOE = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=1e6, norm_eps=1e-5, conv_L_cache=3, num_experts=4,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1.0,
    use_expert_bias=True, vocab_size=64, router_outputs=16, first_expert=0,
    seq_len=64,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_dense_layers=1, num_hidden_layers=5)

#: a configuration file's keys at the toy's sizes: the published list of 7
#: layer types and 2 leading dense layers, of which layers 0 and 2-5 are
#: held. The dense block and the attention block are bodies of their own;
#: the three conv blocks with a MoE are one run
LFM2_MOE = Family(
    "lfm2_moe", zoo.lfm2_moe, _LFM2_MOE, "num_experts",
    lambda **args: _config(
        _LFM2_MOE, ("layer_types", "num_dense_layers", "num_hidden_layers"),
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "conv", "full_attention"],
        num_dense_layers=2, layers_held=[0, 2, 3, 4, 5],
        published={"num_experts": 16},
        builder_args=dict({"seq_len": 64}, **args)),
    step_tol=(2e-5, 2e-3, 1e-12),
    runs=(dict(n=3, glen=6, entry="block1/res2"),))

_KEYE_VL2 = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rope_theta=1e7, rms_norm_eps=1e-6, indexer_num_heads=4,
    indexer_head_dim=8, indexer_topk=16, num_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=16, norm_topk_prob=True,
    vocab_size=64, num_hidden_layers=4, router_outputs=16, first_expert=0,
    seq_len=64)

KEYE_VL2 = Family(
    "keye_vl2", zoo.keye_vl2, _KEYE_VL2, "num_experts",
    lambda **args: _config(
        _KEYE_VL2, ("indexer_num_heads", "indexer_head_dim", "indexer_topk"),
        sa_config={"indexer_num_heads": 4, "indexer_head_dim": 8,
                   "indexer_num_kv_heads": 1, "topk": 16},
        published={"num_experts": 16},
        builder_args=dict({"seq_len": 64}, **args)),
    step_tol=(5e-5, 5e-3, 1e-12), runs=(dict(n=4),))

_NEMOTRON_H = dict(
    hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
    n_groups=2, conv_kernel=4, chunk_size=16, time_step_min=0.001,
    time_step_max=0.1, num_attention_heads=16, num_key_value_heads=1,
    head_dim=8, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    norm_topk_prob=True, routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
    vocab_size=64, router_outputs=16, first_expert=0, seq_len=48,
    pattern="ME*E", whole_pattern="ME*E", carry=True)


def _nemotron_args(d):
    d.pop("carry")
    d.update(pattern=d.pop("whole_pattern"),
             layers=(0, len(d.pop("pattern"))))


#: unlike neighbours unroll: no run
NEMOTRON_H = Family(
    "nemotron_h", zoo.nemotron_h, _NEMOTRON_H, "n_routed_experts",
    lambda **args: _config(
        _NEMOTRON_H, ("pattern", "whole_pattern", "carry"),
        hybrid_override_pattern="ME*E",
        published={"n_routed_experts": 16,
                   "hybrid_override_pattern": "ME*E"},
        builder_args=dict({"seq_len": 48}, **args)),
    adapt=_nemotron_args, step_tol=(5e-5, 5e-3, 1e-12))


_GLM4_MOE_LITE = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=5, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-5,
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=1.8,
    num_nextn_predict_layers=0, vocab_size=64, router_outputs=16,
    first_expert=0, seq_len=64)

#: the leading dense block is a body of its own, the four MoE blocks one
#: run; a prediction module's block (`num_nextn_predict_layers` 1) stands
#: after the loss and joins no run
GLM4_MOE_LITE = Family(
    "glm4_moe_lite", zoo.glm4_moe_lite, _GLM4_MOE_LITE, "n_routed_experts",
    lambda **args: _config(
        _GLM4_MOE_LITE, (), published={"n_routed_experts": 16},
        builder_args=dict({"seq_len": 64}, **args)),
    over=dict(flash=False), step_tol=(5e-5, 5e-3, 1e-12),
    runs=(dict(n=4, glen=6, entry="block0/res2"),))


_LAGUNA = dict(
    hidden_size=32, intermediate_size=48, num_hidden_layers=5,
    num_key_value_heads=1, head_dim=16,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[6, 8, 8, 8, 6],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    sliding_window=24,
    rope_parameters={
        "full_attention": dict(
            rope_theta=10000, rope_type="yarn", factor=4,
            original_max_position_embeddings=64, beta_slow=1, beta_fast=4,
            attention_factor=1.1386294361119891, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=100,
                                  partial_rotary_factor=1)},
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    moe_routed_scaling_factor=2.5, vocab_size=64, router_outputs=16,
    first_expert=0, seq_len=64)

#: the leading dense block with full attention is a body of its own, the
#: three window blocks one run, the full block after them a body again; a
#: full layer has 6 query heads on the one key-value head and a window
#: layer 8, as the published 48 and 64 on 8
LAGUNA = Family(
    "laguna", zoo.laguna, _LAGUNA, "num_experts",
    lambda **args: _config(
        _LAGUNA, (), published={"num_experts": 16},
        builder_args=dict({"seq_len": 64}, **args)),
    step_tol=(5e-5, 5e-3, 1e-12),
    runs=(dict(n=3, glen=6, entry="block0/res2"),))


# ------------------------------------------ the bodies of the shared tests

def three_adam_steps(fam, over=None, config=None, edit=None, **solver_args):
    """Three steps of the program's solver on the toy net beside the
    reference's own Adam from the same seeded weights: the multipliers blob
    for blob, every loss, the first gradient (out of Adam's first moment)
    and the three steps' change. Returns (solver, w0)."""
    reference = fam.ref().build(config or fam.config(), 2)
    solver = fam.solver(over, **solver_args)
    for name, blobs in reference.specs:
        assert solver.updater.mults[name] == [b[2] for b in blobs], name
    w0 = seeded(solver, reference, edit=edit)
    step = reference.make_step(SOLVER, block_rows=1)
    data, labels = tokens(0, reference.seq)
    loss_tol, grad_tol, slack = fam.step_tol
    params, history = w0, None
    for i in range(3):
        got = float(solver.train_step({"data": data, "label": labels}))
        params, history, want, grads = step(params, history, data, labels,
                                            None)
        assert abs(got - float(want)) <= loss_tol * abs(float(want)), i
        if i == 0:
            for name, blobs in grads.items():
                for j, g in enumerate(blobs):
                    decay = dict(reference.specs)[name][j][2][1]
                    m1 = solver.history[name][j][0]
                    close(m1 / 0.1 - 0.1 * decay * w0[name][j], g,
                          tol=grad_tol)
    # Adam divides by the root of its second moment: an element whose tiny
    # gradient differs in the last bits moves a visible part of a step, so
    # the three steps' change is compared blob by blob in the norm
    for name, blobs in params.items():
        for j, w in enumerate(blobs):
            got = np.asarray(solver.params[name][j] - w0[name][j])
            want = np.asarray(w - w0[name][j])
            assert np.linalg.norm(got - want) <= \
                0.05 * np.linalg.norm(want) + slack, (name, j)
    return solver, w0


_PLAIN = {}


def remat_and_scan(fam, remat, scan, over=None, config=None, edit=None,
                   runs=None):
    """The gradients under a remat policy and a scan setting are the
    unknobbed solver's (no remat, scan off), which are computed once a
    family and form (`over`) and kept."""
    key = (fam.reference, repr(sorted((over or {}).items())))
    if key not in _PLAIN:
        reference = fam.ref().build(config or fam.config(), 2)
        plain = fam.solver(over)
        plain.set_scan("off")
        seeded(plain, reference, edit=edit)
        batch = batch_of(1, reference.seq)
        _PLAIN[key] = reference, batch, grads_of(plain, batch)
    reference, batch, want = _PLAIN[key]
    knobbed = fam.solver(over, remat=remat)
    assert knobbed.net.remat == remat
    knobbed.set_scan(scan)
    runs = fam.runs if runs is None else runs
    found = knobbed.net._scan_runs()
    assert [{k: r[k] for k in w} for r, w in zip(found, runs)] == \
        list(runs) and len(found) == len(runs), found
    seeded(knobbed, reference, edit=edit)
    got = grads_of(knobbed, batch)
    for name in want:
        for a, b in zip(got[name], want[name]):
            close(a, b, tol=fam.knob_tol)


def held_share(fam, impl, shapes, dims, seed, tol=5e-4):
    """A MoE layer that holds some of the experts against the reference's
    `moe` given the same: the output, and the gradients of the inputs (of
    `shapes`) and of every blob. Returns what it drew: (inputs, blobs,
    cotangent)."""
    blobs = fill(impl, jax.random.PRNGKey(seed))
    xs = [jax.random.normal(jax.random.PRNGKey(seed + 1 + i), s)
          for i, s in enumerate(shapes)]
    cot = jax.random.normal(jax.random.PRNGKey(seed + 1 + len(xs)),
                            shapes[0])
    rows = (-1, shapes[0][-1])

    def mine(*args):
        return impl.apply(args[-1], list(args[:-1]), True, None)[0]

    def theirs(*args):
        return fam.ref().moe(*[x.reshape(rows) for x in args[:-1]],
                             args[-1], dims).reshape(shapes[0])
    same_value_and_grads(mine, theirs, (*xs, blobs), cot, tol)
    return xs, blobs, cot


def out_and_input_grads(impl, blobs, xs, cot=None):
    """(output, the inputs' gradients under `cot`); the output alone
    without one."""
    def f(*xs):
        return impl.apply(blobs, list(xs), True, None)[0]
    if cot is None:
        return (f(*xs),)
    out, vjp = jax.vjp(f, *xs)
    return (out,) + vjp(cot)


def sum_of_shares(build, chips, per, blobs, xs, cot=None, routed=3):
    """What `chips` shares of `per` experts each add up to, as
    `out_and_input_grads` gives it: every chip has the router (blob 0)
    whole, its own slice of the `routed` expert blobs after it, and the
    blobs after those (a bias, a shared expert) as they are."""
    total = None
    for chip in range(chips):
        lo = per * chip
        part = out_and_input_grads(
            build(per, lo), [blobs[0]] + [w[lo:lo + per]
                                          for w in blobs[1:1 + routed]]
            + list(blobs[1 + routed:]), xs, cot)
        total = part if total is None else tuple(
            a + b for a, b in zip(total, part))
    return total


def traced_steps(fam, steps, over=None, **solver_args):
    """`steps` steps of a solver that shows every loss, with a tracer of
    its own. Returns (the tracer, since): `since(kind)` are the records of
    that kind which the process's ring took meanwhile — this test's alone,
    whatever the worker ran before."""
    tracer, ring = Tracer(), default_tracer()
    mark = ring.mark()
    solver = fam.solver(over, display=1, tracer=tracer, **solver_args)
    data, labels = tokens(2, (over or {}).get("seq_len",
                                              fam.toy["seq_len"]))
    solver.step(steps, iter([{"data": data, "label": labels}] * steps))
    return tracer, lambda kind: ring.since(mark, kind)


def held_loads(tracer, layers):
    """The `moe.load` records: of these layers, a held share inside (0, 1)
    and a window or more."""
    loads = tracer.spans("moe.load")
    assert {r["layer"] for r in loads} == set(layers)
    for r in loads:
        assert 0.0 < r["held_share"] < 1.0 and r["windows"] >= 1.0
    return loads


def stack_contract(net):
    """`models/zoo.py:_lm_stack`'s naming contract on a built net: the
    layers of a block lie together under one "block{i}/" prefix, a block
    reads one blob from outside itself, the boundary of the block before,
    and one of its tops is read outside it. A prediction module's block
    ("block_mtp{k}/", after the loss) reads the top of the layer just
    before it instead, which nothing else reads."""
    blocks, order = {}, []
    for lp in net.layer:
        if "/" in lp.name:
            p = lp.name.split("/")[0]
            if p not in blocks:
                order.append(p)
            assert order[-1] == p, f"{lp.name} lies apart from its block"
            blocks.setdefault(p, []).append(lp)
    assert order and all(p.startswith("block") for p in order), order
    first = next(i for i, lp in enumerate(net.layer) if "/" in lp.name)
    boundary = net.layer[first - 1].top[0]
    names = [lp.name for lp in net.layer]
    for p in order:
        tops = {t for lp in blocks[p] for t in lp.top}
        outside = {b for lp in blocks[p] for b in lp.bottom} - tops
        if p.startswith("block_mtp"):
            (boundary,) = net.layer[names.index(blocks[p][0].name) - 1].top
            assert [lp.name for lp in net.layer if boundary in lp.bottom
                    and not lp.name.startswith(p + "/")] == [], p
        assert outside == {boundary}, (p, outside)
        read = {b for lp in net.layer if not lp.name.startswith(p + "/")
                for b in lp.bottom} & tops
        assert len(read) == 1, (p, read)
        (boundary,) = read
    return order


def layout(net):
    """The layers of a built net by name, the stack's contract and the
    prototxt round trip (which keeps the extensions' fields) checked."""
    stack_contract(net)
    assert text_format.loads(text_format.dumps(net), "NetParameter") == net
    return {lp.name: lp for lp in net.layer}
