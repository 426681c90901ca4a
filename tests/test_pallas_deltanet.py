"""The gated delta rule's kernel pair (ops/pallas_deltanet.py) in interpret
mode at head size 128, against the XLA form's `_group_rule` and against
the plain reference's token-by-token recurrence; which form a layer takes;
and the guard on what a process that runs no such layer imports.
"""

import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import dsl
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.ops import deltanet
from sparknet_tpu.ops import pallas_deltanet as pd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
B, HK, R, D = 2, 2, 2, 128      # 2 value heads to a key head, heads of 128


@pytest.fixture(scope="module")
def ref():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("reference.qwen3_next")


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.isfinite(a).all()
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def delta_inputs(t, g_scale, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, t, HK, D))
    k = jax.random.normal(ks[1], (B, t, HK, D))
    v = jax.random.normal(ks[2], (B, t, HK * R, D))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, t, HK * R)))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[4], (B, t, HK * R)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), beta, g


def kernels(split):
    """The kernel pair over the whole sequence, or over its first `split`
    tokens and then, from the state those leave (not zero), the rest."""
    def run(q, k, v, beta, g):
        if not split:
            return pd.chunk_rule(q, k, v, beta, g)[0]
        args = (q, k, v, beta, g)
        head, state = pd.chunk_rule(*[a[:, :split] for a in args])
        tail, _ = pd.chunk_rule(*[a[:, split:] for a in args], state=state)
        return jnp.concatenate([head, tail], axis=1)
    return run


def prepared(q, k):
    """What the kernels do to raw q and k inside, and the repeat to the
    value heads that their index maps stand for."""
    return (jnp.repeat(deltanet.l2_normalize(q), R, axis=2) * D ** -0.5,
            jnp.repeat(deltanet.l2_normalize(k), R, axis=2))


def group_rule(q, k, v, beta, g):
    t = q.shape[1]
    pad = -t % 64
    q, k = prepared(q, k)
    q, k, v, beta, g = [
        jnp.pad(a.astype(jnp.float32),
                [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        for a in (q, k, v, beta, g)]
    s0 = jnp.zeros((B, HK * R, D, D), jnp.float32)
    return deltanet._group_rule(s0, q, k, v, beta, g, 64)[1][:, :t]


def token_recurrence(ref):
    def run(q, k, v, beta, g):
        q, k = prepared(q, k)
        return jnp.stack([ref.delta_rule(*[a[i].astype(jnp.float32)
                                           for a in (q, k, v, beta, g)])
                          for i in range(B)])
    return run


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t,g_scale,split", [
    (64, 1.0, 0), (128, 1.0, 0), (192, 1.0, 64), (1024 + 17, 1.0, 0),
    (128, 40.0, 0), (192, 40.0, 128), (1024 + 17, 40.0, 512)])
def test_kernel_pair_matches_group_rule_and_token_recurrence(
        ref, t, g_scale, split, dtype):
    args = delta_inputs(t, g_scale, dtype)
    cot = jax.random.normal(jax.random.PRNGKey(9), (B, t, HK * R, D))
    mine = kernels(split)
    # a bfloat16 input's gradient comes back rounded to bfloat16
    grad_tol = [2e-5, 5e-4] if dtype == jnp.float32 else [8e-3, 8e-3]
    got = mine(*args)
    assert got.dtype == jnp.float32
    gm = jax.grad(lambda *a: jnp.sum(cot * mine(*a)), range(5))(*args)
    for theirs, tol, gtol in zip((group_rule, token_recurrence(ref)),
                                 (2e-5, 5e-4), grad_tol):
        close(got, theirs(*args), tol)
        gt = jax.grad(lambda *a: jnp.sum(cot * theirs(*a)), range(5))(*args)
        for a, b, name in zip(gm, gt, "q k v beta g".split()):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            close(a, b, gtol if name in "qkv" else max(tol, 2e-5))


@pytest.mark.parametrize("heads", [1, 2, 4])
def test_side_by_side_inverses_are_the_inverses(heads):
    """`_unit_lower_inverses` outside any kernel: `heads` strictly lower
    64 x 64 matrices side by side, entries of the size beta k.k has."""
    a = jnp.tril(0.5 * jax.random.normal(jax.random.PRNGKey(heads),
                                         (heads, 64, 64)), -1)
    side_by_side = jnp.concatenate(list(a), axis=1)
    got = pd._unit_lower_inverses(side_by_side)
    assert got.shape == (64, heads * 64)
    for h in range(heads):
        want = np.linalg.inv(np.eye(64) + np.asarray(a[h], np.float64))
        close(got[:, h * 64:(h + 1) * 64], want, 2e-5)
        close(got[:, h * 64:(h + 1) * 64],
              deltanet.unit_lower_inverse(a[h]), 2e-5)


def gdn_paths():
    return [(s["layer"], s["path"], s["reason"])
            for s in default_tracer().spans("gdn.path")]


@pytest.mark.parametrize("heads,chunk,path,reason", [
    ((8, 16), None, "xla", "head sizes 8 and 16 are not multiples of the "
                           "lane width 128"),
    ((128, 128), 32, "xla", "chunk 32 is not 64"),
    ((128, 128), None, "kernel", "head sizes and chunk fit")])
def test_layer_takes_the_form_its_shapes_allow_and_records_it(
        heads, chunk, path, reason):
    name = f"mixer_{heads[0]}_{chunk}"
    lp = dsl.GatedDeltaNetLayer(name, ["x"], 1, 2, *heads, conv_kernel=4,
                                chunk=chunk)
    impl = get_layer(lp.type)(lp, [(1, 64, 32)], 0)
    blobs = [jax.ShapeDtypeStruct(s[0], jnp.float32)
             for s in impl.param_shapes()]
    before = len(gdn_paths())
    text = str(jax.make_jaxpr(
        lambda p, x: impl.apply(p, [x], True, None)[0])(
        blobs, jax.ShapeDtypeStruct((1, 64, 32), jnp.float32)))
    assert gdn_paths()[before:] == [(name, path, reason)]
    # the kernel in place of the two nested scans, or the scans
    assert ("gdn_chunk_fwd" in text) == (path == "kernel")
    assert (" scan[" in text) == (path == "xla")


_GUARD = """
import sys
import numpy as np
import jax
import sparknet_tpu, sparknet_tpu.ops, sparknet_tpu.solver.solver
from sparknet_tpu.models import zoo
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver

def pallas():
    return sorted(m for m in sys.modules
                  if m.startswith("jax.experimental.pallas")
                  or m.startswith("jax._src.pallas"))

solver = Solver(Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                        display=0),
                net_param=zoo.caffenet(batch_size=2, num_classes=10))
batch = {"data": np.zeros((2, 3, 227, 227), np.float32),
         "label": np.zeros((2,), np.int32)}
assert len(solver.op_scopes(batch)) > 50        # one step, traced and compiled
print("CNN", pallas())
# the name the remat policies keep is the compiler's too (graph/remat.py)
print("NAME", "sparknet_tpu.graph.remat" in sys.modules)

from sparknet_tpu.graph.registry import get
from sparknet_tpu.models import dsl
lp = dsl.MoELayer("moe", ["x"], 8, hidden_dim=128, top_k=2, experts_held=4,
                  first_expert=0, tile_rows=8)
impl = get(lp.type)(lp, [(1, 16, 128)], 0)
blobs = [jax.ShapeDtypeStruct(s[0], "float32") for s in impl.param_shapes()]
jax.eval_shape(lambda p, x: impl.apply(p, [x], True, None)[0], blobs,
               jax.ShapeDtypeStruct((1, 16, 128), "float32"))
print("MOE", pallas())

def mamba2(heads, head_dim, state, groups, chunk):
    lp = dsl.Mamba2Layer("ssm", ["x"], heads, head_dim, state, groups,
                         conv_kernel=4, chunk=chunk)
    impl = get(lp.type)(lp, [(1, 256, 32)], 0)
    blobs = [jax.ShapeDtypeStruct(s[0], "float32")
             for s in impl.param_shapes()]
    jax.eval_shape(lambda p, x: impl.apply(p, [x], True, None)[0], blobs,
                   jax.ShapeDtypeStruct((1, 256, 32), "float32"))

mamba2(8, 8, 16, 2, 16)
print("SSM_TOY", pallas())

lp = dsl.GatedDeltaNetLayer("mixer", ["x"], 1, 2, 128, 128)
impl = get(lp.type)(lp, [(1, 64, 32)], 0)
blobs = [jax.ShapeDtypeStruct(s[0], "float32") for s in impl.param_shapes()]
jax.eval_shape(lambda p, x: impl.apply(p, [x], True, None)[0], blobs,
               jax.ShapeDtypeStruct((1, 64, 32), "float32"))
print("GDN", pallas())
mamba2(8, 64, 128, 1, 128)
print("SSM", "sparknet_tpu.ops.pallas_ssd" in sys.modules)
"""


def test_a_process_that_steps_caffenet_never_imports_pallas():
    """What this guards: 1.4 s of `setup_s` in four cells. PR 29 brought
    the kernels of this file's subject with a gain of 46% in the LM cell
    and was refused because every process, CaffeNet's too, paid the import
    of `jax.experimental.pallas` (0.9 s here, 1.4 s on the chip tool's
    host; `setup_s` 9.52 -> 10.95 s against a bound of 0.95). A kernel
    module is imported in the branch that calls it (ops/lrn.py,
    ops/attention.py, graph/compiler.py, ops/deltanet.py): importing the
    package, the solver and the zoo, building CaffeNet and tracing and
    compiling one step of it leaves pallas out of `sys.modules`, and so
    does tracing the no-drop MoE where its grouped product is XLA's (off
    the TPU, ops/pallas_moe.py is never imported); tracing a GatedDeltaNet
    at head size 128 brings it in, and so does a Mamba2 at the published
    heads (8 of 64 a group, state and chunks of 128: `ops/pallas_ssd.py`),
    where one at toy heads leaves all of pallas out. The module that holds the name a remat policy keeps (graph/remat.py) is imported by
    the compiler, so by the CNN process too, and is core jax alone."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", _GUARD], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = dict(ln.split(" ", 1) for ln in res.stdout.splitlines()
                 if ln.startswith(("CNN ", "NAME ", "MOE ", "GDN ", "SSM_TOY ",
                                  "SSM ")))
    assert lines["CNN"] == "[]", lines["CNN"]
    assert lines["NAME"] == "True"
    assert lines["MOE"] == "[]", lines["MOE"]
    assert "jax.experimental.pallas" in lines["GDN"]
    assert "jax.experimental.pallas.tpu" in lines["GDN"]
    assert lines["SSM_TOY"] == "[]", lines["SSM_TOY"]
    assert lines["SSM"] == "True"
