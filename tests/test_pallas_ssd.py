"""The Mamba-2 scan's kernel pair (ops/pallas_ssd.py) in interpret mode at
state and chunk 128, against XLA's chunked form (`mamba2.ssd_chunked`) and
against the plain reference's token-by-token recurrence; the carry through
the kernels; which form a layer takes and what a remat policy keeps of it.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparknet_tpu.ops  # noqa: F401  (registers the layers)
from sparknet_tpu.graph import compiler
from sparknet_tpu.graph.registry import get as get_layer
from sparknet_tpu.models import dsl
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.ops import mamba2 as m2
from test_nemotron_h import mamba_blobs

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
N = Q = 128


@pytest.fixture(scope="module")
def ref():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("reference.nemotron_h")


@pytest.fixture(scope="module")
def ps():
    # here and not at the top: collecting this file imports no pallas
    return importlib.import_module("sparknet_tpu.ops.pallas_ssd")


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.isfinite(a).all(), what
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, \
        (what, np.abs(a - b).max(), scale)


def scan_inputs(bsz, s, g, r, p, dtype, seed=0):
    """x, delta (log-uniform in [1e-3, 1e-1], the layer's fill), A in
    (-16, -1], B and C: a slow head keeps most of a state over a chunk, a
    fast one loses it within a few tokens."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = g * r
    return (jax.random.normal(ks[0], (bsz, s, h, p)).astype(dtype),
            jnp.exp(jax.random.uniform(ks[1], (bsz, s, h),
                                       minval=np.log(1e-3),
                                       maxval=np.log(1e-1))),
            -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=np.log(16.0))),
            jax.random.normal(ks[3], (bsz, s, g, N)).astype(dtype),
            jax.random.normal(ks[4], (bsz, s, g, N)).astype(dtype))


def token_recurrence(ref):
    """y and the state after the last token, token by token in float32."""
    def run(x, dt, a, b, c):
        x, b, c = [v.astype(jnp.float32) for v in (x, b, c)]
        y = jnp.stack([ref.recurrence(
            jnp.exp(dt[i] * a), dt[i][..., None] * x[i], b[i], c[i], Q)
            for i in range(x.shape[0])])
        r = x.shape[2] // b.shape[2]

        def token(state, inp):
            dt_t, x_t, b_t = inp
            return (jnp.exp(dt_t * a)[..., None, None] * state
                    + (dt_t[..., None] * x_t)[..., None]
                    * jnp.repeat(b_t, r, axis=1)[:, :, None, :]), None
        last, _ = jax.lax.scan(
            token, jnp.zeros(x.shape[:1] + x.shape[2:] + (N,)),
            tuple(jnp.moveaxis(v, 1, 0) for v in (dt, x, b)))
        return y, last
    return run


# (batch, tokens, groups, heads a group, head size)
SHAPES = {"one_group_r8_p64": (1, 256, 1, 8, 64),
          "two_groups_r1_p128_ragged": (2, 128 + 37, 2, 1, 128),
          "three_groups_r8_p16_ragged": (1, 300, 3, 8, 16),
          "two_groups_r2_p64_rows2": (2, 256, 2, 2, 64)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_pair_matches_xla_form_and_token_recurrence(ref, ps, shape,
                                                           dtype):
    """y, the last state and the gradients of x, delta, A, B and C: one
    group and several, one head a group and eight, lengths that are no
    whole number of chunks (the padded tail moves no state), a batch of
    more than one row, float32 and bfloat16 in."""
    args = scan_inputs(*SHAPES[shape], dtype)
    bsz, s, g, r, p = SHAPES[shape]
    cot = jax.random.normal(jax.random.PRNGKey(9), (bsz, s, g * r, p))
    cot_last = jax.random.normal(jax.random.PRNGKey(10),
                                 (bsz, g * r, p, N))

    def grads(scan):
        def loss(*v):
            y, last = scan(*v)[:2]
            return jnp.sum(cot * y) + jnp.sum(cot_last * last)
        return jax.jit(jax.grad(loss, range(5)))(*args)
    y, last, survive = jax.jit(ps.chunk_scan)(*args)
    assert y.dtype == last.dtype == jnp.float32
    mine = grads(ps.chunk_scan)
    # a bfloat16 input's gradient comes back rounded to bfloat16; XLA's
    # form rounds the cotangent of every bfloat16 operand besides, the
    # kernels keep those float32
    tols = {jnp.float32: ((2e-5, 5e-5), (5e-4, 1e-3)),
            jnp.bfloat16: ((2e-5, 2e-2), (1e-2, 2e-2))}[dtype]
    forms = ((lambda *v: m2.ssd_chunked(*v, Q)), token_recurrence(ref))
    for theirs, (tol, gtol), name in zip(forms, tols, ("xla", "tokens")):
        want = jax.jit(theirs)(*args)
        close(y, want[0], tol, f"{name} y")
        close(last, want[1], tol, f"{name} last")
        if name == "xla":
            close(survive, want[2], 1e-6, "survive")
        for a, b, what in zip(mine, grads(theirs), "x delta A B C".split()):
            assert a.dtype == b.dtype and a.shape == b.shape, what
            assert float(jnp.max(jnp.abs(b.astype(jnp.float32)))) > 0, what
            close(a, b, gtol, f"{name} d{what}")


def test_the_carry_matters_through_the_kernels(ps):
    """The second chunk's output differs from a scan restarted there, and
    agrees with it once the first chunk's state dies at the second's first
    token; so does its gradient reach the first chunk's tokens."""
    x, dt, a, b, c = scan_inputs(1, 2 * Q, 1, 2, 64, jnp.float32, seed=3)
    whole, _, survive = ps.chunk_scan(x, dt, a, b, c)
    restarted = ps.chunk_scan(x[:, Q:], dt[:, Q:], a, b[:, Q:], c[:, Q:])[0]
    gap = float(jnp.max(jnp.abs(whole[:, Q:] - restarted)))
    assert gap > 0.05 * float(jnp.max(jnp.abs(restarted)))
    assert 0.0 < float(survive) < 1.0
    dx = jax.grad(lambda x: jnp.sum(ps.chunk_scan(x, dt, a, b, c)[0][:, Q:]))(x)
    assert float(jnp.max(jnp.abs(dx[:, :Q]))) > 0.0
    dead = dt.at[:, Q].set(50.0)
    close(ps.chunk_scan(x, dead, a, b, c)[0][:, Q:],
          ps.chunk_scan(x[:, Q:], dead[:, Q:], a, b[:, Q:], c[:, Q:])[0],
          1e-6)
    dx = jax.grad(lambda x: jnp.sum(ps.chunk_scan(x, dead, a, b, c)[0][:, Q:]))(x)
    assert float(jnp.max(jnp.abs(dx[:, :Q]))) < 1e-12


def mixer(name, heads, head_dim, state, groups, chunk, seq=2 * Q, embed=32):
    lp = dsl.Mamba2Layer(name, ["x"], heads, head_dim, state, groups,
                         conv_kernel=4, chunk=chunk, norm_eps=1e-5)
    impl = get_layer(lp.type)(lp, [(1, seq, embed)], 0)
    blobs = [jax.ShapeDtypeStruct(s[0], jnp.float32)
             for s in impl.param_shapes()]
    return impl, blobs, jax.ShapeDtypeStruct((1, seq, embed), jnp.float32)


@pytest.mark.parametrize("shape,path,reason", [
    ((8, 64, 128, 1, 128), "kernel",
     "state, chunk and a group's heads fit the kernels' tiles"),
    ((2, 128, 128, 2, 128), "kernel",
     "state, chunk and a group's heads fit the kernels' tiles"),
    ((8, 8, 16, 2, 16), "chunked",
     "state 16 and chunk 16 are not both 128, the kernels' one tile"),
    ((8, 64, 128, 1, 64), "chunked",
     "state 128 and chunk 64 are not both 128, the kernels' one tile"),
    ((6, 64, 128, 2, 128), "chunked",
     "a group's 3 heads of 64 do not fill whole lane tiles of 128"),
    ((4, 96, 128, 1, 128), "chunked",
     "a group's 4 heads of 96 do not fill whole lane tiles of 128")])
def test_layer_takes_the_form_its_shapes_allow_and_records_it(shape, path,
                                                              reason):
    name = "mixer_" + "_".join(map(str, shape))
    impl, blobs, x = mixer(name, *shape)
    ring = default_tracer()
    mark = ring.mark()
    text = str(jax.make_jaxpr(
        lambda p, x: impl.apply(p, [x], True, None)[0])(blobs, x))
    (rec,) = ring.since(mark, "ssm.path")
    assert (rec["layer"], rec["path"], rec["reason"]) == (name, path, reason)
    assert (rec["heads"], rec["head_dim"], rec["state"], rec["groups"],
            rec["chunk"]) == shape
    # the kernel in place of the rows' loop, or the loop
    assert ("ssd_chunk_fwd" in text) == (path == "kernel")
    assert (" scan[" in text) == (path == "chunked")


def test_the_layer_through_the_kernels_is_the_references_mixer(ref):
    """A whole Mamba2 layer at heads the kernels take (4 of 64 over 2
    groups, state and chunks of 128, a length that is no whole number of
    chunks) against the reference's mixer, which computes the recurrence
    token by token: the output and the gradient of every blob and of x."""
    seq, embed = Q + 37, 32
    lp = dsl.Mamba2Layer("ssm", ["x"], 4, 64, N, 2, conv_kernel=4, chunk=Q,
                         norm_eps=1e-5)
    impl = get_layer(lp.type)(lp, [(2, seq, embed)], 0)
    assert impl._why_xla() is None
    d = dict(mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=N,
             n_groups=2, conv_kernel=4, chunk_size=Q, carry=True,
             layer_norm_epsilon=1e-5)
    blobs = mamba_blobs(impl, jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, embed))
    probe = jax.random.normal(jax.random.PRNGKey(2), (2, seq, embed))

    def mine(blobs, x):
        return impl.apply(blobs, [x], True, None)[0]

    def theirs(blobs, x):
        return jnp.stack([ref.mamba2(x[i], blobs, d) for i in range(2)])
    close(mine(blobs, x), theirs(blobs, x), 2e-4, "out")
    got, want = (jax.jit(jax.grad(lambda b, x: jnp.sum(f(b, x) * probe),
                                  (0, 1)))(blobs, x) for f in (mine, theirs))
    for i, (g, w) in enumerate(zip(got[0], want[0])):
        assert float(jnp.max(jnp.abs(w))) > 0, i
        close(g, w, 1e-3, f"blob {i}")
    close(got[1], want[1], 1e-3, "x")


@pytest.mark.parametrize("pol", ["full", "dots"])
def test_a_block_under_remat_keeps_the_scans_results_and_runs_it_once(pol):
    """The gradient's jaxpr of a checkpointed mixer holds ONE forward
    kernel and one backward (a bare `jax.checkpoint` runs the forward
    twice), and `remat.kept` names y, the last state and the chunks' first
    states with their bytes."""
    impl, blobs, x = mixer(f"blk_{pol}/mixer", 2, 64, 128, 1, 128)

    def block(x, *blobs):
        return impl.apply(list(blobs), [x], True, None)[0]

    def calls(checkpointed, kernel):
        step = jax.grad(lambda x, *p: jnp.sum(checkpointed(block)(x, *p)))
        return str(jax.make_jaxpr(step)(x, *blobs)).count(
            f"name={kernel}\n")
    ring = default_tracer()
    mark = ring.mark()
    mine = lambda fn: compiler._checkpointed(fn, pol)  # noqa: E731
    assert calls(mine, "ssd_chunk_fwd") == 1
    kept = {(r["layer"], r["array"]): (r["shape"], r["dtype"], r["bytes"])
            for r in ring.since(mark, "remat.kept")}
    assert kept == {
        (f"blk_{pol}/mixer", "y"): ((1, 2 * Q, 128), "float32", 4 * 2 * Q * N),
        (f"blk_{pol}/mixer", "last"): ((1, 1, N, 128), "float32", 4 * N * N),
        (f"blk_{pol}/mixer", "starts"): ((1, 1, 2, N, 128), "float32",
                                         4 * 2 * N * N)}
    assert calls(mine, "ssd_chunk_bwd") == 1
    assert calls(jax.checkpoint, "ssd_chunk_fwd") == 2
