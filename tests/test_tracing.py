"""The spans and names the program carries itself (obs/trace.py and its
callers): the ring, the profiler annotations, the solver step's records,
both sides of the prefetch queue, jax's compile events, and the layer
scopes and kernel names in the lowered step."""

import contextlib
import glob
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.data.prefetch import PrefetchIterator
from sparknet_tpu.models import zoo
from sparknet_tpu.obs.trace import Tracer, default_tracer, RING
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver


@contextlib.contextmanager
def time_limit(seconds):
    """Fail the test, not the worker, when a profiler or a thread hangs."""
    def on_alarm(signum, frame):
        raise TimeoutError(f"test exceeded its {seconds} s")
    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _solver(net, tracer=None, **sp):
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, momentum=0.9, random_seed=1, **sp)
    return Solver(sp, net_param=net, log_fn=None, tracer=tracer)


def _cifar_batch(n=4):
    rs = np.random.RandomState(0)
    return {"data": rs.randn(n, 3, 32, 32).astype(np.float32),
            "label": rs.randint(0, 10, n).astype(np.int32)}


# ------------------------------------------------------------------- ring

def test_ring_drops_oldest_and_counts():
    tr = Tracer(None, max_buffer=4)
    for i in range(7):
        with tr.hot_span("s", i=i):
            pass
    assert [s["i"] for s in tr.spans()] == [3, 4, 5, 6]
    assert tr.dropped == 3
    assert default_tracer().max_buffer == RING
    assert default_tracer() is default_tracer()


def test_record_takes_the_open_span_as_parent_and_skips_the_sink():
    logged = []

    class Sink:
        def log(self, event, **rec):
            logged.append((event, rec["name"]))

    tr = Tracer(Sink())
    with tr.span("outer"):
        t0 = tr.now_ns()
        tr.record("inner", t0, t0 + 2_000_000, iter=7)
        with tr.hot_span("hot"):
            pass
    inner, hot, outer = tr.spans()
    assert inner["parent"] == hot["parent"] == "outer"
    assert inner["depth"] == 1 and inner["iter"] == 7
    assert inner["dur_ms"] == pytest.approx(2.0)
    assert logged == [("span", "outer")]    # the hot path stays off JSONL
    assert tr.spans("hot") == [hot]


# ----------------------------------------------------------- profiler clock

def test_span_stands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer(None)
    with time_limit(120):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tr.span("outer"):
                with tr.step("solver.step", 41, "solver.prep") as st:
                    st.phase("solver.enqueue")
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sparknet.") or ev.name == "train":
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert {"sparknet.outer", "sparknet.solver.step", "sparknet.solver.prep",
            "sparknet.solver.enqueue", "train"} <= set(found)
    assert found["train"][2]["step_num"] == 41
    o, e = found["sparknet.outer"], found["sparknet.solver.enqueue"]
    assert o[0] <= e[0] and e[0] + e[1] <= o[0] + o[1]  # one clock, nested
    assert e[1] >= 2e6                                   # ns: the sleep


# ------------------------------------------------------------- solver step

def test_train_steps_leave_step_records_with_prep_and_enqueue():
    tr = Tracer(None)
    s = _solver(zoo.cifar10_full(batch_size=4), tracer=tr)
    init = tr.spans("solver.init", "net.build", "net.init")
    assert [r["name"] for r in init] == ["net.build", "net.init",
                                         "solver.init"]
    assert init[0]["parent"] == init[1]["parent"] == "solver.init"
    batch = _cifar_batch()
    n = 4
    for _ in range(n):
        s.train_step(batch)
    steps = tr.spans("solver.step")
    assert [r["iter"] for r in steps] == list(range(n))
    for name in ("solver.prep", "solver.enqueue"):
        kids = tr.spans(name)
        assert [k["iter"] for k in kids] == list(range(n))
        assert all(k["parent"] == "solver.step" and k["depth"] == 1
                   for k in kids)
    for st, p, e in zip(steps, tr.spans("solver.prep"),
                        tr.spans("solver.enqueue")):
        assert st["start_ms"] <= p["start_ms"] <= e["start_ms"]
        assert p["dur_ms"] + e["dur_ms"] <= st["dur_ms"]
    # the step's program was built under the first enqueue, and named
    built = [c for c in tr.spans("compile.backend")
             if c["parent"] == "solver.enqueue"]
    assert built and all(c["fun_name"] == "jit(step)" for c in built)
    # Solver.step fetches the loss where it displays it
    s.param.display = 1
    s.step(2, iter([batch, batch]))
    assert [f["iter"] for f in tr.spans("solver.fetch")] == [n, n + 1]


def test_a_solver_without_a_tracer_records_into_the_default_one():
    before = default_tracer().mark()
    s = _solver(zoo.cifar10_full(batch_size=4))
    assert s.tracer is default_tracer()
    s.train_step(_cifar_batch())
    assert len(default_tracer().since(before, "solver.step")) == 1


def _toy_glm(**over):
    return zoo.glm4_moe_lite(
        vocab_size=64, seq_len=32, batch_size=2, hidden_size=32,
        intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=2, experts_held=4,
        flash=False, **over)


def test_a_latent_attention_says_its_form_and_what_the_shared_key_costs():
    """`attn.path` of a latent layer: the fields every attention writes,
    and `form`, the five sizes and `shared_key_bytes` — one pass's write of
    the ONE rotary key a token over the heads (2 x 32 tokens x 4 heads x 4
    dimensions in float32 here; 0 once a kernel reads it in place)."""
    mark = default_tracer().mark()
    s = _solver(_toy_glm(num_nextn_predict_layers=0), type="Adam")
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 64, (2, 33)).astype(np.int32)
    s.train_step({"data": ids[:, :-1], "label": ids[:, 1:]})
    recs = default_tracer().since(mark, "attn.path")
    assert {r["layer"] for r in recs} == {"block0/attn", "block1/attn"}
    for r in recs:
        assert (r["form"], r["q_rank"], r["kv_rank"], r["nope_dim"],
                r["rope_dim"], r["v_dim"]) == ("latent", 24, 16, 12, 4, 16)
        assert r["shared_key_bytes"] == 2 * 32 * 4 * 4 * 4
        assert (r["path"], r["head_dim"], r["window"]) == ("dense", 16, 0)
    # a net without the module says nothing of one
    assert default_tracer().since(mark, "lm.mtp") == []


def test_a_net_with_a_prediction_module_says_so_once_a_net():
    """`lm.mtp`, beside `net.parts` where the solver builds its net: how
    many depths, their loss weight and losses, and the blobs the module
    shares with the main model."""
    tr = Tracer(None)
    _solver(_toy_glm(), tracer=tr, type="Adam")
    (rec,) = tr.spans("lm.mtp")
    assert (rec["net"], rec["depth"], rec["losses"]) == \
        ("GLM4MoELite", 1, ["mtp1_loss"])
    assert rec["loss_weight"] == pytest.approx(0.3)
    assert rec["shared"] == ["lm_head_table", "tok_embed_table"]
    parts = tr.spans("net.parts")[-1]["parts"]
    assert (parts["mtp1_lm_head"], parts["mtp1_ln_f"], parts["mtp1_proj"],
            parts["mtp1_embed"], parts["mtp1_label"], parts["mtp1_cat"]) == \
        ("head", "final_norm", "proj", "embed", "shape", "shape")
    assert parts["block_mtp1/attn"] == "attn"


@pytest.mark.parametrize("filled", [0, 5, 8, 20])
def test_records_since_a_mark_survive_the_rings_wrap(filled):
    """A count of `spans(name)` stops meaning "new since then" once the
    bounded ring is full and drops from the left (what failed
    test_lfm2_moe.py::test_paths_and_load_are_recorded in a whole run: the
    files before it on its worker had filled the default ring); `mark` /
    `since` count every record ever put."""
    from sparknet_tpu.obs.trace import Tracer
    tr = Tracer(max_buffer=8)
    for i in range(filled):
        tr.record("x.path", 0, 0, i=i)
    count, mark = len(tr.spans("x.path")), tr.mark()
    for i in range(3):
        tr.record("x.path", 0, 0, i=100 + i)
        tr.record("other", 0, 0)
    assert [r["i"] for r in tr.since(mark, "x.path")] == [100, 101, 102]
    assert len(tr.since(mark)) == 6
    if filled >= 8:     # the old reading: nothing new, or the wrong records
        assert [r["i"] for r in tr.spans("x.path")[count:]] != [100, 101, 102]
    # a mark that the ring has since run past gives what is left
    for i in range(10):
        tr.record("x.path", 0, 0, i=200 + i)
    assert [r["i"] for r in tr.since(mark, "x.path")] == list(range(202, 210))


@pytest.mark.parametrize("cls", ["DataParallelSolver", "LocalSGDSolver"])
def test_mesh_solvers_step_through_the_same_helper(cls):
    from sparknet_tpu import parallel
    tr = Tracer(None)
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, random_seed=1)
    # the net's batch is global for data parallelism, per worker (one of
    # the conftest's eight devices) for local SGD
    local = cls == "LocalSGDSolver"
    s = getattr(parallel, cls)(
        sp, net_param=zoo.cifar10_full(batch_size=1 if local else 8),
        log_fn=None, tracer=tr, **({"tau": 2} if local else {}))
    b = _cifar_batch(8)
    if local:
        rounds = {k: np.stack([v, v]) for k, v in b.items()}
        s.train_round(rounds)
        s.train_round(rounds)
        want = [0, 2]
    else:
        s.train_step(b)
        s.train_step(b)
        want = [0, 1]
    assert [r["iter"] for r in tr.spans("solver.step")] == want
    assert [r["parent"] for r in tr.spans("solver.prep", "solver.enqueue")] \
        == ["solver.step"] * 4


# ----------------------------------------------------------------- prefetch

def test_slow_source_shows_as_consumer_wait():
    tr = Tracer(None)

    def slow():
        for i in range(4):
            time.sleep(0.05)
            yield {"x": np.zeros(256, np.float32)}

    with time_limit(60):
        with PrefetchIterator(slow(), depth=2, tracer=tr) as it:
            items = list(it)
    assert len(items) == 4
    waits = tr.spans("prefetch.wait")
    made = tr.spans("prefetch.produce")
    assert len(made) == 4 and all(m["bytes"] == 1024 for m in made)
    assert all(m["dur_ms"] >= 45 for m in made)
    assert made[0]["tid"] != waits[0]["tid"]        # the worker's thread
    # every get but the sentinel's stood about one sleep
    assert sum(w["dur_ms"] for w in waits) >= 4 * 40
    assert sum(p["dur_ms"] for p in tr.spans("prefetch.put_wait")) < 40


def test_slow_consumer_shows_as_producer_put_wait():
    tr = Tracer(None)
    src = ({"x": np.zeros(8, np.float32)} for _ in range(6))
    with time_limit(60):
        with PrefetchIterator(src, depth=1, tracer=tr) as it:
            for _ in it:
                time.sleep(0.05)
    put = tr.spans("prefetch.put_wait")
    assert len(put) == 6
    assert sum(p["dur_ms"] for p in put) >= 100     # blocked on a full queue
    waits = tr.spans("prefetch.wait")
    assert sum(w["dur_ms"] for w in waits[1:-1]) < 50


# ----------------------------------------------------------- compile events

def test_fresh_jit_leaves_compile_records_under_the_open_span():
    tr = Tracer(None)

    def tracing_probe_fn(x):
        return (x * 3.0 + 1.0).sum()

    f = jax.jit(tracing_probe_fn)
    x = jnp.ones((5, 7))
    with tr.hot_span("caller"):
        f(x).block_until_ready()
    mine = [c for c in tr.spans("compile.backend", "compile.lower")
            if c["fun_name"] == "jit(tracing_probe_fn)"]
    assert sorted(c["name"] for c in mine) == ["compile.backend",
                                               "compile.lower"]
    assert all(c["parent"] == "caller" and c["seconds"] > 0 for c in mine)
    n = len(tr.spans())
    with tr.hot_span("caller"):
        f(x).block_until_ready()                    # cached: no compile
    assert len(tr.spans()) == n + 1
    # outside any span the record goes to the process-wide tracer
    g = jax.jit(lambda x: tracing_probe_fn(x) * 2.0)
    g(x).block_until_ready()
    assert any(c["parent"] is None
               for c in default_tracer().spans("compile.backend"))


def test_compile_events_from_a_thread_carry_that_threads_span():
    tr = Tracer(None)
    seen = {}

    def worker():
        with tr.hot_span("worker"):
            jax.jit(lambda x: x - 11.0)(jnp.ones(3)).block_until_ready()
        seen["tid"] = threading.get_ident()

    with time_limit(60):
        with tr.hot_span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
    assert not t.is_alive()
    built = [c for c in tr.spans("compile.backend")
             if c["tid"] == seen["tid"]]
    assert built and all(c["parent"] == "worker" for c in built)


# ------------------------------------------------------- names on the device

def _op_paths(solver, batch):
    """The op_name paths of the solver's lowered train step: whole
    ("jit(step)/jvp(conv1)/mul") or, inside an outlined body such as a
    scan's, from the body's root ("block0/ln1/mul")."""
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    low = solver._memory_step_fn(batch).lower(
        *solver._memory_step_args(batch))
    return set(re.findall(r'loc\("([^"\[/][^"\[]*/[^"\[]+)"',
                          low.as_text(debug_info=True)))


def _scoped(paths, scope):
    """The paths that run under `scope`: "/conv1/", or "jvp(conv1)" where
    autodiff wraps it."""
    hit = re.compile(rf"(^|[/(]){re.escape(scope)}[/)]")
    return [p for p in paths if hit.search(p)]


def test_lowered_step_carries_every_layers_scope():
    s = _solver(zoo.cifar10_full(batch_size=4), tracer=Tracer(None))
    s.set_input_transform(lambda b: dict(b, data=b["data"] * 2.0))
    paths = _op_paths(s, _cifar_batch())
    layers = [lp.name for lp, impl, _, _ in s.net.layers
              if not getattr(impl, "is_feed", False)]
    assert len(layers) >= 12
    for name in layers:
        assert _scoped(paths, name), f"no op of {name}"
    for name in ("conv1", "norm1", "pool3", "ip1"):   # and their backward
        assert any(f"transpose(jvp({name}))" in p for p in paths), name
    assert _scoped(paths, "input_transform")
    update = _scoped(paths, "update")
    assert update and not any("jvp" in p for p in update)  # not in the grad


def test_op_scopes_maps_compiled_instructions_to_layer_and_direction():
    s = _solver(zoo.cifar10_full(batch_size=4), tracer=Tracer(None))
    s.set_input_transform(lambda b: dict(b, data=b["data"] * 2.0))
    scopes = s.op_scopes(_cifar_batch())
    assert all(re.fullmatch(r"[\w.\-]+", k) for k in scopes)
    paths = set(scopes.values())
    for want in ("jvp(conv1)", "transpose(jvp(conv1))", "jvp(ip1)",
                 "transpose(jvp(pool3))", "jvp(input_transform)"):
        assert any(f"/{want}/" in p for p in paths), want
    assert _scoped(paths, "update")
    # what a trace lists (top-level instructions of the entry computation)
    # is among the keys
    assert any(k.startswith(("fusion", "convolution", "custom-call",
                             "reduce-window", "select-and-scatter"))
               for k in scopes)


def test_scan_and_remat_bodies_carry_scopes_too():
    net = zoo.transformer_lm(vocab_size=64, seq_len=32, batch_size=2,
                             d_model=32, num_layers=3, num_heads=4,
                             flash=False)
    s = _solver(net, tracer=Tracer(None))
    toks = np.random.RandomState(0).randint(0, 64, (2, 33))
    batch = {"data": toks[:, :-1], "label": toks[:, 1:]}
    block0 = [lp.name for lp, _, _, _ in s.net.layers
              if lp.name.startswith("block0/")]
    assert len(block0) >= 4
    s.set_scan("on")                    # one traced body: group 0's names
    paths = _op_paths(s, batch)
    assert all(_scoped(paths, n) for n in block0)
    assert not _scoped(paths, block0[0].replace("block0", "block1"))
    s.set_scan("off")
    s.set_remat("full")                 # checkpointed segments
    paths = _op_paths(s, batch)
    every = [lp.name for lp, _, _, _ in s.net.layers
             if lp.name.startswith("block")]
    assert all(_scoped(paths, n) for n in every)
    assert any("checkpoint" in p or "remat" in p for p in paths)


def test_fused_epilogue_runs_under_the_convs_name(monkeypatch):
    monkeypatch.setenv("SPARKNET_EPILOGUE", "on")
    s = _solver(zoo.cifar10_full(batch_size=4), tracer=Tracer(None))
    assert s.net._epilogue_plan()
    batch = {k: jnp.asarray(v) for k, v in _cifar_batch().items()}
    jaxpr = str(jax.make_jaxpr(
        lambda p: s.net.loss_fn(p, s.state, batch, s.rng)[0])(s.params))
    assert "name=bias_relu" in jaxpr


@pytest.mark.parametrize("name", ["bias_relu", "bias_relu_lrn", "lrn_fwd",
                                  "lrn_bwd", "flash_fwd", "flash_dq",
                                  "flash_dkv"])
def test_every_pallas_call_has_its_name(name):
    from sparknet_tpu.ops import pallas_attention as pa
    from sparknet_tpu.ops import pallas_epilogue as pe
    from sparknet_tpu.ops import pallas_lrn as plrn
    x = jnp.ones((2, 8, 6, 6), jnp.float32)
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    fns = {
        "bias_relu": (lambda: pe.bias_relu(x, jnp.ones(8))),
        "bias_relu_lrn": (lambda: pe.bias_relu_lrn(x, jnp.ones(8), 5, 1e-4,
                                                   0.75, 1.0)),
        "lrn_fwd": (lambda: plrn.lrn_across(x, 5, 1e-4, 0.75, 1.0)),
        "lrn_bwd": (lambda: jax.grad(lambda v: plrn.lrn_across(
            v, 5, 1e-4, 0.75, 1.0).sum())(x)),
        "flash_fwd": (lambda: pa.flash_attention(q, q, q, True)),
        "flash_dq": (lambda: jax.grad(lambda v: pa.flash_attention(
            v, q, q, True).sum())(q)),
        "flash_dkv": (lambda: jax.grad(lambda v: pa.flash_attention(
            q, v, q, True).sum())(q)),
    }
    assert f"name={name}" in str(jax.make_jaxpr(fns[name])())


# ------------------------------------------- set-up as a closed ledger (PR 51)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _child(code, timeout=600, **env_over):
    """Run `code` in a fresh process of this checkout -> its stdout."""
    env = dict(os.environ, **env_over)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def _mlp(batch=8, dim=16, classes=4):
    """The smallest net a Solver steps: two blobs to fill, one to feed."""
    net = Message("NetParameter", name="mlp")
    net.add("layer", name="d", type="JavaData", top=["data"],
            java_data_param=dict(shape=dict(dim=[batch, dim])))
    net.add("layer", name="l", type="JavaData", top=["label"],
            java_data_param=dict(shape=dict(dim=[batch])))
    net.add("layer", name="fc", type="InnerProduct", bottom=["data"],
            top=["fc"], inner_product_param=dict(
                num_output=classes, weight_filler=dict(type="xavier")))
    net.add("layer", name="loss", type="SoftmaxWithLoss",
            bottom=["fc", "label"], top=["loss"])
    return net


def _mlp_batch(batch=8, dim=16, classes=4, label=np.int32):
    rs = np.random.RandomState(0)
    return {"data": rs.randn(batch, dim).astype(np.float32),
            "label": rs.randint(0, classes, batch).astype(label)}


def _step_builds(tr, mark=0):
    return [b for b in tr.since(mark, "program.build")
            if b["parent"] == "solver.enqueue"]


def test_every_executable_has_exactly_one_program_build():
    tr = Tracer(None)

    def build_probe_f(x):
        return (x * 5.0 - 2.0).sum()

    def build_probe_g(x):
        return x + 7.0

    f, g = jax.jit(build_probe_f), jax.jit(build_probe_g)
    a, b = jnp.ones((3, 5)), jnp.ones((4, 5))
    before = tr.builds
    with tr.hot_span("caller"):
        f(a).block_until_ready()
        g(a).block_until_ready()
        f(b).block_until_ready()                # another shape: f again
        f(a).block_until_ready()                # cached: nothing
    backends = tr.spans("compile.backend")
    builds = tr.spans("program.build")
    # one for one with jax's backend events, whatever else was built
    assert [x["fun_name"] for x in builds] == \
        [x["fun_name"] for x in backends]
    assert tr.builds - before == len(builds)
    mine = [x for x in builds if "build_probe" in x["fun_name"]]
    assert [(x["fun_name"], x["nth"]) for x in mine] == [
        ("jit(build_probe_f)", 1), ("jit(build_probe_g)", 1),
        ("jit(build_probe_f)", 2)]
    lowers = {(x["fun_name"], round(x["seconds"], 6)): x
              for x in tr.spans("compile.lower")}
    for x in mine:
        assert x["parent"] == "caller" and x["cache"] == "off"
        assert "cache_load_s" not in x and "iter" not in x
        assert x["lower_s"] > 0 and x["backend_s"] > 0
        # from the start of its lowering to the end of the backend's event
        low = lowers[(x["fun_name"], x["lower_s"])]
        back = next(c for c in backends if c["fun_name"] == x["fun_name"]
                    and c["seconds"] == x["backend_s"])
        assert x["start_ms"] == low["start_ms"]
        assert x["start_ms"] + x["dur_ms"] == pytest.approx(
            back["start_ms"] + back["dur_ms"], abs=1e-3)
        assert x["dur_ms"] >= 1e3 * (x["lower_s"] + x["backend_s"]) - 1e-3


_CACHE_CHILD = """
import json
import jax, jax.numpy as jnp
from sparknet_tpu.obs.trace import Tracer
tr = Tracer(None)
def cache_probe(x):
    return (x * 3.0).sum() + 1.0
with tr.hot_span("caller"):
    jax.jit(cache_probe)(jnp.ones((6, 6))).block_until_ready()
print("BUILD " + json.dumps([b for b in tr.spans("program.build")
                             if b["fun_name"] == "jit(cache_probe)"]))
"""


def test_cache_reads_miss_then_hit_across_processes_and_off_without(tmp_path):
    def build(**env):
        out = _child(_CACHE_CHILD, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                     JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1", **env)
        (b,) = json.loads(next(ln for ln in out.splitlines()
                               if ln.startswith("BUILD "))[6:])
        return b

    shared = dict(JAX_ENABLE_COMPILATION_CACHE="true",
                  JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    first, second = build(**shared), build(**shared)
    assert first["cache"] == "miss" and "cache_load_s" not in first
    assert second["cache"] == "hit" and second["cache_load_s"] > 0
    assert second["nth"] == 1           # per process
    off = build(JAX_ENABLE_COMPILATION_CACHE="false")
    assert off["cache"] == "off" and "cache_load_s" not in off


@pytest.mark.parametrize("what", ["dtype", "committed"])
def test_a_rebuild_names_the_leaf_that_differs(what):
    tr = Tracer(None)
    s = _solver(_mlp(), tracer=tr)
    s.train_step(_mlp_batch())
    s.train_step(_mlp_batch())
    (first,) = _step_builds(tr)
    assert first["cause"] == ["first"] and first["changed"] == 0
    assert first["iter"] == 0 and first["fun_name"] == "jit(step)"
    mark = tr.mark()
    if what == "dtype":
        s.train_step(_mlp_batch(label=np.int16))
        want = "batch/label: dtype int32 -> int16"
    else:
        s.params["fc"][1] = jax.device_put(s.params["fc"][1],
                                           jax.devices()[0])
        s.train_step(_mlp_batch())
        want = "params/fc/1: committed False -> True"
    (again,) = _step_builds(tr, mark)
    assert again["cause"] == [want] and again["changed"] == 1
    assert again["iter"] == 2 and again["nth"] >= 2
    if what == "committed":
        # one committed argument commits every result: the next step is
        # built once more, and says so leaf by leaf, alike leaves once
        mark = tr.mark()
        s.train_step(_mlp_batch())
        (third,) = _step_builds(tr, mark)
        assert third["changed"] > 1 and third["cause"][0].startswith(
            "params/fc/0: committed False -> True")
        assert any(c.startswith("history/fc/") and " more of history)" in c
                   for c in third["cause"])


def test_a_retrace_with_nothing_changed_says_same_signature():
    tr = Tracer(None)
    s = _solver(_mlp(), tracer=tr)
    s.train_step(_mlp_batch())
    mark = tr.mark()
    jax.clear_caches()          # jax retraces for a reason of its own
    s.train_step(_mlp_batch())
    (again,) = _step_builds(tr, mark)
    assert again["cause"] == ["same signature"] and again["changed"] == 0


def test_steady_steps_take_no_signature_and_write_no_build(monkeypatch):
    from sparknet_tpu.obs import trace
    taken = []
    real = trace.signature
    monkeypatch.setattr(trace, "signature",
                        lambda *a, **k: taken.append(1) or real(*a, **k))
    tr = Tracer(None)
    s = _solver(_mlp(), tracer=tr)
    batch = _mlp_batch()
    s.train_step(batch)
    assert len(taken) == 1      # the first build's, kept for the next
    s.train_step(batch)
    del taken[:]
    mark, builds = tr.mark(), tr.builds
    for _ in range(5):
        s.train_step(batch)
    assert not taken and tr.builds == builds
    # the parent's three records a step, and nothing else
    assert [r["name"] for r in tr.since(mark)] == \
        ["solver.prep", "solver.enqueue", "solver.step"] * 5


@pytest.mark.parametrize("cls", ["Solver", "DataParallelSolver"])
def test_plain_and_mesh_solvers_record_cause_through_the_same_code(
        cls, monkeypatch):
    from sparknet_tpu import parallel
    explained = []
    real = Tracer._explain_builds
    monkeypatch.setattr(Tracer, "_explain_builds",
                        lambda self, step: explained.append(
                            step.attrs["iter"]) or real(self, step))
    tr = Tracer(None)
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, random_seed=1)
    make = Solver if cls == "Solver" else getattr(parallel, cls)
    s = make(sp, net_param=_mlp(), log_fn=None, tracer=tr)
    for _ in range(3):
        s.train_step(_mlp_batch())
    built = _step_builds(tr)
    assert built and all("cause" in b and "changed" in b for b in built)
    assert built[0]["cause"] == ["first"]
    assert [b["cause"] for b in built if b["fun_name"] == "jit(step)"][0] \
        == ["first"]
    # only a step that built something was explained
    assert explained == sorted({b["iter"] for b in
                                tr.spans("program.build") if "iter" in b})
    # the mesh solver commits what it lays over the mesh: nothing changes
    # between its first call and its second, and the step is built once
    if cls == "DataParallelSolver":
        assert {b["iter"] for b in built} == {0}


_KERNEL_IMPORT_CHILD = """
import json, os, sys
import numpy as np
from sparknet_tpu.models import zoo
from sparknet_tpu.obs.trace import default_tracer
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver

def solver():
    return Solver(Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                          display=0), log_fn=None,
                  net_param=zoo.caffenet(batch_size=2, num_classes=10))
batch = {"data": np.zeros((2, 3, 227, 227), np.float32),
         "label": np.zeros((2,), np.int32)}
tr = default_tracer()
s = solver()
s.train_step(batch); s.train_step(batch)
print("CNN " + json.dumps([tr.spans("import.kernel"),
                           tr.spans("package.import")]))
os.environ["SPARKNET_LRN"] = "pallas"      # the same net, its LRN a kernel
mark = tr.mark()
s = solver()
s.train_step(batch); s.train_step(batch)
s = solver()
s.train_step(batch)
print("KERNEL " + json.dumps(tr.since(mark, "import.kernel",
                                      "package.import", "compile.trace")))
"""


def test_import_kernel_is_written_once_and_never_by_a_caffenet_step():
    out = _child(_KERNEL_IMPORT_CHILD)
    lines = {ln.split(" ", 1)[0]: json.loads(ln.split(" ", 1)[1])
             for ln in out.splitlines() if ln.startswith(("CNN ", "KERNEL "))}
    kernel_imports, (package,) = lines["CNN"]
    assert kernel_imports == []
    # PR 30's rule as a recorded fact: no pallas before, none after
    assert package["pallas"] is False and package["modules"] > 10
    assert package["dur_ms"] > 0 and package["parent"] is None
    recs = lines["KERNEL"]
    (imp,) = [r for r in recs if r["name"] == "import.kernel"]
    assert imp["module"] == "sparknet_tpu.ops.pallas_lrn"
    assert imp["dur_ms"] > 0
    # inside the trace that met the layer, which used to hide it
    assert any(r["name"] == "compile.trace"
               and r["start_ms"] <= imp["start_ms"]
               and r["start_ms"] + r["dur_ms"]
               >= imp["start_ms"] + imp["dur_ms"] for r in recs)
    # and the package's import is said once a process
    assert not [r for r in recs if r["name"] == "package.import"]


_SETUP_PARTS_CHILD = """
import importlib, json, sys
sys.path.insert(0, "benchmark")
import run
rc = run.main(["--workload", sys.argv[1], "--rehearse", "--trace", "1",
               "--seed", "3000000019"])
import setup_parts
from sparknet_tpu.obs.trace import default_tracer
names = ["setup_import_s", "setup_kernel_import_s", "setup_init_programs",
         "setup_init_build_s", "setup_step_trace_s", "setup_step_lower_s",
         "setup_step_backend_s", "step_rebuilds", "setup_outside_s"]
led = setup_parts.ledger({})
print("READ " + json.dumps({
    "rc": rc, "ledger": led,
    "values": {n: importlib.import_module("layer_metrics." + n).read({})
               for n in names},
    "records": len(default_tracer().spans())}))
"""


@pytest.mark.parametrize("cell", ["caffenet_b1536_resident",
                                  "lfm2moe_ep4_s8192_b3"])
def test_setup_parts_of_a_rehearsal_add_up_and_every_reader_reads(cell):
    out = _child(_SETUP_PARTS_CHILD.replace("sys.argv[1]", repr(cell)),
                 timeout=900, JAX_PLATFORMS="cpu")
    said = [ln for ln in out.splitlines() if ln.startswith("# setup parts ")]
    assert len(said) == 1                   # once a process
    line = json.loads(said[0][len("# setup parts "):])
    got = json.loads(next(ln for ln in out.splitlines()
                          if ln.startswith("READ "))[5:])
    assert got["rc"] == 0
    led, values = got["ledger"], got["values"]
    assert abs(sum(led["parts"].values()) - led["interval_s"]) < 1e-3
    assert abs(line["sum_s"] - line["interval_s"]) < 1e-3
    assert set(led["parts"]) == set(line["parts"]) and len(led["parts"]) == 11
    # the interval is the harness's own set-up, less what precedes the
    # package's first import
    assert abs(led["setup_after_import_s"] - led["interval_s"]) < 0.2
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in values.values()), values
    assert values["setup_import_s"] == led["parts"]["import"] > 0
    assert values["setup_outside_s"] == led["parts"]["outside"] > 0
    assert values["setup_init_programs"] > 4
    assert values["setup_init_build_s"] > 0
    assert values["setup_step_trace_s"] > 0
    assert values["setup_step_lower_s"] > 0
    assert values["setup_step_backend_s"] > 0
    # the harness commits the weights it seeds and not the history it
    # zeroes: the step's second call is built again, and says why
    assert values["step_rebuilds"] == 1
    first, second = led["step_builds"]
    assert first["cause"] == ["first"] and second["iter"] == 1
    assert any(c.startswith("history/") and "committed False -> True" in c
               for c in second["cause"])
    if cell.startswith("caffenet"):
        assert values["setup_kernel_import_s"] == 0
        assert led["kernel_modules"] == [] and led["pallas"] is False
    else:       # the toy LM's flash pass runs its kernel, interpreted
        assert values["setup_kernel_import_s"] > 0
        assert led["kernel_modules"] == ["sparknet_tpu.ops.pallas_attention"]
        assert values["setup_import_s"] >= values["setup_kernel_import_s"]


@pytest.fixture
def setup_parts(monkeypatch):
    if BENCH not in sys.path:
        monkeypatch.syspath_prepend(BENCH)
    import harness
    import program_spans
    import setup_parts
    tr = Tracer(None)
    said = []
    monkeypatch.setattr(program_spans, "default_tracer", lambda: tr)
    monkeypatch.setattr(harness, "say", said.append)
    monkeypatch.setattr(setup_parts, "_cache", [])
    return setup_parts, tr, said


def _hand_made_setup(tr, build=True):
    """A set-up's records by hand, in ms of the ring's clock: import
    0-100, init 100-400 with a 50 ms build, a step 500-800 that traces
    for 100, lowers for 50 and compiles for 100, the window's step at
    1000."""
    ms = 1_000_000
    t0 = tr.t0
    tr.record("package.import", t0, t0 + 100 * ms, modules=40, pallas=False)
    stack = tr._stack()

    def put(name, a, b, parent=None, **kw):
        tr._put(name, t0 + a * ms, t0 + b * ms, 1 if parent else 0, parent,
                kw, False)

    if build:
        put("program.build", 200, 250, "net.init", fun_name="jit(fill)",
            nth=1, lower_s=0.01, backend_s=0.04, cache="off")
    put("net.init", 150, 300, "solver.init")
    put("solver.init", 100, 400)
    put("solver.prep", 500, 510, "solver.step", iter=0)
    put("import.kernel", 520, 540, "solver.enqueue", module="m")
    put("compile.trace", 510, 610, "solver.enqueue", fun_name="step")
    put("compile.lower", 610, 660, "solver.enqueue", fun_name="jit(step)")
    put("compile.backend", 670, 770, "solver.enqueue", fun_name="jit(step)")
    if build:
        put("program.build", 610, 770, "solver.enqueue",
            fun_name="jit(step)", nth=1, lower_s=0.05, backend_s=0.1,
            cache="off", iter=0, cause=["first"], changed=0)
    put("solver.enqueue", 510, 790, "solver.step", iter=0)
    put("solver.step", 500, 800, iter=0)
    put("solver.step", 1000, 1010, iter=1)
    assert not stack


def test_setup_parts_partition_gives_every_instant_to_one_part(setup_parts):
    sp, tr, said = setup_parts
    _hand_made_setup(tr)
    led = sp.ledger({"dispatch_s": [0.01]})
    assert led["interval_s"] == pytest.approx(1.0)
    want = {"import": 0.12, "net.build": 0.0, "init.build": 0.05,
            "init.rest": 0.25, "step.prep": 0.01, "step.trace": 0.08,
            "step.lower": 0.05, "step.backend": 0.1,
            "step.enqueue_rest": 0.04, "fetch": 0.0, "outside": 0.3}
    assert led["parts"] == pytest.approx(want, abs=1e-9)
    assert sum(led["parts"].values()) == pytest.approx(1.0, abs=1e-9)
    assert led["init_programs"] == 1 and led["step_rebuilds"] == 0
    assert led["kernel_import_s"] == pytest.approx(0.02)
    assert len(said) == 1 and said[0].startswith("# setup parts {")
    sp.ledger({"dispatch_s": [0.01]})
    assert len(said) == 1                   # computed and said once


@pytest.mark.parametrize("why", ["dropped", "no program.build"])
def test_setup_parts_gives_none_and_says_why(setup_parts, why):
    sp, tr, said = setup_parts
    if why == "dropped":
        _hand_made_setup(tr)
        # the ring wraps: set-up's oldest records fall off its left end
        for _ in range(tr.max_buffer - len(tr.spans()) + 1):
            tr.record("prefetch.wait", tr.t0, tr.t0)
        assert tr.dropped == 1
        assert not tr.spans("package.import")
        want = "the ring dropped 1 records"
    else:
        _hand_made_setup(tr, build=False)       # a parent from before PR 51
        want = "the program writes no program.build record"
    assert sp.ledger({"dispatch_s": [0.01]}) is None
    assert sp.seconds({"dispatch_s": [0.01]}, "import") is None
    assert sp.count({"dispatch_s": [0.01]}, "step_rebuilds") is None
    assert len(said) == 1
    assert said[0].startswith("# setup parts none: ") and want in said[0]
