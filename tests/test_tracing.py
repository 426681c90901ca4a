"""The spans and names the program carries itself (obs/trace.py and its
callers): the ring, the profiler annotations, the solver step's records,
both sides of the prefetch queue, jax's compile events, and the layer
scopes and kernel names in the lowered step."""

import contextlib
import glob
import re
import signal
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
