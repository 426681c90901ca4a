"""Observability subsystem tests (sparknet_tpu.obs + utils.metrics).

Covers the ISSUE-1 acceptance surface: span nesting/export round-trip,
step-accounting percentiles + recompile detection, comms byte counters
under a 2-device CPU mesh, the hardened MetricsLogger encoder, the
`report` CLI on a canned JSONL fixture, and the full `train --metrics
--profile` -> `report` loop on CPU.
"""

import io
import json
import pathlib
import threading

import numpy as np
import pytest
import jax

from sparknet_tpu.proto import Message
from sparknet_tpu.utils.metrics import MetricsLogger
from sparknet_tpu.obs import (Tracer, StepAccounting, CommsMeter,
                              percentiles, tree_bytes,
                              ring_allreduce_bytes,
                              broadcast_collect_bytes, all_to_all_bytes)
from sparknet_tpu.obs import report as obs_report
from sparknet_tpu.obs.trace import chrome_from_spans


def events_of(buf):
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def mlp_net(batch=8, dim=16, classes=4):
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


def toy_batches(batch=8, dim=16, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    while True:
        yield {"data": rs.randn(batch, dim).astype(np.float32),
               "label": rs.randint(0, classes, batch).astype(np.int32)}


# ---------------------------------------------------------------- metrics

class TestMetricsLogger:
    def test_context_manager_and_basic_event(self, tmp_path):
        p = tmp_path / "m.jsonl"
        with MetricsLogger(str(p)) as ml:
            ml.log("hello", x=1)
        ev = json.loads(p.read_text())
        assert ev["event"] == "hello" and ev["x"] == 1
        ml.log("after_close")          # silently dropped, no crash
        assert len(p.read_text().splitlines()) == 1

    def test_non_json_fields_do_not_crash(self):
        buf = io.StringIO()
        ml = MetricsLogger(stream=buf)
        ml.log("mixed",
               arr=np.arange(4),
               big=np.zeros((100, 100)),
               scalar=np.float32(1.5),
               dt=np.dtype("float32"),
               path=pathlib.Path("/tmp/x"),
               s={"b", "a"},
               raw=b"bytes")
        ev = events_of(buf)[0]
        assert ev["arr"] == [0, 1, 2, 3]
        assert ev["big"]["shape"] == [100, 100]     # large arrays elided
        assert ev["scalar"] == 1.5
        assert ev["dt"] == "float32"
        assert ev["path"] == "/tmp/x"
        assert ev["s"] == ["a", "b"]

    def test_thread_safety_line_integrity(self):
        buf = io.StringIO()
        ml = MetricsLogger(stream=buf)

        def work(i):
            for j in range(50):
                ml.log("w", i=i, j=j)
        ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        evs = events_of(buf)               # every line parses
        assert len(evs) == 200


# ----------------------------------------------------------------- tracer

class TestTracer:
    def test_nesting_depth_and_parent(self):
        buf = io.StringIO()
        tr = Tracer(MetricsLogger(stream=buf))
        with tr.span("outer"):
            with tr.span("inner", k=3) as attrs:
                attrs["extra"] = "late"
        evs = events_of(buf)
        inner, outer = evs[0], evs[1]      # inner closes first
        assert inner["name"] == "inner" and inner["parent"] == "outer"
        assert inner["depth"] == 1 and outer["depth"] == 0
        assert inner["k"] == 3 and inner["extra"] == "late"
        assert outer["parent"] is None
        assert outer["dur_ms"] >= inner["dur_ms"]

    def test_chrome_export_round_trip(self, tmp_path):
        tr = Tracer(None)                  # sink-less: buffer still works
        with tr.span("a"):
            with tr.span("b"):
                pass
        tr.instant("mark", note="x")
        path = tr.export_chrome(str(tmp_path / "t" / "trace.json"))
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        assert {e["name"] for e in evs} == {"a", "b", "mark"}
        b = next(e for e in evs if e["name"] == "b")
        a = next(e for e in evs if e["name"] == "a")
        assert b["ph"] == "X" and a["ts"] <= b["ts"]
        assert b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 0.11
        assert b["args"]["parent"] == "a"

    def test_threads_nest_independently(self):
        tr = Tracer(None)
        seen = {}

        def worker():
            with tr.span("t2"):
                seen["depth"] = len(tr._stack())
        with tr.span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["depth"] == 1          # not nested under "main"
        spans = tr.spans()
        t2 = next(s for s in spans if s["name"] == "t2")
        assert t2["parent"] is None and t2["depth"] == 0
        assert chrome_from_spans(spans)    # exportable


# ---------------------------------------------------------- step stats

class TestStepAccounting:
    def test_percentiles(self):
        vals = list(range(1, 101))         # 1..100
        p = percentiles(vals)
        assert p["p50"] == pytest.approx(50.5)
        assert p["p95"] == pytest.approx(95.05)
        assert p["p99"] == pytest.approx(99.01)
        assert percentiles([]) == {}
        assert percentiles([7.0])["p99"] == 7.0

    def test_recompile_detection_via_cache_size(self):
        """The one source since PR 51: the tracer's `program.build` records
        under `solver.enqueue` (the name is older: jit's private
        `_cache_size()` was polled here). Same count: the first build and
        one more."""
        from sparknet_tpu.obs.trace import Tracer
        from sparknet_tpu.obs.event_schema import EVENTS
        buf = io.StringIO()
        tr = Tracer(None)
        sa = StepAccounting(MetricsLogger(stream=buf), sample_every=1000,
                            tracer=tr)
        f = jax.jit(lambda x: x * 2)

        def step(it, batch):
            with tr.step("solver.step", it, "solver.prep") as span:
                span.phase("solver.enqueue")
                span.watch(f, (batch["x"],), ("data",))
                f(batch["x"])
            sa.observe(it, span.host_s, sample=False)

        step(0, {"x": np.ones(3, np.float32)})
        step(1, {"x": np.ones(4, np.float32)})  # shape change -> retrace
        step(2, {"x": np.ones(4, np.float32)})  # steady: nothing
        evs = events_of(buf)
        rec = [e for e in evs if e["event"] == "recompile"]
        assert len(rec) == 2
        assert rec[0]["first"] is True and rec[0]["reason"] == "first_compile"
        assert rec[0]["cause"] == ["first"] and rec[0]["cache_size"] == 1
        assert rec[1]["first"] is False and rec[1]["iter"] == 1
        assert rec[1]["reason"] == "shape_change"
        assert rec[1]["cause"] == ["data: shape (3,) -> (4,)"]
        assert rec[1]["cache_size"] == 2 and rec[1]["cache"] == "off"
        assert rec[1]["lower_s"] > 0 and rec[1]["backend_s"] > 0
        assert sa.recompiles == 1           # beyond the expected first
        # the event is the schema's, cause and all
        assert set(rec[1]) - {"event", "t", "run"} \
            <= set(EVENTS["recompile"]["fields"])

    def test_sampling_and_summary(self):
        buf = io.StringIO()
        sa = StepAccounting(MetricsLogger(stream=buf), sample_every=4)
        x = jax.numpy.ones(2)
        for it in range(12):
            sa.observe(it, 0.002, result=x)
        sa.flush(12)
        evs = events_of(buf)
        steps = [e for e in evs if e["event"] == "step"]
        # first two observes sampled, then every 4th iter
        assert [e["iter"] for e in steps] == [0, 1, 5, 9]
        assert all("device_ms" in e and "host_ms" in e for e in steps)
        summ = [e for e in evs if e["event"] == "step_summary"][-1]
        assert summ["steps"] == 12
        assert summ["host_ms_p50"] == pytest.approx(2.0, rel=0.5)
        assert summ["device_samples"] == len(steps)


# -------------------------------------------------------------- comms

class TestComms:
    def test_byte_models(self):
        assert ring_allreduce_bytes(1000, 1) == 0
        assert ring_allreduce_bytes(1000, 2) == 1000
        assert ring_allreduce_bytes(1000, 4) == 1500
        assert broadcast_collect_bytes(1000, 4) == 8000
        assert all_to_all_bytes(1000, 4) == 750
        assert tree_bytes({"a": [np.zeros((2, 3), np.float32)],
                           "b": np.zeros(5, np.int32)}) == 24 + 20

    def test_meter_emission_and_flush(self):
        buf = io.StringIO()
        cm = CommsMeter(MetricsLogger(stream=buf), emit_every=10)
        cm.set_topology(strategy="X", n_devices=2)
        cm.register("allreduce", 1000, steps_per_round=1)
        cm.register("param_avg", 500, steps_per_round=10)
        for it in range(15):
            cm.add_h2d(100)
            cm.tick(it)
        cm.flush(14)
        evs = events_of(buf)
        assert all(e["event"] == "comms" for e in evs)
        assert evs[0]["iter"] == 0 and evs[0]["h2d_bytes"] == 100
        assert evs[0]["collective_bytes_per_step"] == 1050
        # h2d deltas across all emits sum to the total
        assert sum(e["h2d_bytes"] for e in evs) == 1500
        assert evs[-1]["h2d_bytes_total"] == 1500


# ------------------------------------------------- solver integration

class TestSolverObs:
    def _solver(self, cls=None, **kw):
        from sparknet_tpu.solver.solver import Solver
        sp = Message("SolverParameter", base_lr=0.1, lr_policy="fixed",
                     random_seed=0, display=0)
        buf = io.StringIO()
        s = (cls or Solver)(sp, net_param=mlp_net(),
                            metrics=MetricsLogger(stream=buf),
                            log_fn=None, **kw)
        return s, buf

    def test_single_device_stream(self):
        s, buf = self._solver()
        data = toy_batches()
        for _ in range(3):
            s.train_step(next(data))
        s.close()
        evs = events_of(buf)
        kinds = {e["event"] for e in evs}
        assert {"step", "comms", "recompile", "step_summary"} <= kinds
        step = next(e for e in evs if e["event"] == "step")
        assert step["host_ms"] >= 0 and step["device_ms"] > 0
        comms = next(e for e in evs if e["event"] == "comms")
        b = next(toy_batches())
        assert comms["h2d_bytes"] == sum(np.asarray(v).nbytes
                                         for v in b.values())
        assert comms["strategy"] == "Solver"

    def test_recompile_event_says_the_cause_to_report_and_monitor(self):
        """One source for the operator's `recompile` event (PR 51): the
        tracer's `program.build` under the step's enqueue, with what
        differed in the step's arguments."""
        from sparknet_tpu.obs.event_schema import EVENTS
        from sparknet_tpu.obs.monitor import MonitorState
        s, buf = self._solver()
        data = toy_batches()
        s.train_step(next(data))
        s.train_step(next(data))
        odd = next(data)
        odd["label"] = odd["label"].astype(np.int16)
        s.train_step(odd)
        s.train_step(odd)
        s.close()
        evs = events_of(buf)
        first, again = [e for e in evs if e["event"] == "recompile"]
        for e in (first, again):
            assert set(e) - {"event", "t", "run"} == \
                set(EVENTS["recompile"]["fields"])
        assert first["first"] and first["cause"] == ["first"]
        assert first["iter"] == 0 and first["cache_size"] == 1
        assert again["iter"] == 2 and again["cache_size"] == 2
        assert again["first"] is False and again["reason"] == "shape_change"
        assert again["cause"] == ["batch/label: dtype int32 -> int16"]
        assert again["cache"] == "off" and again["backend_s"] > 0
        summary = next(e for e in evs if e["event"] == "step_summary")
        assert summary["recompiles"] == 1
        # memstats counts the same records where it samples (the first
        # two steps here, before the rebuild)
        assert [e["compile_cache"] for e in evs
                if e["event"] == "memstats"] == [1, 1]
        text = obs_report.render(obs_report.aggregate(evs))
        assert "unexpected recompiles: 1" in text
        assert "step 2 rebuilt" in text
        assert "batch/label: dtype int32 -> int16" in text
        mon = MonitorState()
        for e in evs:
            mon.update(e)
        assert "recompiles 1" in mon.render()
        assert "(step 2: batch/label: dtype int32 -> int16)" in mon.render()

    def test_dp_comms_byte_counters_two_device_mesh(self):
        from sparknet_tpu.parallel import DataParallelSolver, make_mesh
        s, buf = self._solver(cls=DataParallelSolver,
                              mesh=make_mesh({"data": 2}))
        data = toy_batches()
        for _ in range(2):
            s.train_step(next(data))
        gb, sb = tree_bytes(s.params), tree_bytes(s.state)
        expected = ring_allreduce_bytes(gb + sb, 2)
        s.close()
        evs = events_of(buf)
        comms = [e for e in evs if e["event"] == "comms"]
        assert comms, "no comms events from DP solver"
        # bucketed overlap is the default: grads register per bucket in
        # issue order, state separately — total bytes unchanged (the
        # ring model is exactly linear at n=2)
        cols = comms[0]["collectives"]
        grads = [c for c in cols if c["kind"] == "allreduce_grads_bucket"]
        state = [c for c in cols if c["kind"] == "allreduce_state"]
        # the stateless toy MLP registers no zero-byte state collective
        assert grads and len(state) == (1 if sb else 0)
        assert sum(c["bytes_per_round"] for c in grads) == \
            ring_allreduce_bytes(gb, 2)
        assert [c["bucket"] for c in grads] == list(range(len(grads)))
        assert not grads[-1]["overlappable"]
        # the paper comparison rides the (always-registered) grad volume
        assert grads[-1]["paper_broadcast_collect_bytes"] == \
            broadcast_collect_bytes(gb, 2)
        if state:
            assert state[0]["bytes_per_round"] == ring_allreduce_bytes(sb, 2)
        assert comms[0]["axes"] == {"data": 2}
        assert comms[0]["collective_bytes_per_step"] == expected

    def test_local_sgd_round_accounting(self):
        from sparknet_tpu.parallel import LocalSGDSolver, make_mesh
        s, buf = self._solver(cls=LocalSGDSolver,
                              mesh=make_mesh({"data": 2}), tau=3)
        rs = np.random.RandomState(0)
        batches = {"data": rs.randn(3, 16, 16).astype(np.float32),
                   "label": rs.randint(0, 4, (3, 16)).astype(np.int32)}
        s.train_round(dict(batches))
        s.close()
        evs = events_of(buf)
        comms = [e for e in evs if e["event"] == "comms"]
        col = comms[0]["collectives"][0]
        assert col["kind"] == "param_average"
        assert col["steps_per_round"] == 3
        assert comms[0]["tau"] == 3
        assert any(e["event"] == "step" for e in evs)

    def test_close_is_idempotent_and_stops_watchdog(self):
        s, buf = self._solver()
        wd = s.arm_watchdog(stall_seconds=30, poll_seconds=0.01)
        assert wd.metrics is s.metrics     # barks land in the JSONL
        assert wd._thread.is_alive()
        s.close()
        assert s.watchdog is None
        assert not wd._thread.is_alive()
        s.close()                          # second close: no-op


# ------------------------------------------------------------- report

CANNED = [
    {"event": "config", "t": 0.0, "d_model": 64},
    {"event": "span", "t": 0.1, "name": "setup", "start_ms": 0.0,
     "dur_ms": 100.0, "depth": 0, "parent": None, "tid": 1},
    {"event": "span", "t": 0.2, "name": "test", "start_ms": 150.0,
     "dur_ms": 30.0, "depth": 1, "parent": "train_block", "tid": 1},
    {"event": "span", "t": 0.3, "name": "train_block", "start_ms": 100.0,
     "dur_ms": 400.0, "depth": 0, "parent": None, "tid": 1},
    {"event": "step", "t": 0.2, "iter": 0, "host_ms": 5.0,
     "device_ms": 50.0, "sync_ms": 1.0, "steps_since_sync": 1},
    {"event": "step", "t": 0.3, "iter": 5, "host_ms": 1.0,
     "device_ms": 10.0, "sync_ms": 0.5, "steps_since_sync": 5},
    {"event": "recompile", "t": 0.1, "iter": 0, "cache_size": 1,
     "first": True, "reason": "first_compile"},
    {"event": "recompile", "t": 0.25, "iter": 3, "cache_size": 2,
     "first": False, "reason": "shape_change"},
    {"event": "comms", "t": 0.3, "iter": 5, "steps": 6,
     "h2d_bytes": 600, "h2d_bytes_total": 600,
     "collective_bytes_per_step": 1500, "strategy": "DataParallelSolver",
     "n_devices": 2, "axes": {"data": 2},
     "collectives": [{"kind": "allreduce_grads", "bytes_per_round": 1500,
                      "steps_per_round": 1}]},
    {"event": "train", "t": 0.25, "iter": 0, "loss": 2.0, "lr": 0.1,
     "images_per_sec": 100.0},
    {"event": "train", "t": 0.3, "iter": 5, "loss": 1.0, "lr": 0.1,
     "images_per_sec": 120.0},
    {"event": "test", "t": 0.31, "iter": 5, "accuracy": 0.5},
    {"event": "step_summary", "t": 0.35, "iter": 6, "name": "train",
     "steps": 6, "recompiles": 1, "device_samples": 2,
     "host_ms_p50": 1.2, "host_ms_p95": 4.5, "host_ms_p99": 5.0,
     "device_ms_p50": 30.0, "device_ms_p95": 48.0, "device_ms_p99": 50.0},
    {"event": "watchdog", "t": 0.2, "kind": "nan", "loss": float("nan")},
    {"event": "prefetch", "t": 0.3, "name": "train_feed", "gets": 6,
     "depth_cap": 3, "depth_mean": 2.5, "empty_frac": 0.0},
]


class TestReport:
    @pytest.fixture
    def canned(self, tmp_path):
        p = tmp_path / "run.jsonl"
        with open(p, "w") as f:
            for e in CANNED:
                f.write(json.dumps(e) + "\n")
            f.write("not json\n")          # malformed line is tolerated
        return p

    def test_aggregate(self, canned):
        events, bad = obs_report.load_events(str(canned))
        assert bad == 1
        rep = obs_report.aggregate(events)
        assert rep["num_events"] == len(CANNED)
        phases = {p["phase"]: p for p in rep["phases"]}
        assert set(phases) == {"setup", "train_block"}   # top-level only
        assert phases["train_block"]["pct"] == 80.0
        assert rep["steps"]["recompiles"] == 1
        assert rep["steps"]["host_ms_p95"] == 4.5
        assert rep["recompiles"]["count"] == 1
        assert rep["recompiles"]["unexpected"][0]["iter"] == 3
        assert rep["comms"]["collective_bytes_per_step"] == 1500
        assert rep["train"]["first_loss"] == 2.0
        assert rep["train"]["final_loss"] == 1.0
        assert rep["train"]["images_per_sec"]["mean"] == 110.0
        assert rep["test"]["accuracy"] == 0.5
        assert rep["watchdog"] == {"nan": 1}
        assert rep["prefetch"]["depth_mean"] == 2.5

    def test_render_and_cli(self, canned, tmp_path, capsys):
        from sparknet_tpu import cli
        out_json = tmp_path / "rep.json"
        chrome = tmp_path / "trace.json"
        rc = cli.main(["report", str(canned), "--json", str(out_json),
                       "--chrome", str(chrome)])
        assert rc == 0
        out = capsys.readouterr().out
        for needle in ("per-phase time breakdown", "train_block",
                       "step times", "recompiles", "communication",
                       "loss curve", "watchdog", "malformed"):
            assert needle in out, f"missing {needle!r} in report"
        rep = json.load(open(out_json))
        assert rep["malformed_lines"] == 1
        doc = json.load(open(chrome))
        assert len(doc["traceEvents"]) == 3


# ----------------------------------------------- CLI end-to-end (CPU)

NET_PROTOTXT = """
name: "obs_mlp"
layer { name: "data" type: "JavaData" top: "data"
        java_data_param { shape { dim: 8 dim: 16 } } }
layer { name: "label" type: "JavaData" top: "label"
        java_data_param { shape { dim: 8 } } }
layer { name: "fc" type: "InnerProduct" bottom: "data" top: "fc"
        inner_product_param { num_output: 10
                              weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc" bottom: "label"
        top: "loss" }
"""

SOLVER_PROTOTXT = """
net: "net.prototxt"
base_lr: 0.05
lr_policy: "fixed"
display: 2
max_iter: 5
random_seed: 0
"""


def test_train_cli_metrics_profile_report(tmp_path, capsys):
    """ISSUE-1 acceptance: 5-step synthetic run with --metrics/--profile
    produces step/span/comms/recompile events with a host/device split,
    a valid Chrome span trace, and a `report` that renders + exports."""
    from sparknet_tpu import cli
    (tmp_path / "net.prototxt").write_text(NET_PROTOTXT)
    solver = tmp_path / "solver.prototxt"
    solver.write_text(SOLVER_PROTOTXT)
    mj = tmp_path / "run.jsonl"
    tr = tmp_path / "trace"
    rc = cli.main(["train", "--solver", str(solver), "--iterations", "5",
                   "--metrics", str(mj), "--profile", str(tr)])
    assert rc == 0
    events = [json.loads(line) for line in open(mj)]
    kinds = {e["event"] for e in events}
    assert {"step", "span", "comms", "recompile"} <= kinds
    step = next(e for e in events if e["event"] == "step")
    assert "host_ms" in step and "device_ms" in step
    spans = {e["name"] for e in events if e["event"] == "span"}
    assert {"setup", "train_block"} <= spans
    doc = json.load(open(tr / "spans.trace.json"))
    assert any(e["name"] == "train_block" for e in doc["traceEvents"])
    capsys.readouterr()
    rj = tmp_path / "rep.json"
    rc = cli.main(["report", str(mj), "--json", str(rj)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "per-phase time breakdown" in out
    assert "loss curve" in out
    rep = json.load(open(rj))
    assert rep["steps"]["steps"] == 5
    assert rep["comms"]["h2d_bytes_total"] > 0
