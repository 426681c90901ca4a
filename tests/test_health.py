"""Training-dynamics health layer tests (sparknet_tpu.obs, ISSUE 3).

Covers the acceptance surface: divergence measured at the sync round is
monotonically non-decreasing in tau on a deterministic toy model with
worker-disjoint data; a chaos-injected stall makes the straggler
detector name the slow worker; the HealthMonitor detectors (straggler,
loss skew, per-worker NaN, divergence trend/ceiling) fire with the right
attribution and respect cooldowns; the comms cost models are clean at
world_size=1 / zero bytes; `sparknet report` / `sparknet monitor` turn
missing/empty/garbage metrics files into one-line errors; and the
device-cache hit/miss gauge lands in the metrics stream.
"""

import io
import json

import numpy as np
import pytest
import jax

from sparknet_tpu.proto import Message
from sparknet_tpu.utils.metrics import MetricsLogger
from sparknet_tpu.obs import (HealthMonitor, DivergenceMeter, MemoryMonitor,
                              CommsMeter, ring_allreduce_bytes,
                              broadcast_collect_bytes, all_to_all_bytes)
from sparknet_tpu.obs import report as obs_report
from sparknet_tpu.obs.report import MetricsFileError
from sparknet_tpu.obs.monitor import MonitorState, _Tail, monitor_file


def events_of(buf):
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def sink():
    buf = io.StringIO()
    return MetricsLogger(stream=buf), buf


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


def lsgd_solver(tau, metrics=None):
    from sparknet_tpu.parallel import LocalSGDSolver, make_mesh
    sp = Message("SolverParameter", base_lr=0.05, lr_policy="fixed",
                 random_seed=0, display=0)
    return LocalSGDSolver(sp, net_param=mlp_net(), metrics=metrics,
                          mesh=make_mesh({"data": 2}), tau=tau, log_fn=None)


# ------------------------------------------------ divergence vs tau (e2e)

class TestDivergenceVsTau:
    MAXT = 8

    def _round_batches(self):
        """tau worker-disjoint steps: worker 0 (batch rows 0..7) only ever
        sees class 0 drawn around +2, worker 1 only class 1 around -2 —
        each local step pulls the replicas toward different classifiers,
        so drift at the averaging point grows with tau."""
        rs = np.random.RandomState(0)
        data = rs.randn(self.MAXT, 16, 16).astype(np.float32)
        data[:, :8, :] += 2.0
        data[:, 8:, :] -= 2.0
        labels = np.zeros((self.MAXT, 16), np.int32)
        labels[:, 8:] = 1
        return data, labels

    def test_divergence_monotone_in_tau(self):
        data, labels = self._round_batches()
        means = []
        for tau in (1, 2, 4, 8):
            ms, buf = sink()
            s = lsgd_solver(tau, metrics=ms)
            s.train_round({"data": data[:tau].copy(),
                           "label": labels[:tau].copy()})
            d = s.last_divergence
            s.close()
            assert d is not None, f"no divergence measured at tau={tau}"
            assert d["kind"] == "params" and d["tau"] == tau
            assert len(d["per_worker"]) == 2
            ev = next(e for e in events_of(buf)
                      if e["event"] == "divergence")
            assert ev["mean"] == d["mean"]      # event hit the JSONL
            assert len(ev["worker_loss"]) == 2
            means.append(d["mean"])
        assert means[0] > 0, "identical-start workers measured zero drift"
        assert all(b >= a for a, b in zip(means, means[1:])), \
            f"divergence not monotone in tau: {means}"

    def test_divergence_aux_costs_no_host_gather(self):
        """The per-round divergence event carries only scalars/short
        vectors — never weight-sized payloads."""
        ms, buf = sink()
        s = lsgd_solver(2, metrics=ms)
        data, labels = self._round_batches()
        s.train_round({"data": data[:2], "label": labels[:2]})
        s.close()
        ev = next(e for e in events_of(buf) if e["event"] == "divergence")
        assert len(json.dumps(ev)) < 2048


# ------------------------------------------ straggler via chaos stall (e2e)

class TestStragglerInjection:
    def test_chaos_stall_names_slow_worker(self):
        from sparknet_tpu.resilience.chaos import ChaosMonkey, install_chaos
        install_chaos(ChaosMonkey(stall_step=0, stall_s=0.3, stall_worker=1,
                                  stall_repeat=True,
                                  log_fn=lambda *a: None))
        try:
            ms, buf = sink()
            s = lsgd_solver(2, metrics=ms)
            assert s.chaos is not None
            s.arm_health(straggler_factor=1.3, straggler_min_s=0.05,
                         cooldown=1)
            rs = np.random.RandomState(1)
            batches = {"data": rs.randn(2, 16, 16).astype(np.float32),
                       "label": rs.randint(0, 4, (2, 16)).astype(np.int32)}
            for _ in range(3):
                s.train_round(dict(batches))
            s.close()
        finally:
            install_chaos(None)
        evs = events_of(buf)
        stragglers = [e for e in evs if e["event"] == "health"
                      and e["kind"] == "straggler"]
        assert stragglers, "straggler alarm never fired"
        assert all(e["worker"] == 1 for e in stragglers)
        assert stragglers[0]["ratio"] >= 1.3
        # and the report renders the named straggler in training health
        rep = obs_report.aggregate(evs)
        assert rep["health"]["worst_straggler"] == 1
        text = obs_report.render(rep)
        assert "training health" in text and "straggler: worker 1" in text


# -------------------------------------------------- HealthMonitor (unit)

class TestHealthMonitor:
    def test_straggler_detection_and_cooldown(self):
        ms, buf = sink()
        hm = HealthMonitor(ms, log_fn=None, straggler_factor=1.5,
                           straggler_min_s=0.01, cooldown=3)
        for r in range(4):
            hm.observe_round(r, round_idx=r,
                             latencies=[0.1, 0.1, 0.5, 0.1])
        evs = [e for e in events_of(buf) if e["event"] == "health"]
        assert len(evs) == 2            # obs 1 fires, 2-3 cooled, 4 fires
        assert all(e["kind"] == "straggler" and e["worker"] == 2
                   for e in evs)
        assert hm.straggler_counts[2] == 4   # counted even while cooled

    def test_straggler_needs_margin_and_factor(self):
        ms, buf = sink()
        hm = HealthMonitor(ms, log_fn=None, straggler_factor=1.5,
                           straggler_min_s=0.05, cooldown=1)
        hm.observe_round(0, latencies=[0.10, 0.11])      # under min_s
        hm.observe_round(1, latencies=[1.00, 1.30])      # under factor
        hm.observe_round(2, latencies=[0.5])             # one worker
        assert not events_of(buf)

    def test_loss_skew_jump_over_own_ema(self):
        ms, buf = sink()
        hm = HealthMonitor(ms, log_fn=None, loss_skew_factor=3.0,
                           loss_skew_min=0.01, cooldown=1)
        for r in range(5):
            hm.observe_round(r, worker_losses=[1.0, 1.01])
        hm.observe_round(5, worker_losses=[1.0, 2.0])
        evs = [e for e in events_of(buf) if e["event"] == "health"]
        assert len(evs) == 1 and evs[0]["kind"] == "loss_skew"
        assert evs[0]["worker"] == 1          # the off-trend replica

    def test_worker_nonfinite_is_critical_and_arms_recovery(self):
        class FakeSolver:
            recovery = None
            tau = 4
            armed = None

            def arm_recovery(self, **kw):
                self.armed = kw
        ms, buf = sink()
        fs = FakeSolver()
        hm = HealthMonitor(ms, log_fn=None, solver=fs, arm_recovery=True,
                           recovery_kw={"max_rollbacks": 2})
        hm.observe_round(3, worker_losses=[1.0, float("nan")])
        evs = [e for e in events_of(buf) if e["event"] == "health"]
        kinds = {e["kind"] for e in evs}
        assert "worker_nonfinite" in kinds and "recovery_armed" in kinds
        bad = next(e for e in evs if e["kind"] == "worker_nonfinite")
        assert bad["worker"] == 1 and bad["severity"] == "critical"
        assert fs.armed == {"max_rollbacks": 2}

    def test_divergence_trend_suggests_halved_tau(self):
        ms, buf = sink()
        hm = HealthMonitor(ms, log_fn=None, trend_rounds=3,
                           trend_factor=2.0)
        for r, m in enumerate([0.1, 0.25, 0.6]):
            hm.observe_round(r, divergence={"mean": m, "tau": 8})
        evs = [e for e in events_of(buf) if e["event"] == "health"]
        assert len(evs) == 1 and evs[0]["kind"] == "divergence_trend"
        assert evs[0]["suggest_tau"] == 4
        assert hm.summary()["tau_suggestion"] == 4

    def test_divergence_ceiling_is_critical(self):
        ms, buf = sink()
        hm = HealthMonitor(ms, log_fn=None, div_abs=0.5)
        hm.observe_round(0, divergence={"mean": 0.75, "tau": 4})
        ev = [e for e in events_of(buf) if e["event"] == "health"][0]
        assert ev["kind"] == "divergence_high"
        assert ev["severity"] == "critical" and ev["suggest_tau"] == 2

    def test_detectors_never_raise(self):
        hm = HealthMonitor(None, log_fn=None)
        hm.observe_round(0, latencies="not numbers",
                         worker_losses=object(),
                         divergence={"mean": "nan?"})
        assert hm.alarms == 0


# ------------------------------------------------- DivergenceMeter (unit)

class TestDivergenceMeter:
    def test_observe_builds_full_event(self):
        ms, buf = sink()
        dm = DivergenceMeter(ms, topk=2)
        aux = {"div_mean_sq": 0.04, "div_max_sq": 0.09,
               "div_worker_sq": [0.01, 0.09],
               "layer_div_sq": {"fc": 0.03, "conv": 0.01, "bn": 0.0},
               "ref_sq": 4.0, "worker_loss": [1.0, 2.0]}
        ev = dm.observe(10, aux, kind="params", tau=4, round_idx=2)
        assert ev["mean"] == pytest.approx(0.2)
        assert ev["max"] == pytest.approx(0.3)
        assert ev["per_worker"] == [pytest.approx(0.1), pytest.approx(0.3)]
        assert [k for k, _ in ev["top_layers"]] == ["fc", "conv"]
        assert ev["update_norm"] == pytest.approx(2.0)
        assert ev["rel"] == pytest.approx(0.1)            # sqrt(.04/4)
        assert ev["gns_proxy"] == pytest.approx(0.02)     # 2 * .04/4
        assert dm.last is ev and dm.samples == 1
        logged = events_of(buf)[0]
        assert logged["event"] == "divergence" and logged["tau"] == 4

    def test_observe_skips_without_divergence_fields(self):
        dm = DivergenceMeter(None)
        assert dm.observe(0, {"worker_loss": [1.0]}) is None
        assert dm.observe(0, None) is None and dm.samples == 0

    def test_tree_sq_dist_groups_by_layer(self):
        from sparknet_tpu.obs import tree_sq_dist
        a = {"fc": {"w": np.ones((2, 2), np.float32)},
             "bias": {"b": np.zeros(3, np.float32)}}
        b = {"fc": {"w": np.zeros((2, 2), np.float32)},
             "bias": {"b": np.zeros(3, np.float32)}}
        per, total = tree_sq_dist(a, b)
        assert float(per["fc"]) == pytest.approx(4.0)
        assert float(per["bias"]) == pytest.approx(0.0)
        assert float(total) == pytest.approx(4.0)


# ------------------------------------------------- comms edge cases (sat)

class TestCommsEdgeCases:
    def test_world_size_one_and_zero_bytes_are_zero(self):
        for fn in (ring_allreduce_bytes, broadcast_collect_bytes,
                   all_to_all_bytes):
            assert fn(1 << 20, 1) == 0
            assert fn(0, 8) == 0
            assert fn(0, 1) == 0
            assert fn(1 << 20, 4) > 0

    def test_register_zero_byte_collective_is_noop(self):
        ms, buf = sink()
        cm = CommsMeter(ms)
        assert cm.register("avg", 0) is None
        assert cm.register("avg", ring_allreduce_bytes(100, 1)) is None
        assert cm.collectives == []
        assert cm.collective_bytes_per_step() == 0
        # steps_per_round=0 must not divide by zero downstream
        c = cm.register("avg", 100, steps_per_round=0)
        assert c["steps_per_round"] == 1
        assert cm.collective_bytes_per_step() == 100


# ------------------------------------------------- MemoryMonitor (unit)

class TestMemoryMonitor:
    def test_sample_emits_memstats(self):
        ms, buf = sink()
        from sparknet_tpu.obs.trace import Tracer
        tr = Tracer(None)
        mm = MemoryMonitor(ms, tracer=tr)
        f = jax.jit(lambda a: a + 1)
        # the step's programs are counted from the tracer's records
        zeros = jax.numpy.zeros((64, 64), jax.numpy.float32)
        with tr.step("solver.step", 5, "solver.prep") as span:
            span.phase("solver.enqueue")
            x = f(zeros)
        x.block_until_ready()
        ev = mm.sample(5)
        assert ev["iter"] == 5 and ev["compile_cache"] == 1
        assert ev["live_arrays"] >= 1 and ev["live_bytes"] > 0
        assert ev["host_rss_bytes"] > 0
        assert mm.peak_live_bytes >= ev["live_bytes"] > 0
        logged = events_of(buf)
        assert logged and logged[-1]["event"] == "memstats"

    def test_sample_cadence_and_force(self):
        mm = MemoryMonitor(None, sample_every=3)
        assert mm.sample(0) is not None
        assert mm.sample(1) is None and mm.sample(2) is None
        assert mm.sample(3) is not None
        assert mm.sample(4, force=True) is not None


# ------------------------------------- report/monitor error paths (sat)

class TestReportErrors:
    def test_missing_file_raises_metrics_file_error(self, tmp_path):
        with pytest.raises(MetricsFileError, match="cannot read"):
            obs_report.load_events(str(tmp_path / "nope.jsonl"))

    def test_empty_file(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        with pytest.raises(MetricsFileError, match="no parseable events"):
            obs_report.report_file(str(p))

    def test_garbage_lines_skipped_with_count(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('garbage{{{\n'
                     '{"event": "train", "iter": 1, "loss": 2.0}\n'
                     '{"event": "train", "it\n'
                     '[1, 2]\n')
        events, bad = obs_report.load_events(str(p))
        assert len(events) == 1 and bad == 3
        rep = obs_report.aggregate(events)
        rep["malformed_lines"] = bad
        assert "3 malformed" in obs_report.render(rep)

    def test_report_cli_one_line_error(self, tmp_path, capsys):
        from sparknet_tpu.cli import main
        rc = main(["report", str(tmp_path / "missing.jsonl")])
        assert rc == 2
        err = capsys.readouterr().err
        assert "sparknet report: error" in err
        assert "Traceback" not in err

    def test_monitor_cli_once(self, tmp_path, capsys):
        from sparknet_tpu.cli import main
        rc = main(["monitor", str(tmp_path / "missing.jsonl"), "--once"])
        assert rc == 2
        assert "sparknet monitor: error" in capsys.readouterr().err
        p = tmp_path / "m.jsonl"
        p.write_text(
            '{"event": "train", "iter": 3, "loss": 1.5}\n'
            'trunc{"a"\n'
            '{"event": "health", "kind": "straggler", "worker": 1,'
            ' "ratio": 2.0}\n')
        rc = main(["monitor", str(p), "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "iter 3" in out and "straggler" in out
        assert "1 bad lines" in out


# ------------------------------------------------------- monitor (unit)

class TestMonitorTail:
    def test_partial_trailing_line_buffered(self, tmp_path):
        p = tmp_path / "t.jsonl"
        p.write_text('{"event": "a"}\n{"ev')
        tail = _Tail(str(p))
        assert tail.poll() == ['{"event": "a"}']
        with open(p, "a") as f:
            f.write('ent": "b"}\n')
        assert tail.poll() == ['{"event": "b"}']
        assert tail.poll() == []

    def test_truncation_reopens_from_start(self, tmp_path):
        p = tmp_path / "t.jsonl"
        p.write_text('{"event": "a"}\n{"event": "b"}\n')
        tail = _Tail(str(p))
        tail.poll()
        p.write_text('{"event": "c"}\n')
        assert tail.poll() == ['{"event": "c"}']

    def test_state_folds_and_renders(self):
        st = MonitorState()
        st.update({"event": "round", "round": 3, "iter": 15, "loss": 2.1})
        st.update({"event": "divergence", "mean": 0.01, "max": 0.02,
                   "tau": 5, "worker_loss": [2.0, 2.2],
                   "top_layers": [["fc", 0.01]]})
        st.update({"event": "health", "kind": "straggler", "worker": 1,
                   "ratio": 3.0})
        st.update({"event": "health", "kind": "straggler", "worker": 1,
                   "ratio": 2.5})
        st.update({"event": "summary"})
        text = st.render("x.jsonl")
        assert "round 3" in text and "loss 2.1" in text
        assert "divergence: mean 0.01" in text and "tau=5" in text
        assert "worker 1 flagged 2x" in text
        assert "last alarm: [straggler]" in text
        assert "FINISHED" in text

    def test_monitor_file_missing_and_once(self, tmp_path):
        with pytest.raises(MetricsFileError):
            monitor_file(str(tmp_path / "none.jsonl"), once=True)
        p = tmp_path / "m.jsonl"
        p.write_text('{"event": "train", "iter": 1, "loss": 9.0}\n')
        got = []
        st = monitor_file(str(p), once=True, out=got.append)
        assert st.events == 1 and "iter 1" in got[0]

    def test_monitor_live_loop_ingests_on_tailer_thread(self, tmp_path):
        # the live view runs a background tailer (MonitorState is
        # lock-guarded — the discipline `sparknet lint` SPK201 checks);
        # events appended mid-run must land in the final state
        p = tmp_path / "m.jsonl"
        p.write_text('{"event": "train", "iter": 1, "loss": 9.0}\n')
        import threading

        def append_late():
            with open(p, "a") as f:
                f.write('{"event": "train", "iter": 2, "loss": 8.0}\n')
                f.write("garbage not json\n")
        t = threading.Timer(0.15, append_late)
        t.start()
        got = []
        st = monitor_file(str(p), interval=0.05, duration=0.6,
                          out=got.append, clear=False)
        t.join()
        assert st.events == 2 and st.bad_lines == 1
        assert st.iter == 2 and any("iter 2" in s for s in got)


# -------------------------------------------- device-cache gauge (sat)

class TestDeviceCacheGauge:
    def _make_db(self, path, n=24):
        from sparknet_tpu.data.lmdb import LMDBWriter
        from sparknet_tpu.data.datum import array_to_datum
        rs = np.random.RandomState(0)
        imgs = rs.randint(0, 256, (n, 3, 8, 8)).astype(np.uint8)
        with LMDBWriter(path) as w:
            for i in range(n):
                w.put(b"%08d" % i, array_to_datum(imgs[i], i % 4))

    def test_resident_cache_emits_hit_gauge(self, tmp_path):
        from sparknet_tpu.data.db_source import DatumBatchSource
        from sparknet_tpu.data.device_cache import (DeviceCachedSource,
                                                    maybe_device_cache)
        self._make_db(str(tmp_path / "db"))
        ms, buf = sink()
        src = DatumBatchSource(str(tmp_path / "db"), 8,
                               device_transform=True)
        cached = maybe_device_cache(src, metrics=ms)
        assert isinstance(cached, DeviceCachedSource)
        it = iter(cached)
        for _ in range(3):
            next(it)
        cached.close()
        evs = [e for e in events_of(buf) if e["event"] == "device_cache"]
        assert evs[0]["resident"] is True and evs[0]["records"] == 24
        assert evs[-1]["hits"] == 3 and evs[-1]["hit_rate"] == 1.0
        assert evs[-1]["misses"] == 0

    def test_refused_promotion_logs_all_miss_gauge(self, tmp_path):
        from sparknet_tpu.data.db_source import DatumBatchSource
        from sparknet_tpu.data.device_cache import maybe_device_cache
        self._make_db(str(tmp_path / "db"))
        ms, buf = sink()
        src = DatumBatchSource(str(tmp_path / "db"), 8,
                               device_transform=True)
        assert maybe_device_cache(src, budget_mb=1e-6, metrics=ms) is src
        ev = [e for e in events_of(buf) if e["event"] == "device_cache"][0]
        assert ev["resident"] is False and ev["reason"] == "over_budget"
        assert ev["hits"] == 0 and ev["hit_rate"] == 0.0
