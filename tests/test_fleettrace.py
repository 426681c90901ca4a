"""Fleet observability plane (obs/fleettrace.py, obs/critpath.py, the
`sparknet trace` CLI verb): clock-offset estimation from
heartbeat trace_align beacons under wall jumps and drifting monotonic
clocks, merged-timeline determinism, torn/partial stream recovery,
critical-path straggler attribution against the chaos injectors
(slow_host / slow_worker) end-to-end through REAL coordinators, and the
simfleet path through the same machinery."""

import json
import os
import threading
import time

import pytest

from conftest import REFERENCE  # noqa: F401  (conftest sets the cpu env)

from sparknet_tpu.obs import critpath, fleettrace
from sparknet_tpu.resilience.chaos import ChaosMonkey
from sparknet_tpu.resilience.heartbeat import HeartbeatCoordinator
from sparknet_tpu.sim import FleetSim

HERE = os.path.dirname(os.path.abspath(__file__))


class _Sink:
    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def log(self, event, **fields):
        with self._lock:
            self.events.append(dict(fields, event=event))

    def of(self, kind):
        return [e for e in self.events if e["event"] == kind]


def _beacon(observer, peer, peer_mono, obs_mono, t=None):
    ev = {"event": "trace_align", "observer": observer, "peer": peer,
          "seq": 1, "peer_mono": peer_mono, "peer_stamp": 0.0,
          "obs_mono": obs_mono}
    if t is not None:
        ev["t"] = t
    return ev


def _coord(tmp_path, host, n, metrics=None, chaos=None,
           interval=0.05, lease=1.0):
    return HeartbeatCoordinator(str(tmp_path), host=host, n_hosts=n,
                                interval_s=interval, lease_s=lease,
                                metrics=metrics, chaos=chaos,
                                log_fn=lambda *a: None)


# ------------------------------------------------- offset estimation ----
class TestOffsetEstimation:
    """host 1's monotonic clock reads D seconds AHEAD of host 0's; the
    solved offset must map host-1 monos back onto host 0's timeline:
    offset_1 = -D (ref_time = mono + offset)."""

    D = 5.0

    def _streams(self, d=None, delay=0.001, two_sided=True, n=4):
        d = self.D if d is None else d
        s0, s1 = [], []
        for i in range(n):
            ts = 10.0 + i          # true send time, host-0 frame
            # host 0 observes host 1's beat: peer stamped on host 1's
            # clock (ts + d), received on host 0's clock (ts + delay)
            s0.append(_beacon(0, 1, peer_mono=ts + d,
                              obs_mono=ts + delay))
            if two_sided:
                tr = 10.5 + i
                s1.append(_beacon(1, 0, peer_mono=tr,
                                  obs_mono=tr + d + delay))
        if not s1:
            # the sim shape: host 1 writes metrics but only the
            # observer ever pairs clocks — one-sided alignment
            s1.append({"event": "host_round", "observer": 1, "round": 0,
                       "wait_s": 0.0, "mono": 10.0 + d, "t": 10.0})
        return [s0, s1]

    def test_two_sided_recovers_known_skew_with_error_bar(self):
        ft = fleettrace.merge_streams(self._streams())
        off = ft.offsets[1]
        assert off["aligned"] and not off["one_sided"]
        assert off["offset_s"] == pytest.approx(-self.D, abs=0.01)
        assert off["err_s"] is not None and off["err_s"] <= 0.01
        # host 1's mono maps onto host 0's timeline
        at = ft.place(1, {"event": "relay_io", "host": 1,
                          "mono": 12.0 + self.D})
        assert at == pytest.approx(12.0, abs=0.01)

    def test_one_sided_gives_bound_without_error_bar(self):
        ft = fleettrace.merge_streams(self._streams(two_sided=False))
        off = ft.offsets[1]
        assert off["aligned"] and off["one_sided"]
        assert off["err_s"] is None
        # the bound is biased by at most the delivery delay
        assert off["offset_s"] == pytest.approx(-self.D, abs=0.01)

    def test_offsets_chain_through_intermediate_host(self):
        # 0 <-> 1 at +D, 1 <-> 2 at a further +2.0; no direct 0-2 pair
        s0, s1 = self._streams()
        d2 = self.D + 2.0
        for i in range(4):
            ts = 20.0 + i
            s1.append(_beacon(1, 2, peer_mono=ts + d2,
                              obs_mono=ts + self.D + 0.001))
        s2 = [_beacon(2, 1, peer_mono=20.5 + i + self.D,
                      obs_mono=20.5 + i + d2 + 0.001) for i in range(4)]
        ft = fleettrace.merge_streams([s0, s1, s2])
        assert ft.offsets[2]["offset_s"] == pytest.approx(-d2, abs=0.02)
        # error bars accumulate along the BFS path
        assert ft.offsets[2]["err_s"] >= ft.offsets[1]["err_s"]

    def test_drifting_monotonic_offset_stays_inside_drift_band(self):
        # D drifts 5.000 -> 5.010 across the beacons (clock drift);
        # the estimate lands inside the drift band, not outside it
        s0, s1 = [], []
        for i in range(6):
            d = self.D + 0.010 * i / 5
            ts = 10.0 + i
            s0.append(_beacon(0, 1, peer_mono=ts + d,
                              obs_mono=ts + 0.001))
            s1.append(_beacon(1, 0, peer_mono=ts + 0.4,
                              obs_mono=ts + 0.4 + d + 0.001))
        ft = fleettrace.merge_streams([s0, s1])
        est = ft.offsets[1]["offset_s"]
        assert -self.D - 0.012 <= est <= -self.D + 0.002

    def test_unreachable_host_marked_unaligned(self):
        streams = self._streams()
        streams.append([{"event": "host_round", "observer": 7,
                         "round": 0, "wait_s": 0.0, "t": 1.0}])
        ft = fleettrace.merge_streams(streams)
        assert ft.offsets[7]["aligned"] is False
        assert not ft.aligned(7) and ft.aligned(1)

    @pytest.mark.parametrize("jump", [3600.0, -3600.0])
    def test_wall_jump_does_not_poison_the_wall_fit(self, jump):
        # ten mono-bearing events with wall == mono, then an NTP step
        # moves wall by +-3600 s for a minority tail: the median fit
        # must ignore the stepped samples
        evs = [{"event": "host_round", "observer": 0, "round": i,
                "wait_s": 0.0, "t": float(i), "mono": float(i)}
               for i in range(10)]
        evs += [{"event": "host_round", "observer": 0, "round": 10 + i,
                 "wait_s": 0.0, "t": 100.0 + i + jump,
                 "mono": 100.0 + i} for i in range(3)]
        fit = fleettrace.wall_to_mono(evs)
        assert fit == pytest.approx(0.0, abs=1e-9)
        ft = fleettrace.merge_streams([evs])
        # an event with only wall time places via the (unpoisoned) fit
        at = ft.place(0, {"event": "round", "round": 3, "t": 3.5})
        assert at == pytest.approx(3.5, abs=1e-6)


# ------------------------------------------- merge / chrome synthesis ----
class TestMergeAndChrome:
    def _run_real_pair(self, tmp_path, rounds=2, pre_gate=None,
                       chaos_b=None, sink_b=None):
        """Two REAL coordinators, separate metrics streams, concurrent
        gates — the per-host files a real 2-process run would write."""
        sa, sb = _Sink(), sink_b or _Sink()
        a = _coord(tmp_path, 0, 2, metrics=sa).start()
        b = _coord(tmp_path, 1, 2, metrics=sb, chaos=chaos_b).start()
        errs = []

        def side(coord, pre=None):
            try:
                for r in range(rounds):
                    if pre is not None:
                        pre(coord, r)
                    coord.gate(r, timeout=10)
            except Exception as e:   # pragma: no cover - surfaced below
                errs.append(e)
        tb = threading.Thread(target=side, args=(b, pre_gate))
        tb.start()
        side(a)
        tb.join(timeout=30)
        a.stop()
        b.stop()
        assert not errs and not tb.is_alive()
        return sa.events, sb.events

    def test_heartbeat_emits_throttled_two_sided_beacons(self, tmp_path):
        ea, eb = self._run_real_pair(tmp_path, rounds=3)
        ba = [e for e in ea if e["event"] == "trace_align"]
        bb = [e for e in eb if e["event"] == "trace_align"]
        assert ba and bb                      # both directions observed
        for e in ba:
            assert e["observer"] == 0 and e["peer"] == 1
            assert e["obs_mono"] >= 0 and e["peer_mono"] >= 0
        # throttle: at most ~run_time/lease_s beacons per peer, not one
        # per view() poll (gates poll every interval/4)
        assert len(ba) <= 3 and len(bb) <= 3

    def test_merged_chrome_has_one_track_per_host_with_offsets(
            self, tmp_path):
        ea, eb = self._run_real_pair(tmp_path, rounds=2)
        ft = fleettrace.merge_streams([ea, eb])
        assert ft.hosts == [0, 1]
        doc = fleettrace.chrome_doc(ft)
        names = {e["args"]["name"]: e["pid"]
                 for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert len(names) == 2
        assert any("host 0" in n for n in names)
        assert any("offset" in n for n in names)
        offs = doc["otherData"]["clock_offsets"]
        assert set(offs) == {"0", "1"}
        # same process: solved skew is ~0 within the error bar
        o1 = offs["1"]
        bar = o1["err_s"] if o1["err_s"] is not None else 0.25
        assert abs(o1["offset_s"]) <= bar + 0.25
        gates = [e for e in doc["traceEvents"]
                 if e.get("ph") == "X" and e["name"].startswith("gate")]
        assert len(gates) == 4                # 2 hosts x 2 rounds

    def test_merge_is_deterministic_and_order_independent(self):
        s0 = [{"event": "host_round", "observer": 0, "round": r,
               "wait_s": 0.01 * r, "mono": 1.0 + r, "t": 1.0 + r}
              for r in range(3)]
        s1 = [{"event": "host_round", "observer": 1, "round": r,
               "wait_s": 0.0, "mono": 1.0 + r, "t": 1.0 + r}
              for r in range(3)]
        s1 += [_beacon(1, 0, peer_mono=1.5, obs_mono=1.501)]
        s0 += [_beacon(0, 1, peer_mono=1.6, obs_mono=1.601)]
        one = json.dumps(fleettrace.chrome_doc(
            fleettrace.merge_streams([s0, s1])), sort_keys=True)
        two = json.dumps(fleettrace.chrome_doc(
            fleettrace.merge_streams([s0, s1])), sort_keys=True)
        rev = json.dumps(fleettrace.chrome_doc(
            fleettrace.merge_streams([s1, s0])), sort_keys=True)
        assert one == two == rev

    def test_torn_and_partial_streams_recover(self, tmp_path):
        from sparknet_tpu.obs.report import load_events
        p = tmp_path / "torn.jsonl"
        good = [{"event": "host_round", "observer": 0, "round": 0,
                 "wait_s": 0.0, "mono": 1.0, "t": 1.0},
                {"event": "host_round", "observer": 0, "round": 1,
                 "wait_s": 0.0, "mono": 2.0, "t": 2.0}]
        with open(p, "w") as f:
            f.write(json.dumps(good[0]) + "\n")
            f.write('{"event": "host_round", "obse')   # torn mid-write
            f.write("\n\x00garbage\n")
            f.write(json.dumps(good[1]) + "\n")
        events, bad = load_events(str(p))
        assert bad == 2 and len(events) == 2
        # partial fleet: a second host with NO mono evidence still gets
        # a track, marked unaligned, placed on raw t
        ft = fleettrace.merge_streams(
            [events, [{"event": "host_round", "observer": 1, "round": 0,
                       "wait_s": 0.0, "t": 1.0}]])
        assert ft.hosts == [0, 1]
        assert not ft.aligned(1)
        doc = fleettrace.chrome_doc(ft)
        labels = [e["args"]["name"] for e in doc["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "process_name"]
        assert any("unaligned" in n for n in labels)


# --------------------------------------------------- critical path ------
class TestCritPath:
    def test_slow_host_straggler_named_from_metrics(self, tmp_path):
        """chaos slow_host stalls host 1 at the round-1 gate; the
        merged critpath names host 1 as the blocker from timing alone
        and corroborates with the chaos event."""
        sink_b = _Sink()
        chaos = ChaosMonkey(slow_host=1, slow_host_s=0.4,
                            slow_host_round=1, metrics=sink_b,
                            log_fn=lambda *a: None)
        runner = TestMergeAndChrome()
        ea, eb = runner._run_real_pair(tmp_path, rounds=3,
                                       chaos_b=chaos, sink_b=sink_b)
        ft = fleettrace.merge_streams([ea, eb])
        cp = critpath.compute(ft)
        blocked = [r for r in cp["rounds"] if r["blocker"] is not None]
        assert blocked, cp["rounds"]
        worst = max(blocked, key=lambda r: r["phases"]["gate_wait"])
        assert worst["round"] == 1
        assert worst["blocker"] == 1
        assert worst["chaos"] == "slow_host"
        assert worst["phases"]["gate_wait"] >= 0.3
        top = cp["summary"]["top_blockers"]
        assert top and top[0]["host"] == "1"
        # render() prints the attribution line
        lines = []
        critpath.render(cp, out=lines.append)
        txt = "\n".join(lines)
        assert "blocked on host 1" in txt and "slow_host" in txt

    def test_slow_worker_stall_named_as_compute(self, tmp_path):
        """A slow_worker stall happens in round WORK (outside any
        instrumented phase) — the blocker's dominant phase must come
        out as compute, with the chaos kind corroborated."""
        sink_b = _Sink()
        chaos = ChaosMonkey(slow_worker=1, slow_s=0.4, slow_round=1,
                            metrics=sink_b, log_fn=lambda *a: None)

        def stall(coord, r):
            chaos.maybe_slow_worker(r)
        runner = TestMergeAndChrome()
        ea, eb = runner._run_real_pair(tmp_path, rounds=3,
                                       pre_gate=stall, sink_b=sink_b)
        ft = fleettrace.merge_streams([ea, eb])
        cp = critpath.compute(ft)
        blocked = [r for r in cp["rounds"] if r["blocker"] == 1]
        assert blocked
        worst = max(blocked, key=lambda r: r["phases"]["gate_wait"])
        assert worst["blocker_phase"] == "compute"
        assert any(r["chaos"] == "slow_worker" for r in blocked)

    def test_balanced_round_names_nobody(self):
        s0 = [{"event": "host_round", "observer": 0, "round": 0,
               "wait_s": 0.001, "mono": 1.0, "t": 1.0}]
        s1 = [{"event": "host_round", "observer": 1, "round": 0,
               "wait_s": 0.002, "mono": 1.0, "t": 1.0}]
        cp = critpath.compute(fleettrace.merge_streams([s0, s1]))
        assert cp["rounds"][0]["blocker"] is None
        lines = []
        critpath.render(cp, out=lines.append)
        assert "balanced" in "\n".join(lines)

    def test_round_filter_limits_to_one_round(self):
        s0 = [{"event": "host_round", "observer": 0, "round": r,
               "wait_s": 0.0, "mono": float(r), "t": float(r)}
              for r in range(4)]
        cp = critpath.compute(fleettrace.merge_streams([s0]),
                              round_filter=2)
        assert [r["round"] for r in cp["rounds"]] == [2]


# ----------------------------------------------- simfleet + CLI ---------
class TestSimfleetAndCli:
    def _sim_events(self):
        sink = _Sink()
        FleetSim(hosts=4, rounds=6, interval_s=0.25, lease_s=1.0,
                 round_s=0.3, consensus="none",
                 chaos="slow_worker=2,slow_s=1.0,slow_round=3",
                 metrics=sink).run()
        return sink.events

    def _write(self, tmp_path, events, name="metrics.jsonl"):
        p = tmp_path / name
        with open(p, "w") as f:
            for i, e in enumerate(events):
                f.write(json.dumps(dict(e, t=round(0.01 * i, 4))) + "\n")
        return str(p)

    def test_simfleet_stream_flows_through_the_same_beacon_path(self):
        """1,000-host simulations and 2-host real runs share the merge
        path: sim events land on the virtual timeline, critpath
        computes a summary — zero special cases."""
        ft = fleettrace.merge_streams([self._sim_events()])
        cp = critpath.compute(ft)
        assert cp["summary"]["rounds"] == 6
        assert cp["summary"]["wall_s"] > 0
        # the straggler's extra second shows up as round wall time
        walls = {r["round"]: r["wall_s"] for r in cp["rounds"]
                 if r["wall_s"] is not None}
        assert walls and max(walls.values()) >= 1.0

    def test_cli_trace_critpath_renders_simfleet_cell(self, tmp_path,
                                                      capsys):
        from sparknet_tpu.cli import main
        path = self._write(tmp_path, self._sim_events())
        assert main(["trace", path, "--critpath"]) == 0
        out = capsys.readouterr().out
        assert "critical path" in out
        assert "6 round(s)" in out

    def test_cli_trace_chrome_export_and_summary(self, tmp_path, capsys):
        from sparknet_tpu.cli import main
        s0 = [{"event": "host_round", "observer": 0, "round": 0,
               "wait_s": 0.0, "mono": 1.0, "t": 1.0},
              _beacon(0, 1, peer_mono=1.0, obs_mono=1.001, t=1.0)]
        s1 = [{"event": "host_round", "observer": 1, "round": 0,
               "wait_s": 0.0, "mono": 1.0, "t": 1.0},
              _beacon(1, 0, peer_mono=1.1, obs_mono=1.101, t=1.1)]
        p0 = self._write(tmp_path, s0, "h0.jsonl")
        p1 = self._write(tmp_path, s1, "h1.jsonl")
        out_path = str(tmp_path / "fleet.json")
        assert main(["trace", p0, p1, "--chrome", out_path]) == 0
        doc = json.load(open(out_path))
        assert set(doc["otherData"]["clock_offsets"]) == {"0", "1"}
        names = [e for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert len(names) == 2
        capsys.readouterr()
        assert main(["trace", p0, p1, "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["beacons"] == 2
        assert set(summary["offsets"]) == {"0", "1"}

    def test_cli_trace_missing_file_exits_2(self, tmp_path, capsys):
        from sparknet_tpu.cli import main
        assert main(["trace", str(tmp_path / "nope.jsonl")]) == 2

    def test_report_json_format_has_stable_keys(self, tmp_path, capsys):
        from sparknet_tpu.cli import main
        path = self._write(tmp_path, self._sim_events())
        assert main(["report", path, "--format", "json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["num_events"] > 0
        assert "events_by_type" in rep
        assert rep["fleet"]["critpath"]["rounds"] == 6

    def test_report_text_renders_fleet_timeline_section(self, tmp_path):
        from sparknet_tpu.obs import report as obs_report
        rep = obs_report.aggregate(self._sim_events())
        txt = obs_report.render(rep)
        assert "fleet timeline" in txt

    def test_monitor_renders_the_fleet_line(self):
        from sparknet_tpu.obs.monitor import MonitorState
        st = MonitorState()
        st.update({"event": "trace_align", "observer": 0, "peer": 1,
                   "seq": 1, "peer_mono": 1.0, "peer_stamp": 0.0,
                   "obs_mono": 1.001, "t": 1.0})
        st.update({"event": "host_round", "observer": 0, "round": 2,
                   "wait_s": 0.45, "mono": 2.0, "t": 2.0,
                   "arrived": [1], "dead": []})
        st.update({"event": "host_round", "observer": 1, "round": 2,
                   "wait_s": 0.01, "mono": 2.0, "t": 2.0,
                   "arrived": [0], "dead": []})
        txt = st.render("mem:fleet")
        assert "fleet:" in txt and "beacon" in txt
