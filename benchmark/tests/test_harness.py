"""CPU tests of the benchmark's own code. Run by hand:

    python -m pytest benchmark/tests -q

Nothing here loads the TPU library at import, and nothing under tests/
knows of this file."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_py(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                           *args], capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=900)


def test_names_and_units_hold_only_allowed_characters():
    b = spec()
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics] \
        + [w["name"] for w in b["workloads"]] \
        + [c["name"] for c in b["configs"]] \
        + [w["traffic"] for w in b["workloads"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["bound"] <= 0.1 for m in b["end_to_end"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in b["per_layer"])


def test_every_cell_finds_its_files_by_name():
    b = spec()
    for w in b["workloads"]:
        cell = json.load(open(os.path.join(BENCH, "workloads",
                                           w["name"] + ".json")))
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        cfg = json.load(open(os.path.join(BENCH, "configs",
                                          w["config"] + ".json")))
        traffic = json.load(open(os.path.join(BENCH, "traffic",
                                              w["traffic"] + ".json")))
        for rel in (f"reference/{cfg['reference']}.py",
                    f"feeds/{traffic['feed']}.py"):
            assert os.path.exists(os.path.join(BENCH, rel)), rel
    for m in b["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        assert os.path.exists(path), path
        scope = {}
        exec(compile(open(path).read(), path, "exec"), scope)
        assert {k: m[k] for k in scope["META"]} == scope["META"]
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


@pytest.mark.parametrize("config,gflop", [("caffenet", 4.35),
                                          ("googlenet", 9.56)])
def test_flops_per_image_from_shapes(config, gflop):
    import flops
    cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    got = flops.train_flops_per_sample(cfg) / 1e9
    assert abs(got - gflop) / gflop < 0.02, got


@pytest.mark.parametrize("config", ["caffenet", "googlenet"])
def test_reference_lists_the_program_s_train_layers_in_order(config):
    """The dropout stream is told by a layer's position, so the plain
    list and the program's TRAIN net have to agree name by name."""
    import importlib
    from sparknet_tpu.graph.compiler import CompiledNet, TRAIN
    cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    mod, fn = cfg["builder"].split(":")
    net = CompiledNet(getattr(importlib.import_module(mod), fn)(
        batch_size=2, num_classes=10), TRAIN)
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    assert [lp.name for lp, *_ in net.layers] == \
        [l["name"] for l in ref.layers(10)]


def test_p95_and_rate_arithmetic():
    import timing
    assert timing.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert timing.percentile([3.0], 95) == 3.0
    starts = [0.0, 1.0, 2.0, 3.0]
    ends = [1.0, 2.0, 3.0, 5.0]         # the last unit took twice as long
    m = timing.window_metrics(starts, ends, sync_every=5, batch=10)
    assert m["train_rate"] == pytest.approx(4 * 5 * 10 / 5.0)
    assert m["step_ms_median"] == pytest.approx(200.0)
    assert m["step_ms_p95"] == pytest.approx(200 + 0.85 * 200)


def hand_made_events():
    dev0, dev1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    ops = "XLA Ops"
    return [
        (host, "python3", "bench.unit", 0.0, 100.0),
        (host, "python3", "bench.feed_next", 0.0, 10.0),
        (host, "python3", "bench.train_step", 10.0, 30.0),
        (host, "python3", "bench.sync", 40.0, 60.0),
        # device 0: busy 12-50 (two overlapping ops) and 60-90
        (dev0, ops, "%fusion.1 = f32[8]{0} fusion(...)", 12.0, 30.0),
        (dev0, ops, "%convolution.2 = f32[8]{0} convolution(...)", 30.0,
         20.0),
        (dev0, ops, "%fusion.1 = f32[8]{0} fusion(...)", 60.0, 30.0),
        (dev0, "XLA Modules", "jit_step(1)", 12.0, 78.0),   # not an op
        # device 1: busy 20-100
        (dev1, ops, "%fusion.1 = f32[8]{0} fusion(...)", 20.0, 80.0),
        # outside the unit: clipped away
        (dev0, ops, "%copy.9 = f32[8]{0} copy(...)", 150.0, 10.0),
    ]


def test_trace_reduction_on_hand_made_events():
    import trace_reduce
    r = trace_reduce.reduce_events(hand_made_events())
    assert r["devices"] == 2 and r["units"] == 1
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0 busy 38 + 30, device 1 busy 80 -> mean 74
    assert r["busy_s"] == pytest.approx(74e-9)
    assert r["idle_pct"] == pytest.approx(26.0)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 30 + 80) / 2 * 1e-9)
    assert ops["convolution.2"] == pytest.approx(10e-9)
    assert "copy.9" not in ops and "jit_step(1)" not in ops
    gaps = dict(r["idle_gaps"])
    # dev0: 0-12 (10 under feed_next, 2 under train_step), 50-60 and
    # 90-100 under sync; dev1: 0-20 (10 feed, 10 train_step -> first max)
    assert gaps["bench.sync"] == pytest.approx(20 / 2 * 1e-9)
    assert gaps["bench.feed_next"] == pytest.approx((12 + 20) / 2 * 1e-9)
    assert trace_reduce.reduce_events(
        [e for e in hand_made_events() if "device" not in e[0]]) is None


def test_layer_metric_readers_return_nothing_without_their_source():
    import importlib
    ctx = {"feed_wait_s": [], "dispatch_s": [], "trace": None,
           "traffic": {}, "compile_s": None}
    for name in ("input_wait_ms", "dispatch_ms", "compile_s", "mxu_share",
                 "device_idle_pct"):
        assert importlib.import_module(
            f"layer_metrics.{name}").read(ctx) is None
    tr = {"units": 2, "busy_s": 1.0, "idle_pct": 4.0}
    ctx = {"trace": tr, "sync_every": 5, "flops_per_step": 1e12,
           "chips": 1, "peak": {"bf16_flops": 100e12}}
    from layer_metrics import mxu_share
    assert mxu_share.read(ctx) == pytest.approx(10.0)


def test_without_a_tpu_no_result():
    r = run_py("--workload", "caffenet_b1536_resident", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "no TPU" in r.stderr


def test_rehearsal_of_cell_1_runs_to_its_end():
    r = run_py("--workload", "caffenet_b1536_resident", "--seed",
               "3000000019", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "correct=True" in r.stdout and "metrics" not in r.stdout
