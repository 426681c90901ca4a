"""CPU tests of the per-layer metrics that read the program's own spans
(layer_metrics/step_prep_ms.py and its five siblings, program_spans.py).
Run by hand:

    python -m pytest benchmark/tests -q
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

NAMES = ("step_prep_ms", "step_host_ms_max", "prefetch_wait_ms",
         "prefetch_produce_ms", "solver_init_s", "step_programs")
MS = 1_000_000                      # ns


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}")


@pytest.fixture
def tracer(monkeypatch):
    """A tracer of the test's own in the place of the process-wide one."""
    import program_spans
    from sparknet_tpu.obs.trace import Tracer
    tr = Tracer(None)
    monkeypatch.setattr(program_spans, "default_tracer", lambda: tr)
    return tr


@pytest.mark.parametrize("name", NAMES)
def test_meta_equals_the_benchmark_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    cells = entry.pop("workloads")
    assert reader(name).META == entry
    assert "caffenet_b1536_hostfed" in cells
    assert (len(cells) == 1) == name.startswith("prefetch_")


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_on_an_empty_tracer(name, tracer):
    assert reader(name).read({"dispatch_s": [0.001] * 3}) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_where_the_program_has_no_tracer(
        name, monkeypatch):
    import program_spans
    monkeypatch.setattr(program_spans, "default_tracer", lambda: None)
    assert reader(name).read({"dispatch_s": [0.001] * 3}) is None


def hand_made(tr):
    """Set-up (init with a helper program built under it, two programs
    built under warm-up steps), then a window of three steps; the program
    built inside the window does not count as built before it."""
    t = tr.now_ns()
    tr.record("solver.init", t, t + 2500 * MS)
    with tr.hot_span("net.init"):
        tr.record("compile.backend", t, t + MS,
                  fun_name="jit(_threefry_split)")
    t += 3000 * MS

    def step(t, it, prep_ms, enqueue_ms, built=0):
        tr.record("solver.prep", t, t + prep_ms * MS, iter=it)
        with tr.hot_span("solver.enqueue"):     # the parent of what it builds
            for _ in range(built):
                tr.record("compile.cache_load", t + prep_ms * MS,
                          t + (prep_ms + 1) * MS)
                tr.record("compile.backend", t + prep_ms * MS,
                          t + (prep_ms + 1) * MS, fun_name="jit(step)")
        tr.record("solver.step", t, t + (prep_ms + enqueue_ms) * MS, iter=it)

    step(t, 0, 5, 1000, built=1)                    # warm-up: not the window
    step(t + 2000 * MS, 1, 5, 900, built=1)
    t += 4000 * MS
    for it, (prep, enq, built) in enumerate(
            [(1, 2, 0), (9, 1, 1), (2, 40, 0)], start=2):
        step(t, it, prep, enq, built)
        t += 100 * MS
    for w, p in [(50, 70), (0.5, 30), (0.25, 50), (0.75, 40)]:
        tr.record("prefetch.wait", t, t + int(w * MS))
        tr.record("prefetch.produce", t, t + p * MS, bytes=302_000_000)


def test_readers_on_hand_made_records(tracer):
    hand_made(tracer)
    ctx = {"dispatch_s": [0.003, 0.010, 0.042]}     # a window of 3 steps
    read = {n: reader(n).read(ctx) for n in NAMES}
    assert read["step_prep_ms"] == pytest.approx(2.0)       # median 1 2 9
    assert read["step_host_ms_max"] == pytest.approx(42.0)
    assert read["prefetch_wait_ms"] == pytest.approx(0.5)   # last 3 gets
    assert read["prefetch_produce_ms"] == pytest.approx(40.0)
    assert read["solver_init_s"] == pytest.approx(2.5)
    assert read["step_programs"] == 2


def test_rehearsal_of_the_hostfed_cell_still_runs_to_its_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "caffenet_b1536_hostfed", "--rehearse", "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "correct=True" in r.stdout and "metrics" not in r.stdout
