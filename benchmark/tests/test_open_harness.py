"""What PR 27 opened, proven without a cell (CPU). Run by hand:

    python -m pytest benchmark/tests -q

The fixture under `fixtures/` is laid out like the benchmark's directory,
beside a BENCHMARK.json of its own that the repo's does not know of: a
token model on (B, S) int32 inputs, Adam, bfloat16 by a solver argument, a
layer of four blobs, a `uniform` filler, a reference with `build`, a tokens
feed, a per-layer reader that reads the trace file itself. Every file of it
is found by name; no file of the harness knows it.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
FIXTURE = os.path.join(HERE, "fixtures", "benchmark")
sys.path[:0] = [BENCH, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELL = "tokens_toy_resident"
SEED = 3000000019                   # beyond 31 bits, as the driver's are


def fixture_cell():
    import harness
    return harness.Cell(CELL, rehearse=True, here=FIXTURE)


def rehearse(capsys, *more):
    import run
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--rehearse",
                   "--dir", FIXTURE, *more])
    got = capsys.readouterr()
    return rc, got.out + got.err


def check_rows(text):
    """{name: value} of the run's `# check` lines."""
    return {line.split()[2]: float(line.split()[4])
            for line in text.splitlines() if line.startswith("# check ")}


# ------------------------------------------------------- BENCHMARK.json

def test_the_repo_s_benchmark_lists_no_fixture():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert "fixtures" not in text and "tokens_toy" not in text


def test_every_per_layer_metric_lists_cells_that_report_what_it_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cells = {w["name"] for w in b["workloads"]}
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["workloads"], m["name"]        # a later cell is not bound
        assert set(m["workloads"]) <= reported[m["moves"]], m["name"]


# --------------------------------------------------------------- weights

def test_uniform_filler_and_the_order_of_keys():
    import jax.numpy as jnp
    import weights
    old = [("a", [((4, 3), ("gaussian", 0.01)), ((4,), ("constant", 1.0))]),
           ("b", [((5, 4), ("xavier",))])]
    new = [("a", [(s, f, (1.0, 1.0)) for s, f in old[0][1]]),
           ("b", [((5, 4), ("xavier",), (2.0, 0.0)),
                  ((64, 8), ("uniform", -0.25, 0.75), (1.0, 1.0))])]
    w_old, w_new = (weights.make_weights(s, SEED) for s in (old, new))
    # a third element and a further blob move no number that was there
    assert all(bool(jnp.array_equal(x, y)) for n in ("a", "b")
               for x, y in zip(w_old[n], w_new[n]))
    u = w_new["b"][1]
    assert -0.25 <= float(u.min()) < -0.2 and 0.7 < float(u.max()) < 0.75
    assert abs(float(u.mean()) - 0.25) < 0.05
    again = weights.make_weights(new, SEED)["b"][1]
    other = weights.make_weights(new, SEED + 1)["b"][1]
    assert bool(jnp.array_equal(u, again))
    assert not bool(jnp.array_equal(u, other))
    with pytest.raises(ValueError, match="msra"):
        weights.make_weights([("c", [((2, 2), ("msra",))])], 1)


# ------------------------------------------- the first gradient, by type

SOLVERS = {
    "SGD": {"type": "SGD", "base_lr": 0.01, "lr_policy": "fixed",
            "momentum": 0.9, "weight_decay": 0.01},
    "Adam": {"type": "Adam", "base_lr": 0.001, "lr_policy": "fixed",
             "momentum": 0.9, "momentum2": 0.999, "delta": 1e-8,
             "weight_decay": 0.01},
}


@pytest.mark.parametrize("kind", sorted(SOLVERS))
def test_first_gradient_out_of_the_program_s_state(kind):
    """The program's solver in float32 takes one step; the gradient
    worked out of its history equals the reference's, taken directly."""
    import check
    import harness
    cell = fixture_cell()
    cell.solver_cfg = SOLVERS[kind]
    cell.config = dict(cell.config, solver_args={})         # float32
    timed = harness.Timed(cell, SEED)
    try:
        got, _ = timed.checked_steps()
        inputs = timed.reference_inputs()
    finally:
        timed.free()
    want = harness.run_reference(cell, SEED, inputs)
    shares = check.leaf_shares(got["grads"], want["grads"])
    assert max(shares) < 1e-4, shares
    assert max(check.leaf_shares(got["dparams"], want["dparams"])) < 1e-3
    for a, b in zip(got["losses"], want["losses"]):
        assert a == pytest.approx(b, rel=1e-5)


def test_a_solver_type_without_a_formula_is_named():
    import check
    with pytest.raises(SystemExit, match="RMSProp"):
        check.first_gradients({}, {}, [], {"type": "RMSProp"})
    from reference import plain
    with pytest.raises(ValueError, match="RMSProp"):
        plain.make_update({"type": "RMSProp", "base_lr": 0.1,
                           "weight_decay": 0.0}, {})


# ----------------------------------------------------------- the trace

def hand_made_events():
    dev, host, ops = "/device:TPU:0", "/host:CPU", "XLA Ops"
    ev = [
        (host, "python3", "bench.unit", 0.0, 200.0),
        (host, "python3", "bench.train_step", 0.0, 120.0),
        (host, "python3", "bench.sync", 120.0, 80.0),
        (host, "python3", "sparknet.solver.step", 5.0, 110.0),
        (host, "python3", "sparknet.solver.prep", 5.0, 20.0),
        (host, "python3", "sparknet.solver.enqueue", 25.0, 90.0),
        # a worker thread's span covers the first gap too: not the host
        # thread that drives the step, so it takes no part of a gap
        (host, "prefetch-worker", "sparknet.prefetch.produce", 0.0, 30.0),
    ]
    # twelve operations back to back from 30 to 150, 10 ns each; the last
    # overlaps an asynchronous copy that runs to 170
    for i in range(12):
        ev.append((dev, ops, f"%fusion.{i} = f32[8]{{0}} fusion(...)",
                   30.0 + 10 * i, 10.0))
    ev.append((dev, ops, "%copy-start.1 = f32[8]{0} copy-start(...)",
               140.0, 30.0))
    return ev


def test_reduction_keeps_every_operation_and_the_program_s_spans():
    import trace_reduce
    r = trace_reduce.reduce_events(hand_made_events())
    assert len(r["device_ops"]) == 10 and len(r["op_seconds"]) == 13
    assert r["op_seconds"]["fusion.11"] == pytest.approx(10e-9)
    assert r["op_seconds"]["copy-start.1"] == pytest.approx(30e-9)
    assert dict(r["device_ops"])["copy-start.1"] == pytest.approx(30e-9)
    # busy is the union, 30 to 170; the names' sum counts the overlap twice
    assert r["busy_s"] == pytest.approx(140e-9)
    assert sum(r["op_seconds"].values()) == pytest.approx(150e-9)
    # gaps: 0-30 (5 bare, 20 under prep inside step, 5 under enqueue
    # inside step) and 170-200 (no program span); by harness span as before
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.train_step": 30e-9, "bench.sync": 30e-9})
    assert dict(r["idle_gaps_program"]) == pytest.approx(
        {"sparknet.solver.prep": 20e-9, "sparknet.solver.enqueue": 5e-9,
         "unattributed": 35e-9})


def test_read_events_keeps_bench_and_program_spans(monkeypatch):
    import types
    import trace_reduce

    def plane(name, *lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=ln, events=[
                types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                for n, s, d in evs]) for ln, evs in lines])
    data = types.SimpleNamespace(planes=[
        plane("/device:TPU:0", ("XLA Ops", [("%fusion.1 = ...", 1, 2)])),
        plane("/host:CPU",
              ("python3", [("bench.unit", 0, 9), ("sparknet.solver.step", 1, 5),
                           ("$threading.py:637 wait", 2, 1)]),
              ("python3", [("sparknet.prefetch.produce", 0, 4)]))])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    got = [(e[1], e[2]) for e in trace_reduce.read_events("x")]
    # two threads' lines of one name are told apart by their position
    assert got == [("XLA Ops", "%fusion.1 = ..."), ("python3/0", "bench.unit"),
                   ("python3/0", "sparknet.solver.step"),
                   ("python3/1", "sparknet.prefetch.produce")]


# ------------------------------------------------- the fixture, end to end

def test_the_fixture_is_found_by_name_and_is_what_it_says():
    import jax.numpy as jnp
    cell = fixture_cell()
    assert cell.ref.inputs == [("data", (8, 16), "int32"),
                               ("label", (8, 16), "int32")]
    assert [len(blobs) for _, blobs in cell.specs] == [2, 4, 2]
    assert cell.specs[0][1][0][1][0] == "uniform"
    import harness
    timed = harness.Timed(cell, SEED)
    try:
        solver = timed.solver
        assert str(solver.param.type) == "Adam"
        assert all(len(h) == 2 for blobs in solver.history.values()
                   for h in blobs)                  # two moments a blob
        batch = next(timed.feed)
        assert batch["data"].dtype == jnp.int32 and \
            batch["data"].shape == (8, 16)
        # bfloat16 reached the net through the constructor's argument
        assert solver.net.compute_dtype == jnp.bfloat16
    finally:
        timed.free()


def test_the_fixture_s_rehearsal_is_correct_and_reads_the_trace(
        capsys, monkeypatch):
    import importlib
    fixture_cell()                      # puts the fixture on the path
    reader = importlib.import_module("layer_metrics.xplane_bytes")
    seen = []

    def read(ctx, real=reader.read):
        seen.append((ctx["xplane"], real(ctx), ctx["op_seconds"],
                     ctx["idle_gaps_program"]))
        return seen[-1][1]
    monkeypatch.setattr(reader, "read", read)
    rc, text = rehearse(capsys, "--trace", "1")
    assert rc == 0 and "correct=True" in text, text[-3000:]
    (path, size, op_seconds, gaps), = seen
    assert path.endswith(".xplane.pb") and size > 0     # there to be read
    assert not os.path.exists(path)                     # and gone after
    assert op_seconds is None and gaps is None  # no device plane on a CPU


def broken_sgd(monkeypatch):
    """The program runs SGD where the configuration states Adam."""
    from sparknet_tpu.solver import updates
    monkeypatch.setattr(updates, "canonical_type", lambda sp: "SGD")


def broken_half(monkeypatch):
    """The step trains on the first half of the sequences, twice."""
    from sparknet_tpu.solver.solver import Solver
    real = Solver._train_step_fn

    def half(self):
        step = real(self)

        def half_batch(params, state, history, batch, it, rng):
            n = batch["label"].shape[0] // 2
            batch = {k: v.at[n:].set(v[:n]) for k, v in batch.items()}
            return step(params, state, history, batch, it, rng)
        return half_batch
    monkeypatch.setattr(Solver, "_train_step_fn", half)


@pytest.mark.parametrize("fault,fails", [
    (broken_sgd, ("grad_worst_leaf_rel_diff", "dparam_worst_leaf_rel_diff")),
    (broken_half, ("loss_step1_rel_gap", "grad_worst_leaf_rel_diff")),
])
def test_a_broken_timed_path_is_not_correct(fault, fails, capsys,
                                            monkeypatch):
    fault(monkeypatch)
    rc, text = rehearse(capsys, "--trace", "0")
    assert rc == 1 and "correct=False" in text, text[-3000:]
    rows = check_rows(text)
    with open(os.path.join(FIXTURE, "configs", "tokens_toy.json")) as f:
        limits = json.load(f)["check"]["limits"]
    limit = {n: limits["loss_rel_gap" if n.startswith("loss") else n]
             for n in rows}
    for name in fails:
        assert rows[name] > limit[name], (name, rows[name])


def test_the_fixture_s_float8_control_is_not_correct():
    import check
    import harness
    cell = fixture_cell()
    timed = harness.Timed(cell, SEED)
    try:
        inputs = timed.reference_inputs()
    finally:
        timed.free()
    want = harness.run_reference(cell, SEED, inputs)
    low = harness.run_reference(cell, SEED, inputs, control=True)
    rows = {r[0]: r for r in check.compare(low, want, cell.limits,
                                           cell.specs)}
    assert not rows["grad_worst_leaf_rel_diff"][3], rows
