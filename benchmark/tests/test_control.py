"""The correctness check has to be able to fail (CPU, toy sizes):

- the control — the plain reference in float8, put in the program's place —
  comes out as not correct under the configuration's own limits;
- a run whose timed path is broken underneath (a step that returns its
  state unchanged; a step that trains on half the batch) reports
  `correct` false, with the harness's look for a chip skipped and the
  rest of the run driven as it is.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELL = "caffenet_b1536_resident"


def test_the_float8_control_is_not_correct():
    import check
    import harness
    cell = harness.Cell(CELL, rehearse=True)
    timed = harness.Timed(cell, 7)
    try:
        inputs = timed.reference_inputs()
    finally:
        timed.free()
    want = harness.run_reference(cell, 7, inputs)
    low = harness.run_reference(cell, 7, inputs, control=True)
    rows = check.compare(low, want, cell.limits, cell.specs)
    assert not all(r[3] for r in rows), rows


def _rehearse(capsys):
    import run
    rc = run.main(["--workload", CELL, "--seed", "11", "--trace", "0",
                   "--rehearse"])
    got = capsys.readouterr()
    return rc, got.out + got.err        # the check's rows stand on stderr


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from sparknet_tpu.solver.solver import Solver
    real = Solver._train_step_fn

    def frozen(self):
        step = real(self)

        def same_state(params, state, history, batch, it, rng):
            _, state2, _, loss, it2 = step(params, state, history, batch,
                                           it, rng)
            return params, state2, history, loss, it2
        return same_state
    monkeypatch.setattr(Solver, "_train_step_fn", frozen)
    rc, out = _rehearse(capsys)
    assert rc == 1 and "correct=False" in out
    assert "FAILED" in out


def test_a_step_that_leaves_out_half_the_batch_is_not_correct(
        monkeypatch, capsys):
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
    rc, out = _rehearse(capsys)
    assert rc == 1 and "correct=False" in out


def test_the_sound_rehearsal_is_correct(capsys):
    rc, out = _rehearse(capsys)
    assert rc == 0 and "correct=True" in out
