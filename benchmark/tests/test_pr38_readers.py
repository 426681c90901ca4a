"""PR 38's six readers through the harness on the CPU: the fixture's
rehearsal with their entries in its BENCHMARK.json (a copy: the fixture
stays as it is) runs them, each gives nothing where the trace holds no
device operation, and the run does not fail. The partition itself is tested
in tests/test_step_parts.py. Run by hand: `python -m pytest benchmark/tests
-q`."""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

NEW = ["step_unscoped_ms", "step_recompute_ms", "lm_proj_ms",
       "lm_head_loss_ms", "lm_glue_ms", "lm_scan_carry_ms"]
CELL = "tokens_toy_resident"


def test_the_new_readers_give_nothing_on_the_cpu_and_do_not_fail(
        tmp_path, capsys, monkeypatch):
    import importlib
    shutil.copytree(os.path.join(HERE, "fixtures"), tmp_path / "fx")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mine = {m["name"]: m for m in json.load(f)["per_layer"]}
    path = tmp_path / "fx" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"] += [dict(mine[n], workloads=[CELL]) for n in NEW]
    path.write_text(json.dumps(bench))
    read = []
    for name in NEW:
        mod = importlib.import_module(f"layer_metrics.{name}")

        def spy(ctx, real=mod.read, name=name):
            read.append((name, real(ctx)))
            return read[-1][1]
        monkeypatch.setattr(mod, "read", spy)
    import run
    import step_parts
    step_parts._cache.clear()
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--rehearse",
                   "--trace", "1", "--dir", str(tmp_path / "fx" / "benchmark")])
    got = capsys.readouterr()
    text = got.out + got.err
    assert rc == 0 and "correct=True" in text, text[-3000:]
    assert read == [(name, None) for name in NEW]
    # said once, by whichever reader ran first, and why
    assert text.count("# parts ") == 1 and "# parts none: " in text
