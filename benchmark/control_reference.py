"""The control's reading where `control.py` cannot take it: a configuration
whose solver state and float32 reference do not fit on the device side by
side (`control.py` keeps the timed solver there while the reference runs).

    python3 benchmark/control_reference.py --workload <cell> --seeds 1,2

For each seed: the plain reference through the three checked steps, then the
same reference in the nearest precision below the ones the configuration
states (`check.control`), compared by `check.compare` under the cell's
limits. No solver is built; the sound reading of the same seed is on the
last lines of any `run.py` run. Never run by the benchmark itself."""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness
from harness import say


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = harness.Cell(args.workload, rehearse=args.rehearse)

    import jax
    import check
    harness.find_device(cell.chips, args.rehearse)
    harness.configure_cache()
    feeds = importlib.import_module(f"feeds.{cell.traffic['feed']}")
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        feed = feeds.build(traffic=cell.traffic, config=cell.sized_config,
                           seed=seed, solver=None,
                           data_shape=cell.data_shape,
                           num_classes=cell.num_classes)
        inputs = [feed.reference_inputs(i)
                  for i in range(harness.CHECKED_STEPS)]
        # on the host while the control runs: the device holds one
        # reference at a time
        want = jax.device_get(harness.run_reference(cell, seed, inputs))
        low = harness.run_reference(cell, seed, inputs, control=True)
        rows = check.compare(low, want, cell.limits, cell.specs)
        del low
        ok = all(r[3] for r in rows)
        all_failed = all_failed and not ok
        say("# " + json.dumps({
            "seed": seed, "control_correct": ok,
            "control": {n: [v, lim, note] for n, v, lim, _, note in rows}}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
