"""The control that tells a state-space layer from a window of one chunk
(the `nemotron_twotower_30b_a3b` configuration's second control):

    python3 benchmark/control_carry.py --workload <cell> --seeds 1,2

For each seed: the plain reference through the three checked steps, then
the same reference with the carry between chunks dropped (every chunk of
`chunk_size` tokens starts from a zero state), compared with the true
reference by `check.compare` under the cell's limits. It must come out as
not correct, else the limits could not tell a scan that forgets its state
at every chunk from one that carries it. No solver is built; never run by
the benchmark itself. The exit code is 0 when the substitute failed a limit
on every seed."""

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
    if "carry" not in getattr(cell.ref, "d", {}):
        raise SystemExit(f"benchmark: the reference of {cell.name} has no "
                         "`carry` to drop")

    import jax
    import check
    harness.find_device(cell.chips, args.rehearse)
    harness.configure_cache()
    feeds = importlib.import_module(f"feeds.{cell.traffic['feed']}")
    all_failed, sizes = True, cell.ref.d
    for seed in (int(s) for s in args.seeds.split(",")):
        feed = feeds.build(traffic=cell.traffic, config=cell.sized_config,
                           seed=seed, solver=None,
                           data_shape=cell.data_shape,
                           num_classes=cell.num_classes)
        inputs = [feed.reference_inputs(i)
                  for i in range(harness.CHECKED_STEPS)]
        # on the host while the substitute runs: the device holds one
        # reference at a time
        want = jax.device_get(harness.run_reference(cell, seed, inputs))
        cell.ref.d = dict(sizes, carry=False)
        try:
            got = harness.run_reference(cell, seed, inputs)
        finally:
            cell.ref.d = sizes
        rows = check.compare(got, want, cell.limits, cell.specs)
        del got
        ok = all(r[3] for r in rows)
        all_failed = all_failed and not ok
        say("# " + json.dumps({
            "seed": seed, "carry": False, "control_correct": ok,
            "control": {n: [v, lim, note] for n, v, lim, _, note in rows}}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
