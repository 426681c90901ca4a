"""The control that tells an index-picked key set from a window and from
dense attention (the `keye_vl2_30b_a3b` configuration's second control):

    python3 benchmark/control_selection.py --workload <cell> --seeds 1,2 \
        [--forms window,dense]

For each seed: the plain reference through the three checked steps, then
the same reference with every query's key set replaced by its last `topk`
keys (`window`) and by all its keys (`dense`), each compared with the true
reference by `check.compare` under the cell's limits. Both must come out
as not correct, else the limits could not tell this model from a
sliding-window or a dense one. No solver is built; never run by the
benchmark itself. The exit code is 0 when every substitute failed a limit.
`--forms index_bf16` is a look and no control: the set the program's
bfloat16 indexer picks, every other number float32, which reads what keys
flipped at a query's threshold do alone and has to PASS the limits."""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness
from harness import say

FORMS = ("window", "dense")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    if "selection" not in getattr(cell.ref, "d", {}):
        raise SystemExit(f"benchmark: the reference of {cell.name} has no "
                         "`selection` to replace")

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
        # on the host while a substitute runs: the device holds one
        # reference at a time
        want = jax.device_get(harness.run_reference(cell, seed, inputs))
        for form in args.forms.split(","):
            cell.ref.d = dict(sizes, selection=form)
            try:
                got = harness.run_reference(cell, seed, inputs)
            finally:
                cell.ref.d = sizes
            rows = check.compare(got, want, cell.limits, cell.specs)
            del got
            ok = all(r[3] for r in rows)
            all_failed = all_failed and not ok
            say("# " + json.dumps({
                "seed": seed, "selection": form, "control_correct": ok,
                "control": {n: [v, lim, note]
                            for n, v, lim, _, note in rows}}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
