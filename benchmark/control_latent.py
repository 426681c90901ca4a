"""The controls that tell latent attention from what is left of it without
its mechanism (the `glm_4_7_flash` configuration's second control):

    python3 benchmark/control_latent.py --workload <cell> --seeds 1,2

For each seed: the plain reference through the three checked steps, then
the same reference twice more, each time with one thing taken out —
`shared_rope_key` false: the ONE rotary key a token that every head shares
set to zero, so that a key is its head's non-rotary part alone and the
queries' rotary part meets nothing; `kv_latent_norm` false: the key-value
latent used as W_kva gives it, without its RMSNorm — compared with the true
reference by `check.compare` under the cell's limits. Each must come out as
not correct, else the limits could not tell a model with its shared rotary
key from one without. No solver is built; never run by the benchmark
itself. The exit code is 0 when every substitute failed a limit on every
seed."""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness
from harness import say

FORMS = ("shared_rope_key", "kv_latent_norm")


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
    forms = args.forms.split(",")
    sizes = getattr(cell.ref, "d", {})
    missing = sorted(f for f in forms if f not in FORMS or f not in sizes)
    if missing:
        raise SystemExit(f"benchmark: the reference of {cell.name} has no "
                         f"{', '.join(missing)} to take out; the forms are "
                         f"{', '.join(FORMS)}")

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
        # on the host while a substitute runs: the device holds one
        # reference at a time
        want = jax.device_get(harness.run_reference(cell, seed, inputs))
        for form in forms:
            cell.ref.d = dict(sizes, **{form: False})
            try:
                got = harness.run_reference(cell, seed, inputs)
            finally:
                cell.ref.d = sizes
            rows = check.compare(got, want, cell.limits, cell.specs)
            del got
            ok = all(r[3] for r in rows)
            all_failed = all_failed and not ok
            say("# " + json.dumps({
                "seed": seed, form: False, "control_correct": ok,
                "control": {n: [v, lim, note]
                            for n, v, lim, _, note in rows}}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
