"""The readings that the limits of the correctness check are set from, and
the control that has to come out as not correct:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

For each seed, in one process: the timed object's first three steps against
the plain reference (the sound reading), and the reference put in the
program's place in the nearest precision below the ones the configuration
states — float8_e4m3 blobs for bfloat16, bfloat16 stored weights and
momentum for float32 — against the same reference (the control's reading). No measured window: training's check needs none.
The benchmark's own runs never call this; PERF.md holds the readings.
"""

import argparse
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
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds only")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dir", default=harness.HERE)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = harness.Cell(args.workload, rehearse=args.rehearse,
                        here=os.path.abspath(args.dir))

    import check
    harness.find_device(cell.chips, args.rehearse)
    harness.configure_cache()
    limits = cell.limits
    seeds = [int(s) for s in args.seeds.split(",")]
    n_control = len(seeds) if args.control_seeds is None \
        else args.control_seeds
    timed, readings = None, []
    for i, seed in enumerate(seeds):
        if timed is None:
            timed = harness.Timed(cell, seed)
        else:
            timed.reseed(seed)
        got, _ = timed.checked_steps()
        inputs = timed.reference_inputs()
        want = harness.run_reference(cell, seed, inputs)
        row = {"seed": seed, "sound": {
            n: v for n, v, *_ in check.compare(got, want, limits,
                                               cell.specs)}}
        if i < n_control:
            low = harness.run_reference(cell, seed, inputs, control=True)
            rows = check.compare(low, want, limits, cell.specs)
            row["control"] = {n: v for n, v, *_ in rows}
            row["control_correct"] = all(r[3] for r in rows)
        readings.append(row)
        say("# " + json.dumps(row))
    timed.free()
    names = list(readings[0]["sound"])
    for n in names:
        sound = max(r["sound"][n] for r in readings)
        ctl = [r["control"][n] for r in readings if "control" in r]
        say(f"# {n}: largest sound {sound:.6g} over {len(readings)} seeds; "
            f"smallest control {min(ctl):.6g} over {len(ctl)} seeds; "
            f"ratio {min(ctl) / sound:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
