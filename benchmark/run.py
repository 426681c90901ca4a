"""One cell of BENCHMARK.json, once:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: weights and inputs from --seed, the program's compile cache,
the cell's one step shape warmed by the three steps that the correctness
check reads, the measured window of sync units, then the plain reference.
The last line of stdout is the result object; without a TPU listed in
peaks.json there is none and the exit code is not 0. `--rehearse` drives
the same control flow on the CPU at the cell file's `toy` sizes and never
prints `metrics`.
"""

import time

_T0 = time.perf_counter()           # set-up is counted from process start

import argparse
import importlib
import json
import math
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness
from harness import say

harness.MARKS.last = _T0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dir", default=harness.HERE,
                    help="a directory laid out like this one, beside a "
                         "BENCHMARK.json of its own (the tests' fixtures)")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    cell = harness.Cell(args.workload, rehearse=args.rehearse,
                        here=os.path.abspath(args.dir))
    # the trace stays on disk until the per-layer readers have run
    trace_dir = os.path.join(harness.ROOT, ".bench_trace", cell.name) \
        if args.trace else None
    try:
        return run(args, cell, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def run(args, cell, trace_dir):
    seconds = args.seconds if args.seconds is not None \
        else cell.toy.get("seconds", cell.bench["run_seconds"])

    import jax
    import jax.numpy as jnp
    import check
    import timing
    import trace_reduce

    harness.MARKS.mark("imports")
    devices, peak = harness.find_device(cell.chips, args.rehearse)
    # the TPU runtime's own start-up: 6 to 12 s on the chip tool's machines,
    # drifting by seconds from one set of runs to the next, and nothing a
    # PR of this repo can move; reported, and left out of setup_s
    runtime_start_s = dict(harness.MARKS.spans)["device"]
    cache_dir = harness.configure_cache()
    say(f"# cell {cell.name} seed {args.seed} seconds {seconds} trace "
        f"{args.trace} device {devices[0].device_kind} x{len(devices)} "
        f"cache {cache_dir}")

    # -- set-up ----------------------------------------------------------
    timed = harness.Timed(cell, args.seed)
    try:
        got, first_step_s = timed.checked_steps()
        for _ in range(harness.WARM_UNITS):
            timed.one_unit()
        harness.MARKS.mark("warm units")
        programs_before = timed.programs()
        del timed.feed_wait[:], timed.dispatch[:], timed.losses[:]
        harness.settle()
        harness.MARKS.mark("settle")
        setup_s = time.perf_counter() - _T0 - runtime_start_s

        # -- the window --------------------------------------------------
        starts, ends = timed.window(seconds, trace_dir)
        harness.MARKS.mark("window")
        programs_after = timed.programs()
        step_losses = jax.device_get(jnp.stack(timed.losses))
        feed_stats = timed.feed.stats()
        mem_peak = harness.memory_peak(devices)
        mem_stats = devices[0].memory_stats() or {}
        ref_inputs = timed.reference_inputs()
    finally:
        timed.free()

    # -- after the window ------------------------------------------------
    e2e = timing.window_metrics(starts, ends, cell.sync_every, cell.batch)
    attempted = len(step_losses)
    failed = harness.count_failed(step_losses, programs_before,
                                  programs_after)
    fps = cell.flops_per_sample()
    sample = cell.config["sample"]
    mfu = e2e["train_rate"] * fps / (len(devices) * peak["bf16_flops"])
    slowest = sorted((e - s) * 1e3 / cell.sync_every
                     for s, e in zip(starts, ends))[-3:]
    say(f"# window: {e2e['units']} units of {cell.sync_every} steps, batch "
        f"{cell.batch}, span {e2e['span_s']:.3f} s; step ms median "
        f"{e2e['step_ms_median']:.4f} p95 {e2e['step_ms_p95']:.4f}, "
        f"slowest units {', '.join(f'{x:.3f}' for x in slowest)}")
    say(f"# train_rate {e2e['train_rate']:.2f} {sample}/s, "
        f"{fps / 1e9:.3f} GFLOP/{sample}, MFU {100 * mfu:.2f}% of "
        f"{len(devices)} x {peak['bf16_flops'] / 1e12:.0f} TFLOP/s")
    say(f"# loss first {float(step_losses[0]):.5f} last "
        f"{float(step_losses[-1]):.5f}; programs compiled for the step "
        f"before/after the window: {programs_before}/{programs_after}; "
        f"feed {json.dumps(feed_stats)}")
    say(f"# memory: peak {mem_peak} B; device {json.dumps(mem_stats)}")

    trace = xplane = None
    if args.trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.reduce_events(trace_reduce.read_events(xplane))
        if trace is None and not args.rehearse:
            raise SystemExit("benchmark: the trace holds no operation on a "
                             f"{trace_reduce.DEVICE_PLANE}* plane; no result")
        if trace:
            say(f"# trace {xplane} ({os.path.getsize(xplane)} B, kept for "
                f"the readers): {len(trace['op_seconds'])} operations by "
                f"name, {sum(trace['op_seconds'].values()):.6f} s summed "
                f"against {trace['busy_s']:.6f} s busy; idle by the "
                f"program's spans {json.dumps(trace['idle_gaps_program'])}")

    harness.MARKS.mark("reduce")
    # the reference, now that the program's state is freed
    want = harness.run_reference(cell, args.seed, ref_inputs)
    del ref_inputs
    rows = check.compare(got, want, cell.limits, cell.specs)
    correct = all(r[3] for r in rows) and failed == 0
    harness.MARKS.mark("compare")
    say(f"# seconds: {harness.MARKS}")

    if args.trace:
        ctx = {"feed_wait_s": timed.feed_wait, "dispatch_s": timed.dispatch,
               "compile_s": first_step_s, "trace": trace,
               "op_seconds": (trace or {}).get("op_seconds"),
               "idle_gaps_program": (trace or {}).get("idle_gaps_program"),
               "xplane": xplane, "traffic": cell.traffic, "flops_per_step": fps * cell.batch,
               "peak": peak, "sync_every": cell.sync_every,
               "batch": cell.batch, "chips": len(devices)}
        metrics = {}
        for m in cell.bench["per_layer"]:
            if "workloads" in m and cell.name not in m["workloads"]:
                continue
            value = importlib.import_module(
                f"layer_metrics.{m['name']}").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.bench["end_to_end"]
                   if "workloads" not in m or cell.name in m["workloads"]}
    say(f"# setup_s {setup_s:.3f} (first step {first_step_s:.3f} s) besides "
        f"{runtime_start_s:.3f} s of accelerator runtime start-up; whole "
        f"run {time.perf_counter() - _T0:.1f} s")
    # every number compared beside its limit: the last lines of stderr,
    # and the last key of the result
    for name, value, limit, ok, note in rows:
        print(f"# check {name} = {value:.6g} (limit {limit:g}) "
              f"{'ok' if ok else 'FAILED'}; {note}", file=sys.stderr,
              flush=True)
    if args.rehearse:
        say(f"# rehearsal on {devices[0].platform}: control flow only, "
            f"correct={correct}, no result line")
        return 0 if correct else 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
            "idle_gaps_program": trace["idle_gaps_program"][:10]}
    result["check"] = {
        name: {"value": value if math.isfinite(value) else None,
               "limit": limit} for name, value, limit, _, _ in rows}
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
