"""A cell's step time and every MoE layer's load, step by step: the share
of token-expert pairs on the experts held here, the largest over the mean
held load and the windows the layer ran (`moe.load`'s three numbers, read
from the layers' state after each step of the cell's solver on the cell's
resident batch). PR 42 found with it that a route drifts towards the
experts a chip holds; not part of the benchmark.

    chiprun --chips 1 -- python3 scripts/probe_moe_load.py \\
        --workload nemotron_tt_ep16_s8192_b2 --seed 4200000101 --steps 48 \\
        [--base_lr 3e-4] [--toy]     (about 4 chip-minutes; --toy: the CPU)
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--base_lr", type=float, default=None)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args(argv)
    if args.toy:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import harness
    import weights
    cell = harness.Cell(args.workload, rehearse=args.toy)
    harness.find_device(cell.chips, args.toy)
    harness.configure_cache()
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.proto import Message
    net = harness.by_path(cell.config["builder"])(
        batch_size=cell.batch, **dict(cell.builder_args, moe_stats=True))
    fields = {k: v for k, v in cell.solver_cfg.items()
              if k not in ("weight_mults", "bias_mults", "note")}
    if args.base_lr:
        fields["base_lr"] = args.base_lr
    sp = Message("SolverParameter", display=0,
                 random_seed=args.seed % (2 ** 31 - 1), **fields)
    cls = harness.by_path(cell.file.get(
        "solver", "sparknet_tpu.solver.solver:Solver"))
    kw = {k: getattr(jnp, v) if k.endswith("dtype") else v
          for k, v in cell.config.get("solver_args", {}).items()}
    solver = cls(sp, net_param=net, log_fn=None, **kw)
    w0 = weights.make_weights(cell.specs, args.seed)
    for name, blobs in w0.items():
        solver.params[name] = [
            jax.device_put(jnp.array(b, dtype=m.dtype, copy=True),
                           m.sharding)
            for b, m in zip(blobs, solver.params[name])]
    del w0
    feed = importlib.import_module(f"feeds.{cell.traffic['feed']}").build(
        traffic=cell.traffic, config=cell.sized_config, seed=args.seed,
        solver=solver, data_shape=cell.data_shape,
        num_classes=cell.num_classes)
    batch = next(feed)
    moes = [n for n, st in solver.state.items() if st]
    for i in range(args.steps):
        t = time.perf_counter()
        loss = float(solver.train_step(batch))
        ms = (time.perf_counter() - t) * 1e3
        load = {n: [round(float(v), 4) for v in solver.state[n][0]]
                for n in moes}
        print(json.dumps({"step": i, "ms": round(ms, 1),
                          "loss": round(loss, 4), "load": load}),
              flush=True)


if __name__ == "__main__":
    main()
