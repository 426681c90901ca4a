"""tau x s sweep: bounded-staleness async local SGD vs the synchronous
barrier, under a straggler (ISSUE 7; ROADMAP item 5; CONVERGENCE.md
section 6 is the writeup).

The SparkNet paper positions synchronous tau-interval averaging against
downpour-style async SGD but never ships the comparison. This driver
settles it at experiment scale: every (workload, tau, mode) cell trains
the SAME model on the SAME data for the SAME total number of local
steps, with a chaos ``slow_worker`` making worker 1 pay ``--slow-s``
extra seconds per round — the persistent straggler both update rules
must live with:

  * mode "sync"  — the paper's barrier: the collect & average waits for
    the straggler every round, so wall clock tracks the MAX worker.
  * mode "s=K"   — bounded staleness: the round proceeds at the median
    worker's pace; the straggler's push is discounted by decay**lag and
    parked past the bound (resync = readmission from the consensus).

Measured per cell: wall clock (post-compile), mean round latency, final
eval (accuracy for the CIFAR surrogate, CE nats for the LM), parks /
unparks, and the straggler's max version lag. Rows land as ``sweep``
events in results/tau_s_<workload>.jsonl; a markdown table prints at
the end for CONVERGENCE.md.

Usage:
    python experiments/tau_s_sweep.py --workload cifar \
        --metrics results/tau_s_cifar.jsonl
    python experiments/tau_s_sweep.py --workload lm \
        --metrics results/tau_s_lm.jsonl
"""

import argparse
import json
import os
import sys
import time


def _pre_jax(n_devices):
    # must be set before any jax import — the tests/conftest.py discipline
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()


sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_cifar(workers, batch):
    """CIFAR-surrogate workload: a compact conv net (conv-pool-conv-fc,
    the cifar10_quick shape at experiment scale) on the shape-texture
    3x32x32 surrogate — the learnable zero-egress stand-in the repo's
    convergence artifacts use throughout (CONVERGENCE.md)."""
    import numpy as np
    from sparknet_tpu.proto import Message
    from sparknet_tpu.data.synthetic import shape_texture_images

    def net(b):
        n = Message("NetParameter", name="cifar_sweep")
        n.add("layer", name="data", type="JavaData", top=["data"],
              java_data_param=dict(shape=dict(dim=[b, 3, 32, 32])))
        n.add("layer", name="label", type="JavaData", top=["label"],
              java_data_param=dict(shape=dict(dim=[b])))
        n.add("layer", name="conv1", type="Convolution", bottom=["data"],
              top=["conv1"], convolution_param=dict(
                  num_output=16, kernel_size=[5], stride=[2],
                  weight_filler=dict(type="xavier")))
        n.add("layer", name="relu1", type="ReLU", bottom=["conv1"],
              top=["conv1"])
        n.add("layer", name="pool1", type="Pooling", bottom=["conv1"],
              top=["pool1"], pooling_param=dict(pool="MAX", kernel_size=3,
                                                stride=2))
        n.add("layer", name="conv2", type="Convolution", bottom=["pool1"],
              top=["conv2"], convolution_param=dict(
                  num_output=16, kernel_size=[3],
                  weight_filler=dict(type="xavier")))
        n.add("layer", name="relu2", type="ReLU", bottom=["conv2"],
              top=["conv2"])
        n.add("layer", name="ip1", type="InnerProduct", bottom=["conv2"],
              top=["ip1"], inner_product_param=dict(
                  num_output=10, weight_filler=dict(type="xavier")))
        n.add("layer", name="acc", type="Accuracy",
              bottom=["ip1", "label"], top=["accuracy"])
        n.add("layer", name="loss", type="SoftmaxWithLoss",
              bottom=["ip1", "label"], top=["loss"])
        return n

    ti, tl = shape_texture_images(4096, seed=0)
    vi, vl = shape_texture_images(512, seed=1)
    ti = np.asarray(ti, np.float32)
    vi = np.asarray(vi, np.float32)
    # mean-subtract + scale to ~unit range (the 0-255 pixel scale with
    # xavier init and momentum diverges at any useful lr)
    mean = ti.mean(0)
    ti = (ti - mean) / 64.0
    vi = (vi - mean) / 64.0
    tl, vl = np.asarray(tl, np.int32), np.asarray(vl, np.int32)

    def batch_fn(tau, seed):
        r = np.random.RandomState(seed)
        idx = r.randint(0, len(ti), tau * workers * batch)
        return {"data": ti[idx].reshape(tau, workers * batch, 3, 32, 32),
                "label": tl[idx].reshape(tau, workers * batch)}

    def eval_fn(solver):
        it = iter({"data": vi[i:i + batch], "label": vl[i:i + batch]}
                  for i in range(0, 512 - batch + 1, batch))
        scores = solver.test(it, num_iters=512 // batch)
        return {"accuracy": float(np.mean(scores["accuracy"])),
                "eval_loss": float(np.mean(scores["loss"]))}

    sp = dict(base_lr=0.02, momentum=0.9, lr_policy="fixed",
              random_seed=0, display=0)
    return net(batch), sp, batch_fn, eval_fn, "accuracy"


def build_lm(workers, batch):
    """LM workload: a tiny decoder-only transformer on the synthetic
    bigram corpus (floor = corpus bigram entropy, logged in the row)."""
    import numpy as np
    from sparknet_tpu.models import zoo
    from sparknet_tpu.data.synthetic import bigram_corpus

    seq = 32
    net = zoo.transformer_lm(vocab_size=64, seq_len=seq,
                             batch_size=batch, d_model=64, num_layers=2,
                             num_heads=4, flash=False)
    # ONE bigram corpus for train and eval (each lm_batch_stream seed
    # would draw a different transition matrix — a train/eval
    # distribution mismatch, not a held-out set)
    sample, floor = bigram_corpus(64, seed=0)

    def draw(n, rng):
        toks = sample(n, seq, rng)
        return {"data": toks[:, :-1].astype(np.int32),
                "label": toks[:, 1:].astype(np.int32)}

    cache = {}

    def batch_fn(tau, seed):
        # deterministic per (tau, seed): every mode sees identical data
        key = (tau, seed)
        if key not in cache:
            rng = np.random.RandomState(1000 + seed)
            ds = [draw(workers * batch, rng) for _ in range(tau)]
            cache[key] = {k: np.stack([d[k] for d in ds])
                          for k in ds[0]}
        return cache[key]

    probe_rng = np.random.RandomState(9)
    probe_batches = [draw(batch, probe_rng) for _ in range(8)]

    def eval_fn(solver):
        scores = solver.test(iter(list(probe_batches)), num_iters=8)
        return {"eval_ce": float(np.mean(scores["loss"])),
                "floor": round(floor, 4)}

    sp = dict(base_lr=3e-3, lr_policy="fixed", type="Adam",
              random_seed=0, display=0)
    return net, sp, batch_fn, eval_fn, "eval_ce"


def run_cell(workload, tau, mode, args, metrics):
    import numpy as np
    from sparknet_tpu.proto import Message
    from sparknet_tpu.parallel import LocalSGDSolver, make_mesh
    from sparknet_tpu.resilience.chaos import ChaosMonkey

    builder = build_cifar if workload == "cifar" else build_lm
    net, sp_kw, batch_fn, eval_fn, metric = builder(args.workers,
                                                    args.batch)
    sp = Message("SolverParameter", **sp_kw)
    s = LocalSGDSolver(sp, net_param=net, tau=tau,
                       mesh=make_mesh({"data": args.workers}),
                       log_fn=None)
    if mode != "sync":
        s.arm_staleness(int(mode.split("=")[1]), decay=args.s_decay)
    chaos = ChaosMonkey(slow_worker=1, slow_s=args.slow_s, log_fn=None)
    s.chaos = chaos
    if s.elastic is not None:
        s.elastic.chaos = chaos
    rounds = args.steps // tau
    s.train_round(batch_fn(tau, 0))            # warm-up (compile) round
    t0 = time.perf_counter()
    lat = []
    for r in range(1, rounds):
        r0 = time.perf_counter()
        s.train_round(batch_fn(tau, r))
        lat.append(time.perf_counter() - r0)
    wall = time.perf_counter() - t0
    ev = eval_fn(s)
    el = s.elastic
    row = {"workload": workload, "tau": tau, "mode": mode,
           "workers": args.workers, "batch_per_worker": args.batch,
           "local_steps": rounds * tau, "rounds": rounds,
           "slow_s": args.slow_s, "s_decay": args.s_decay,
           "wall_s": round(wall, 2),
           "round_s_mean": round(float(np.mean(lat)), 3) if lat else None,
           "parks": len(el.parks) if el is not None else 0,
           "unparks": len(el.unparks) if el is not None else 0,
           "straggler_max_lag": int(max(
               (p["lag"] or 0) for p in el.parks)) if el is not None
           and el.parks else 0,
           **{k: round(v, 4) for k, v in ev.items()}}
    s.close()
    metrics.log("sweep", **row)
    print(json.dumps(row))
    return row, metric


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", choices=("cifar", "lm"),
                    default="cifar")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=32,
                    help="per-worker batch size")
    ap.add_argument("--steps", type=int, default=48,
                    help="total LOCAL steps per cell (rounds = steps/tau "
                         "— every cell sees the same optimization "
                         "budget)")
    ap.add_argument("--taus", default="2,8")
    ap.add_argument("--modes", default="sync,s=0,s=1,s=3")
    ap.add_argument("--slow-s", type=float, default=0.5,
                    help="chaos slow_worker: worker 1's extra seconds "
                         "per round")
    ap.add_argument("--s-decay", type=float, default=0.5)
    ap.add_argument("--metrics", default=None)
    args = ap.parse_args()
    _pre_jax(args.workers * 2)

    from sparknet_tpu.utils.metrics import MetricsLogger
    metrics = MetricsLogger(args.metrics) if args.metrics \
        else MetricsLogger(stream=sys.stderr)
    rows, metric = [], None
    for tau in [int(t) for t in args.taus.split(",")]:
        for mode in args.modes.split(","):
            row, metric = run_cell(args.workload, tau, mode.strip(),
                                   args, metrics)
            rows.append(row)
    metrics.close()

    # the CONVERGENCE.md table
    print(f"\n| tau | mode | wall s | round s | {metric} | parks |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['tau']} | {r['mode']} | {r['wall_s']} | "
              f"{r['round_s_mean']} | {r[metric]} | {r['parks']} |")


if __name__ == "__main__":
    main()
