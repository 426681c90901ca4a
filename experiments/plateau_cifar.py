"""Local-SGD vs per-step DP to plateau — the SparkNet paper's core claim.

Runs ONE (strategy, tau, workers) configuration of the CifarApp comparison
(CifarApp.scala:92-135; paper arXiv:1511.06051 fig. 4) on the virtual CPU
mesh until the test-accuracy curve flattens, with test points matched in
IMAGES SEEN across configurations so curves are directly comparable.

Beyond the round-3 version (CONVERGENCE.md section 2, stopped at 216k images
with both curves still climbing) this driver:
  * stops on a plateau rule (last --flat-window test points within
    --flat-eps accuracy points of each other) instead of a fixed round count;
  * logs images_seen and cumulative communication volume with every record:
    DP pays one gradient allreduce per STEP, local SGD one weight average
    per ROUND — the 10x saving the paper claims, here measured in actual
    allreduce payload bytes (param_bytes each, identical payload per event
    since grads and weights are the same pytree).

Usage (the sweep driver experiments/run_plateau_sweep.sh runs the matrix):
    python experiments/plateau_cifar.py --strategy local_sgd --tau 10 \
        --workers 4 --data _work/cifar20k --metrics results/plateau_t10_w4.jsonl
"""

import argparse
import os
import sys

# Virtual CPU mesh: the variables must be set before jax is imported.

def _pre_jax(n_devices):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", choices=("local_sgd", "dp"), required=True)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--data", default="_work/cifar20k")
    ap.add_argument("--metrics", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--max-images", type=int, default=1_600_000)
    ap.add_argument("--min-images", type=int, default=400_000,
                    help="never declare plateau before this many images")
    ap.add_argument("--test-every-images", type=int, default=24_000)
    ap.add_argument("--flat-window", type=int, default=5)
    ap.add_argument("--flat-eps", type=float, default=0.6,
                    help="accuracy-percentage-point spread that counts "
                         "as flat over the window")
    args = ap.parse_args()

    if not os.path.isdir(args.data):
        sys.exit(f"--data {args.data} does not exist; CifarApp would fall "
                 f"back to gaussian noise and the curves would be "
                 f"meaningless. Create it: python -m sparknet_tpu "
                 f"make_synth_cifar {args.data} --train 20000 --test 2000")

    _pre_jax(args.workers)
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from sparknet_tpu.apps.cifar_app import CifarApp, TRAIN_BATCH
    from sparknet_tpu.utils.metrics import MetricsLogger

    app = CifarApp(num_workers=args.workers, data_dir=args.data,
                   strategy=args.strategy, tau=args.tau, seed=args.seed)
    solver = app.solver
    if os.path.exists(args.metrics):
        # MetricsLogger appends; a stale series under the same path would
        # interleave two runs into one unreadable curve
        os.rename(args.metrics, args.metrics + ".old")
    metrics = MetricsLogger(path=args.metrics)

    steps_per_round = args.tau if args.strategy == "local_sgd" else 1
    imgs_per_round = TRAIN_BATCH * app.num_workers * steps_per_round
    param_bytes = sum(np.prod(v.shape) * v.dtype.itemsize
                      for v in jax.tree_util.tree_leaves(solver.params))
    # one allreduce per round in both strategies; a DP "round" is one step
    # (gradient pmean), a local-SGD round is tau steps (weight pmean)
    events_per_round = 1
    app.log(f"plateau driver: {args.strategy} tau={args.tau} "
            f"workers={app.num_workers} imgs/round={imgs_per_round} "
            f"test every {args.test_every_images} images "
            f"param_bytes={param_bytes}")

    accs = []           # (images_seen, accuracy)
    images_seen = 0
    rounds = 0
    import time
    t0 = time.time()

    scores = None
    next_test_at = 0    # test when images_seen first crosses k*test_every
    plateaued = False
    while images_seen < args.max_images:
        if images_seen >= next_test_at:
            next_test_at = (images_seen // args.test_every_images + 1) \
                * args.test_every_images
            scores = app.run_test()
            acc = next((v for k, v in scores.items() if "accuracy" in k),
                       None)
            comm = rounds * events_per_round * param_bytes
            metrics.log("test", round=rounds, images_seen=images_seen,
                        allreduces=rounds * events_per_round,
                        comm_bytes=int(comm), **scores)
            acc_s = f"{acc:.4f}" if acc is not None else "?"
            app.log(f"[{images_seen}] acc={acc_s} "
                    f"allreduces={rounds * events_per_round} "
                    f"({time.time() - t0:.0f}s)")
            if acc is not None:
                accs.append((images_seen, acc))
            w = args.flat_window
            if (len(accs) >= w and images_seen >= args.min_images
                    and (max(a for _, a in accs[-w:])
                         - min(a for _, a in accs[-w:])) * 100
                    <= args.flat_eps):
                app.log(f"PLATEAU at {images_seen} images: last {w} points "
                        f"within {args.flat_eps} pts")
                plateaued = True
                break
        if args.strategy == "local_sgd":
            loss = solver.train_round(app._tau_batches(solver.tau))
        else:
            imgs, labs = app._train_arrays(TRAIN_BATCH * app.num_workers)
            loss = solver.train_step({"data": imgs, "label": labs})
        loss = float(loss)
        rounds += 1
        images_seen += imgs_per_round
        if rounds % 10 == 0:
            metrics.log("round", round=rounds, images_seen=images_seen,
                        loss=loss, iter=solver.iter,
                        images_per_s=round(images_seen
                                           / max(time.time() - t0, 1e-9), 1))

    final = scores if plateaued and scores is not None else app.run_test()
    metrics.log("final", round=rounds, images_seen=images_seen,
                allreduces=rounds * events_per_round,
                comm_bytes=int(rounds * events_per_round * param_bytes),
                param_bytes=int(param_bytes), plateau=plateaued, **final)
    metrics.close()
    app.log(f"done: {images_seen} images, {rounds} rounds, "
            f"{rounds * events_per_round} allreduces, final {final}")


if __name__ == "__main__":
    main()
