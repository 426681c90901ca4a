"""ImageNet/CaffeNet training driver — the reference ImageNetApp.scala.

Reference behavior: AlexNet-class CaffeNet, batch 256, 256x256 source
images, random 227x227 crop + mean subtraction on TRAIN (center crop on
TEST), mean image via ComputeMean, tau=50 local steps per round.
Data arrives as (image, label) record streams (reference: S3 tar archives
-> RDD; here: any iterator of (N,3,256,256) uint8 batches — see
sparknet_tpu.data.imagenet for the tar reader).
"""

import os
import time

import numpy as np

from ..proto import Message
from ..models import zoo
from ..data.transforms import transform_train, transform_test, compute_mean
from ..parallel import make_mesh, DataParallelSolver, LocalSGDSolver

SOURCE_SIZE = 256
CROP = 227
BATCH = 256


class ImageNetApp:
    def __init__(self, num_workers=None, train_source=None, test_source=None,
                 num_classes=1000, strategy="local_sgd", tau=50, batch=BATCH,
                 log_path=None, seed=0, metrics_path=None):
        self.t0 = time.time()
        self.logf = open(log_path, "w") if log_path else None
        self.metrics_path = metrics_path
        # shared stream: app round/test events + solver obs accounting
        from ..utils.metrics import MetricsLogger
        self.metrics = MetricsLogger(metrics_path) if metrics_path else None
        from ..parallel import distributed_init
        distributed_init()      # no-op single-process (DEPLOY.md)
        mesh = make_mesh({"data": num_workers if num_workers else -1})
        self.num_workers = mesh.shape["data"]
        self.strategy = strategy
        self.batch = batch
        self.num_classes = num_classes
        self.rng = np.random.RandomState(seed)

        if train_source is None:
            self.log("no ImageNet source; using synthetic class-gaussians")
            train_source = _synthetic_source(self.rng, num_classes)
            test_source = _synthetic_source(
                np.random.RandomState(seed + 1), num_classes)
        self.train_source = train_source
        self.test_source = test_source

        self.log("computing mean image (ComputeMean.scala equivalent)")
        probe = [next(self.train_source) for _ in range(4)]
        self.mean_image = compute_mean(
            (b[0] for b in probe), (3, SOURCE_SIZE, SOURCE_SIZE))

        scale = 1 if strategy == "local_sgd" else self.num_workers
        net = zoo.caffenet(batch_size=batch * scale, num_classes=num_classes,
                           crop_size=CROP)
        solver_param = Message(
            "SolverParameter", base_lr=0.01, momentum=0.9,
            weight_decay=0.0005, lr_policy="step", gamma=0.1, stepsize=100000,
            display=0, random_seed=seed)
        if strategy == "local_sgd":
            self.solver = LocalSGDSolver(solver_param, mesh=mesh, tau=tau,
                                         net_param=net, log_fn=self.log,
                                         metrics=self.metrics)
        else:
            self.solver = DataParallelSolver(solver_param, mesh=mesh,
                                             net_param=net, log_fn=self.log,
                                             metrics=self.metrics)
        self.log(f"initialized: {self.num_workers} workers, "
                 f"strategy={strategy}, batch={batch * scale}")

    def log(self, msg):
        line = f"{time.time() - self.t0:9.2f}: {msg}"
        print(line)
        if self.logf:
            self.logf.write(line + "\n")
            self.logf.flush()

    # -- preprocessing (ImageNetApp.scala:155-169 / :117-131) --------------
    def _prep_train(self, images):
        return transform_train(images, CROP, mean=self.mean_image,
                               mirror=True, rng=self.rng)

    def _prep_test(self, images):
        return transform_test(images, CROP, mean=self.mean_image)

    def _collect(self, source, n, prep):
        imgs, labs = [], []
        have = 0
        while have < n:
            bi, bl = next(source)
            imgs.append(bi)
            labs.append(bl)
            have += len(bi)
        images = np.concatenate(imgs)[:n]
        labels = np.concatenate(labs)[:n]
        return prep(images), labels

    def _round_stream(self):
        """Per-round batches, produced in the prefetch worker: JPEG-decoded
        source batches -> native crop/mirror/mean transform, overlapping the
        device round (base_data_layer.cpp:70-101 economics)."""
        while True:
            if self.strategy == "local_sgd":
                tau = self.solver.tau
                d, l = self._collect(
                    self.train_source, tau * self.batch * self.num_workers,
                    self._prep_train)
                yield {
                    "data": d.reshape(self.num_workers, tau, self.batch,
                                      3, CROP, CROP)
                    .transpose(1, 0, 2, 3, 4, 5)
                    .reshape(tau, -1, 3, CROP, CROP),
                    "label": l.reshape(self.num_workers, tau, self.batch)
                    .transpose(1, 0, 2).reshape(tau, -1)}
            else:
                d, l = self._collect(self.train_source,
                                     self.batch * self.num_workers,
                                     self._prep_train)
                yield {"data": d, "label": l}

    # -- driver loop (ImageNetApp.scala:100-182) ---------------------------
    def run(self, num_rounds=10, test_every=10, test_iters=4,
            stall_seconds=1200.0):
        from ..data.prefetch import PrefetchIterator
        from ..utils.watchdog import Watchdog

        metrics = self.metrics
        steps = self.solver.tau if self.strategy == "local_sgd" else 1
        imgs_per_round = self.batch * self.num_workers * steps
        wd = Watchdog(stall_seconds=stall_seconds, metrics=metrics,
                      on_stall=lambda dt: self.log(
                          f"WATCHDOG: no round finished in {dt:.0f}s"),
                      on_nan=lambda v: self.log(f"WATCHDOG: loss = {v}"))
        batches = PrefetchIterator(self._round_stream(), depth=2,
                                   metrics=metrics, name="round_feed")
        try:
            with wd:
                for r in range(num_rounds):
                    if test_every and r % test_every == 0 and \
                            self.test_source:
                        def it():
                            bs = self.batch * (
                                1 if self.strategy == "local_sgd"
                                else self.num_workers)
                            while True:
                                d, l = self._collect(self.test_source, bs,
                                                     self._prep_test)
                                yield {"data": d, "label": l}
                        scores = self.solver.test(it(), num_iters=test_iters)
                        for k, v in scores.items():
                            v = float(np.asarray(v).mean())
                            self.log(f"round {r}: test {k} = {v:.4f}")
                            if metrics:
                                metrics.log("test", round=r, metric=k,
                                            value=v)
                    rt0 = time.perf_counter()
                    if self.strategy == "local_sgd":
                        loss = self.solver.train_round(next(batches))
                    else:
                        loss = self.solver.train_step(next(batches))
                    loss = float(loss)
                    dt = time.perf_counter() - rt0
                    wd.beat(loss)
                    self.log(f"round {r}: loss = {loss:.4f}")
                    if metrics:
                        metrics.log("round", round=r, loss=loss,
                                    iter=self.solver.iter,
                                    images_per_s=round(
                                        imgs_per_round / max(dt, 1e-9), 1))
        finally:
            batches.close()
            self.solver.close()     # flush step/comms summaries
            if metrics:
                metrics.close()
        return self.solver


_PROTO_GRID = 16     # class prototypes are 16x16 blocks, upsampled x16


def _synthetic_source(rng, num_classes, batch=64):
    """Endless (images uint8 (N,3,256,256), labels) batch generator of
    class-gaussians: one coarse prototype per class, FIXED for the run
    and shared by the train and the test stream (so labels carry signal
    from batch to batch and the test score means something), plus fresh
    noise per image. Prototypes are kept at 3x16x16 and upsampled, so
    1000 classes cost 3 MB, not 786 MB a draw."""
    protos = np.random.RandomState(1234).randn(
        num_classes, 3, _PROTO_GRID, _PROTO_GRID).astype(np.float32)
    up = SOURCE_SIZE // _PROTO_GRID
    noise = np.random.default_rng(int(rng.randint(1 << 31)))

    def gen():
        while True:
            labels = rng.randint(0, num_classes, size=batch).astype(np.int32)
            images = np.repeat(np.repeat(protos[labels], up, axis=2),
                               up, axis=3)
            images = 64.0 * images + 128.0
            images += noise.standard_normal(images.shape,
                                            dtype=np.float32) * 32.0
            img8 = np.clip(images, 0, 255).astype(np.uint8)
            yield img8, labels
    return gen()
