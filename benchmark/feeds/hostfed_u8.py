"""`hostfed_u8`: bench.py's bench_hostfed with no lever set. A seeded pool
of `pool_batches` x batch uint8 records of 3 x `source_size`^2 lives in host
memory (Caffe's ImageNet LMDB holds such records: create_imagenet.sh
resizes to 256x256 and stores raw bytes). Each step takes a random window
of the pool plus per-image crop offsets and mirror flags; the program's
PrefetchIterator worker puts them on the device `prefetch_depth` ahead, and
its DeviceTransformer crops, mirrors and subtracts the mean inside the
jitted step. Every seed draws the same amount of work: same sizes, other
windows and offsets."""

import jax
import jax.numpy as jnp
import numpy as np


class Feed:
    def __init__(self, traffic, config, seed, solver, data_shape,
                 num_classes):
        from sparknet_tpu.data.device_transform import (DeviceTransformer,
                                                        aux_keys)
        from sparknet_tpu.data.prefetch import PrefetchIterator
        from sparknet_tpu.data.transforms import DataTransformer
        from sparknet_tpu.proto import Message

        batch, _, crop, _ = data_shape
        src = int(traffic["source_size"])
        self.crop, self.mean = crop, [float(m) for m in traffic["mean"]]
        self.record = (3, src, src)
        rng = np.random.default_rng(seed)
        n = int(traffic["pool_batches"]) * batch
        self.pool = np.frombuffer(
            rng.bytes(n * 3 * src * src), np.uint8).reshape(n, *self.record)
        self.labels = rng.integers(0, num_classes, n).astype(np.int32)
        self.draws = []                 # (window, ys, xs, flips), first 3
        ky, kx, kf = aux_keys("data")

        def host_batches():
            while True:
                lo = int(rng.integers(0, n - batch + 1))
                ys = rng.integers(0, src - crop + 1, batch).astype(np.int32)
                xs = rng.integers(0, src - crop + 1, batch).astype(np.int32)
                flips = rng.integers(0, 2, batch).astype(np.uint8)
                if len(self.draws) < 3:
                    self.draws.append((lo, ys, xs, flips))
                host = {"data": self.pool[lo:lo + batch],
                        "label": self.labels[lo:lo + batch],
                        ky: ys, kx: xs, kf: flips}
                yield {k: jax.device_put(v) for k, v in host.items()}

        tp = Message("TransformationParameter", crop_size=crop, mirror=1)
        tp.mean_value.extend(self.mean)
        devt = DeviceTransformer(
            DataTransformer(tp, phase=0, rng=np.random.RandomState(0)))
        inner = devt.device_fn()

        def transform(b):
            b = inner(b)
            b["data"] = b["data"].astype(jnp.bfloat16)
            return b
        solver.set_input_transform(
            transform, raw_overrides=devt.raw_overrides(batch, self.record))
        self.batch = batch
        self.it = PrefetchIterator(host_batches(),
                                   depth=int(traffic["prefetch_depth"]))

    def __iter__(self):
        return self

    def __next__(self):
        return next(self.it)

    def reference_inputs(self, i):
        """Draw `i` transformed in plain numpy, as Caffe's data_transformer
        does it: crop, subtract the channel mean, mirror; then bf16, the
        type in which the batch enters the net."""
        lo, ys, xs, flips = self.draws[i]
        c = self.crop
        out = np.empty((self.batch, 3, c, c), np.float32)
        mean = np.asarray(self.mean, np.float32)[:, None, None]
        for j in range(self.batch):
            img = self.pool[lo + j, :, ys[j]:ys[j] + c, xs[j]:xs[j] + c]
            img = img.astype(np.float32) - mean
            out[j] = img[:, :, ::-1] if flips[j] else img
        return (jnp.asarray(out).astype(jnp.bfloat16),
                jnp.asarray(self.labels[lo:lo + self.batch]))

    def stats(self):
        return self.it.stats()

    def close(self):
        self.it.close()


def build(**kw):
    return Feed(**kw)
