"""Device-resident dataset cache — the RDD-in-memory model, HBM edition.

SparkNet's apps keep the ENTIRE training set in cluster memory: CifarApp
loads all records into an RDD cached across executors and each worker
samples minibatches from its in-memory partition (CifarApp.scala:56-64,
MiniBatchSampler). The TPU-native analog is the dataset resident in HBM:
one bulk uint8 upload at startup (CIFAR-10: 150 MB of a v5e's 16 GB), then
each training step ships only a (B, k) int32 control array — the cursor
indices plus the host-drawn crop/mirror randomness, a few hundred BYTES —
and the jitted step gathers its batch and applies the reference transform
(device_transform.py) on-chip.

Why this matters on any hardware: host->HBM bandwidth is orders of magnitude below HBM bandwidth, and a
blocking per-step device_put serializes transfer with compute. With the
dataset resident, steady-state H2D is O(batch) control words, so the input
pipeline can never be the bottleneck — the exact property SparkNet bought
by caching RDDs (its Spark stages read no HDFS after the first epoch).

Cursor semantics match the reference data layer: sequential read order
with wrap-around (data_layer.cpp:40-45), rand_skip consumed at source
construction. TEST passes restart from record 0 (fresh `iter()` per test,
as the CLI has always done).
"""

import os

import numpy as np

from .datum import datum_to_array


def _chunk_bytes():
    """Upload chunk size — the ONE definition shared by the uploader and
    maybe_device_cache's 2x-headroom gate, so the gate's single-put-vs-
    chunked decision always matches the path actually taken."""
    return int(float(os.environ.get("SPARKNET_CACHE_CHUNK_MB", "32"))
               * (1 << 20))


class DeviceCachedSource:
    """Wrap a device-mode DatumBatchSource: bulk-load every record to the
    device, then yield per-step control arrays instead of pixel batches.

    Feed protocol (all through one packed int32 array so a step costs ONE
    tiny device_put):
      {data_top}#ctl : (B, k) int32 — columns [idx][, y, x][, flip] per
      the transform config; device_fn() gathers images/labels from the
      resident arrays and applies the on-device transform.
    The label blob is produced on-device from the same indices, so the
    host feeds nothing else (its check_batch override is None).
    """

    def __init__(self, dbsource, device=None, metrics=None, emit_every=100):
        import jax
        if not dbsource.device_mode:
            raise ValueError("DeviceCachedSource needs a device-mode source")
        self.inner = dbsource
        # hit/miss gauge into the shared metrics stream (next to the
        # prefetch queue gauges): every batch served from the resident
        # arrays is a hit; misses only happen when promotion was refused
        # (maybe_device_cache logs that refusal as an all-miss event)
        self.metrics = metrics
        self.emit_every = max(1, int(emit_every))
        self.hits = 0
        self.source = dbsource.source
        self.batch_size = dbsource.batch_size
        self.data_top = dbsource.data_top
        self.label_top = dbsource.label_top
        self.record_shape = dbsource.record_shape
        self.shape = dbsource.shape
        self._devt = dbsource._devt
        self._ctl_key = f"{self.data_top}#ctl"
        self._img_key = f"{self.data_top}#cacheimg"
        self._lab_key = f"{self.data_top}#cachelab"

        n = len(dbsource.db)
        labels = np.empty(n, np.int32)
        arrs = None
        for i, (_, value) in enumerate(dbsource.db.items()):
            arr, labels[i] = datum_to_array(value)
            if arrs is None:
                arrs = np.empty((n,) + self.record_shape, arr.dtype)
            arrs[i] = arr.reshape(self.record_shape)
        self.num_records = n
        # bulk H2D once; steady-state steps transfer ~nothing. The upload
        # goes up in bounded chunks rather than one giant device_put: a
        # multi-hundred-MB single transfer is what a flaky host->device
        # link hangs on, and chunking also bounds peak host pinned memory.
        rec_bytes = int(np.prod(self.record_shape)) * arrs.itemsize + 4
        per = max(1, _chunk_bytes() // rec_bytes)
        if n > per:
            import jax.numpy as jnp
            parts = [jax.device_put(arrs[s0:s0 + per], device)
                     for s0 in range(0, n, per)]
            self._images = jnp.concatenate(parts, axis=0)
            del parts              # transient 2x HBM only during assembly
        else:
            self._images = jax.device_put(arrs, device)
        self._labels = jax.device_put(labels, device)
        self._start = dbsource._skip % n
        dbsource.db.close()
        self._gauge(resident=True)

    def _gauge(self, **extra):
        if self.metrics is None:
            return
        self.metrics.log("device_cache", source=self.source,
                         records=self.num_records, nbytes=self.nbytes,
                         hits=self.hits, misses=0, hit_rate=1.0, **extra)

    @property
    def nbytes(self):
        if self._images is None:
            return 0
        return self._images.nbytes + self._labels.nbytes

    @property
    def device_mode(self):
        return True

    @property
    def num_batches(self):
        return max(1, self.num_records // self.batch_size)

    def _ctl_columns(self):
        t = self._devt.h
        cols = 1
        if t.crop_size:
            cols += 2
        if t.mirror:
            cols += 1
        return cols

    def __iter__(self):
        """Infinite per-step control stream: sequential cursor + the host
        rng's crop/mirror draws (same rng, same order as the streaming
        device mode — the augmentation stream is identical).

        The resident arrays ride along in every batch dict as ARGUMENTS to
        the jitted step rather than closure constants: an already-on-device
        array costs nothing to pass, while a closed-over multi-hundred-MB
        constant gets embedded into the HLO where XLA's constant handling
        can stall compilation for tens of minutes (observed on the 383 MB
        imagenet-shaped cache; the 150 MB CIFAR cache merely compiled
        slowly)."""
        n, b = self.num_records, self.batch_size
        pos = self._start
        self._start = 0
        while True:
            idx = (pos + np.arange(b)) % n
            pos = (pos + b) % n
            cols = [idx.astype(np.int32)]
            aux = self._devt.aux(b, self.record_shape)
            ky, kx, kf = self._devt.ky, self._devt.kx, self._devt.kf
            if ky in aux:
                cols += [aux[ky], aux[kx]]
            if kf in aux:
                cols.append(aux[kf].astype(np.int32))
            self.hits += 1
            if self.hits % self.emit_every == 0:
                self._gauge()
            yield {self._ctl_key: np.stack(cols, axis=1),
                   self._img_key: self._images,
                   self._lab_key: self._labels}

    @property
    def device_fn(self):
        """fn(batch)->batch for Solver.set_input_transform: unpack the ctl
        array, gather the resident records (arriving as batch entries, see
        __iter__), transform on-device."""
        import jax.numpy as jnp
        t = self._devt.h
        ctl_key, img_key, lab_key = \
            self._ctl_key, self._img_key, self._lab_key
        data_top, label_top = self.data_top, self.label_top
        ky, kx, kf = self._devt.ky, self._devt.kx, self._devt.kf
        has_crop, has_flip = bool(t.crop_size), bool(t.mirror)
        inner_fn = self._devt.device_fn()

        def fn(batch):
            batch = dict(batch)
            ctl = batch.pop(ctl_key)
            images = batch.pop(img_key)
            labels = batch.pop(lab_key)
            idx = ctl[:, 0]
            feed = {data_top: jnp.take(images, idx, axis=0),
                    label_top: jnp.take(labels, idx, axis=0)}
            col = 1
            if has_crop:
                feed[ky], feed[kx] = ctl[:, col], ctl[:, col + 1]
                col += 2
            if has_flip:
                feed[kf] = ctl[:, col]
            out = inner_fn(feed)
            out.update(batch)      # extra host-fed blobs pass through
            return out

        return fn

    @property
    def raw_feed_overrides(self):
        """check_batch overrides: the tiny ctl array plus the (free,
        already-resident) cache arrays; the net's data/label blobs are
        produced on-device (None = not host-fed)."""
        over = {self.data_top: None, self.label_top: None,
                self._ctl_key: (self.batch_size, self._ctl_columns()),
                self._img_key: (self.num_records,) + self.record_shape,
                self._lab_key: (self.num_records,)}
        return over

    def close(self):
        if self.hits % self.emit_every:
            self._gauge()              # final partial-window gauge
        self._images = self._labels = None


def _log_miss_mode(metrics, src, reason, **extra):
    """Promotion refused: every batch will stream through the host — an
    all-miss ``device_cache`` gauge with the reason, so a report can tell
    'cache never engaged' apart from 'no gauge at all'."""
    if metrics is None:
        return
    metrics.log("device_cache", source=getattr(src, "source", "?"),
                resident=False, reason=reason, hits=0,
                misses=getattr(src, "num_records", None), hit_rate=0.0,
                **extra)


def maybe_device_cache(src, budget_mb=2048, iter_size=1, metrics=None):
    """Promote a device-mode DatumBatchSource to a DeviceCachedSource when
    the whole dataset fits the HBM budget; otherwise return it unchanged
    (the streaming device-transform path still applies).

    Refuses under iter_size > 1 (Solver.step stacks micro-batch dicts on
    the HOST, which would read the resident arrays back and re-upload
    iter_size copies per step) and under multi-process JAX (the resident
    arrays are whole-dataset, not per-host batch slices, so the per-host
    check_batch slicing rule doesn't apply to them)."""
    if src is None or not getattr(src, "device_mode", False):
        return src
    if not hasattr(src, "db"):
        return src
    if int(iter_size) > 1:
        _log_miss_mode(metrics, src, "iter_size")
        return src
    import jax
    if jax.process_count() > 1:
        _log_miss_mode(metrics, src, "multiprocess")
        return src
    # size from the first record's ACTUAL dtype — float_data datums decode
    # to float32, 4x the uint8 pixel estimate
    arr, _ = datum_to_array(next(src.db.items())[1])
    est = len(src.db) * (arr.size * arr.itemsize + 4)
    # the chunked upload path (datasets > one chunk) transiently holds
    # parts + their concatenation in HBM, so gate on ~2x for it — a
    # dataset near the budget must not OOM where a single device_put
    # would have fit
    needed = est * 2 if est > _chunk_bytes() else est
    if needed > budget_mb * (1 << 20):
        _log_miss_mode(metrics, src, "over_budget", est_bytes=est,
                       budget_mb=budget_mb)
        return src
    return DeviceCachedSource(src, metrics=metrics)
