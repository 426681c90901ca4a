"""Background prefetch — the reference's double-buffered loader threads.

Caffe's BasePrefetchingDataLayer ran an InternalThread pumping batches
through a prefetch_free_/prefetch_full_ BlockingQueue pair
(base_data_layer.cpp:70-101, data_layers.hpp:91-93). Same structure: a
bounded queue (depth = the number of in-flight buffers), worker thread(s)
running the host-side produce fn (decode/transform — which release the GIL
in the native pipeline), and optionally jax.device_put so host->HBM copies
overlap the running step.

Two feed-path companions live here because they slot into the same
iterator chain:

  H2DStager    — a prefetch ``transform`` that turns "device_put in the
                 worker" into true double buffering: each put is
                 dispatched non-blocking into a rotating slot and only
                 the (slots+1)-th oldest transfer is waited on, so batch
                 N+1's H2D copy runs while step N computes, with bounded
                 in-flight HBM.
  EchoIterator — data echoing (Choi et al.): serve each upstream batch E
                 times, optionally swapping in fresh crop/mirror aux
                 draws per echo so the device sees E distinct
                 augmentations of one transferred payload.
"""

import collections
import queue
import threading
import time

from jax.profiler import TraceAnnotation


_END = object()
_ERR = object()     # a worker died; the queue stays FIFO so items the
                    # worker produced before failing still arrive first


def _nbytes(batch):
    """Bytes of a batch's arrays (0 for what has no ``nbytes``)."""
    vals = batch.values() if isinstance(batch, dict) else [batch]
    return sum(int(getattr(v, "nbytes", 0)) for v in vals)


class PrefetchIterator:
    """Wrap a batch iterator (or factory) with N background workers.

    depth: max buffered batches (2 = classic double buffering).
    transform: optional fn(batch)->batch run in the worker (e.g. the crop/
               mean native transform, or an H2DStager for H2D overlap).
    workers > 1 preserves NO ordering guarantees (like the reference's
    single reader it defaults to 1, which does).
    metrics: optional utils.metrics.MetricsLogger; queue-depth gauges are
             emitted as ``prefetch`` events every ``emit_every`` consumer
             gets (and once at close). An empty queue at get time means the
             consumer is about to block on the producer — a sustained
             empty_frac near 1.0 says the input pipeline, not the device,
             is the bound.
    extra: optional static fields (echo factor, wire mode, ingest shard)
           merged into stats() and the ``prefetch`` event.
    tracer: the obs.trace.Tracer that times both sides of the queue (the
            process-wide default when None). Per get: ``prefetch.wait``,
            the time the consumer was blocked. Per item, on the worker's
            thread: ``prefetch.produce`` (next(source) + transform, with
            the item's ``bytes``; with workers > 1 it includes the wait
            for the shared source) and ``prefetch.put_wait``, the time it
            then stood before a full queue — the producer's slack.

    A worker exception is propagated to the consumer exactly once, with
    the original traceback, after any batches produced before the failure;
    iteration then ends (StopIteration). The failing worker also stops its
    siblings, so a poisoned source cannot wedge a workers>1 pool on a full
    queue.
    """

    def __init__(self, source, depth=2, transform=None, workers=1,
                 metrics=None, name="prefetch", emit_every=100, extra=None,
                 tracer=None):
        from ..obs.trace import default_tracer
        self._tracer = tracer if tracer is not None else default_tracer()
        self._q = queue.Queue(maxsize=depth)
        self._transform = transform
        self._stop = threading.Event()
        self._done = False
        self._error = None
        self._src_lock = threading.Lock()
        # workers > 1 share one upstream iterator; the lock checker
        # (`sparknet lint`, SPK201) verifies every next() holds the lock
        self._source = iter(source)     # spk: guarded-by=_src_lock
        self._metrics = metrics
        self._name = name
        self._emit_every = max(1, emit_every)
        self._extra = dict(extra) if extra else {}
        self._depth = depth
        self._gets = 0
        self._depth_sum = 0
        self._empty_gets = 0
        self._stats_emitted = False
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"sparknet-prefetch-{i}")
            for i in range(workers)]
        self._live_lock = threading.Lock()
        self._live = len(self._threads)  # spk: guarded-by=_live_lock
        for t in self._threads:
            t.start()

    def _run(self):
        tr = self._tracer
        try:
            while not self._stop.is_set():
                t0 = tr.now_ns()
                with TraceAnnotation("sparknet.prefetch.produce"):
                    with self._src_lock:
                        try:
                            item = next(self._source)
                        except StopIteration:
                            break
                    if self._transform is not None:
                        item = self._transform(item)
                t1 = tr.now_ns()
                with TraceAnnotation("sparknet.prefetch.put_wait"):
                    while not self._stop.is_set():
                        try:
                            self._q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                tr.record("prefetch.produce", t0, t1, bytes=_nbytes(item))
                tr.record("prefetch.put_wait", t1, tr.now_ns())
        except BaseException as e:     # surfaced on the consumer side
            if self._error is None:    # first failure wins
                self._error = e
            self._stop.set()           # release siblings blocked on put()
            # the stop flag just disarmed the normal put loop, so push the
            # sentinel with its own bounded retry (consumer may lag or may
            # already be closed)
            while not self._done:
                try:
                    self._q.put(_ERR, timeout=0.1)
                    break
                except queue.Full:
                    continue
        finally:
            with self._live_lock:
                self._live -= 1
                last = self._live == 0
            # a put that was already waiting when close() drained lands
            # after the drain and fills the queue: once the consumer is
            # done nobody makes room, so the end marker gives up then
            while last:
                try:
                    self._q.put(_END, timeout=0.1)
                    break
                except queue.Full:
                    if self._done:
                        break

    def __iter__(self):
        return self

    def _finish(self):
        # exactly-once error propagation: hand the exception object (its
        # __traceback__ points at the worker frame) to the first raiser,
        # then clear it so later calls see a plain end-of-stream
        self._done = True
        err, self._error = self._error, None
        if err is not None:
            raise err
        raise StopIteration

    def __next__(self):
        if self._done:
            self._finish()
        d = self._q.qsize()          # approximate, fine for a gauge
        self._gets += 1
        self._depth_sum += d
        if d == 0:
            self._empty_gets += 1
        if self._metrics is not None and self._gets % self._emit_every == 0:
            self._emit_stats()
        with self._tracer.hot_span("prefetch.wait"):
            item = self._q.get()
        if item is _END or item is _ERR:
            self._finish()
        return item

    def stats(self):
        """Queue-depth gauges over the consumer's gets so far."""
        g = self._gets
        out = {"name": self._name, "gets": g, "depth_cap": self._depth,
               "depth_mean": round(self._depth_sum / g, 3) if g else None,
               "empty_frac": round(self._empty_gets / g, 3) if g else None}
        out.update(self._extra)
        return out

    def _emit_stats(self):
        self._metrics.log("prefetch", **self.stats())

    def close(self):
        if self._metrics is not None and self._gets \
                and not self._stats_emitted:
            self._stats_emitted = True
            self._emit_stats()
        self._done = True
        self._stop.set()
        # drain so producers blocked on put() can exit; an unconsumed
        # worker error is dropped — the consumer chose to stop first
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class H2DStager:
    """Rotating-slot async H2D staging, used as a prefetch ``transform``.

    ``jax.device_put`` only *dispatches* a copy; the old inline-put path
    still serialized feeds whenever the worker produced faster than the
    link, because nothing bounded how the puts queued behind each other.
    The stager keeps up to ``slots`` transfers in flight: each call
    dispatches the new batch non-blocking, then waits on the transfer that
    is now slots+1 deep — i.e. one the consumer is about to need anyway —
    so the wait overlaps the running step instead of preceding it, and
    staged HBM stays bounded at slots+1 batches.

    Safe from multiple prefetch workers (counters are lock-guarded);
    ``chaos`` hooks ChaosMonkey.maybe_slow_h2d so the smoke test can make
    the wire artificially slow.
    """

    def __init__(self, slots=2, metrics=None, name="h2d", emit_every=50,
                 chaos=None):
        import jax
        self._jax = jax
        self.slots = max(1, int(slots))
        self._metrics = metrics
        self._name = name
        self._emit_every = max(1, emit_every)
        self._chaos = chaos
        self._lock = threading.Lock()
        self._ring = collections.deque()    # spk: guarded-by=_lock
        self._puts = 0                      # spk: guarded-by=_lock
        self._bytes = 0                     # spk: guarded-by=_lock
        self._dispatch_s = 0.0              # spk: guarded-by=_lock
        self._wait_s = 0.0                  # spk: guarded-by=_lock

    def __call__(self, batch):
        nbytes = _nbytes(batch)
        if self._chaos is not None:
            self._chaos.maybe_slow_h2d(nbytes=nbytes)
        put = self._jax.device_put
        t0 = time.perf_counter()
        if isinstance(batch, dict):
            staged = {k: put(v) for k, v in batch.items()}
            leaves = list(staged.values())
        else:
            staged = put(batch)
            leaves = [staged]
        t1 = time.perf_counter()
        with self._lock:
            self._ring.append(leaves)
            oldest = self._ring.popleft() \
                if len(self._ring) > self.slots else None
        t2 = time.perf_counter()
        if oldest is not None:
            for leaf in oldest:
                leaf.block_until_ready()
        t3 = time.perf_counter()
        with self._lock:
            self._puts += 1
            self._bytes += nbytes
            self._dispatch_s += t1 - t0
            self._wait_s += t3 - t2
            puts = self._puts
            emit = (self._metrics is not None
                    and puts % self._emit_every == 0)
            snap = self._stats_locked() if emit else None
        if emit:
            self._metrics.log(
                "h2d_stage", name=snap["name"], puts=snap["puts"],
                bytes=snap["bytes"], kb_per_item=snap["kb_per_item"],
                dispatch_ms=snap["dispatch_ms"], wait_ms=snap["wait_ms"],
                in_flight=snap["in_flight"], slots=snap["slots"])
        return staged

    def _stats_locked(self):        # spk: holds=_lock
        p = self._puts
        return {
            "name": self._name, "puts": p, "bytes": self._bytes,
            "kb_per_item": round(self._bytes / p / 1024.0, 1) if p else 0.0,
            "dispatch_ms": round(self._dispatch_s / p * 1e3, 3) if p else 0.0,
            "wait_ms": round(self._wait_s / p * 1e3, 3) if p else 0.0,
            "in_flight": len(self._ring), "slots": self.slots}

    def stats(self):
        with self._lock:
            return self._stats_locked()

    def flush(self):
        """Block the remaining in-flight transfers (end of run)."""
        with self._lock:
            pending, self._ring = list(self._ring), collections.deque()
        for leaves in pending:
            for leaf in leaves:
                leaf.block_until_ready()


class EchoIterator:
    """Serve each upstream batch ``echo`` times (data echoing).

    fresh_aux: optional fn(batch)->{aux_key: array} giving NEW host-side
    crop/mirror draws for every echo after the first, so each echo is a
    distinct augmentation of the same transferred pixels. Echoes shallow-
    copy the batch dict and swap only the tiny aux arrays — the staged
    pixel payload is reused by reference, which is the whole point.

    echo == 1 is a strict passthrough: no extra rng draws, no copies, so
    the E=1 trajectory is bit-identical to the unwrapped pipeline.
    Delegates close()/stats() to the wrapped iterator.
    """

    def __init__(self, source, echo, fresh_aux=None):
        self._inner = source
        self._it = iter(source)
        self.echo = max(1, int(echo))
        self._fresh_aux = fresh_aux
        self._base = None
        self._left = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self.echo == 1:
            return next(self._it)
        if self._left > 0:
            self._left -= 1
            b = self._base
            if self._fresh_aux is not None and isinstance(b, dict):
                b = dict(b)
                b.update(self._fresh_aux(self._base))
            return b
        self._base = next(self._it)
        self._left = self.echo - 1
        return self._base

    def stats(self):
        inner = getattr(self._inner, "stats", None)
        out = dict(inner()) if inner is not None else {}
        out["echo"] = self.echo
        return out

    def close(self):
        inner = getattr(self._inner, "close", None)
        if inner is not None:
            inner()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
