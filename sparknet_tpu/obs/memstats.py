"""Memory & compile-cache accounting: why did step time regress?

The two silent step-time killers on XLA backends are recompiles (a shape
change retraces mid-run) and memory growth (live arrays accumulating
until allocator pressure or an OOM). stepstats.py already *reports*
recompiles from the tracer's ``program.build`` records; this module samples
the surrounding state on the same cadence so a regression is
explainable from the metrics stream alone:

  live_arrays       count + total bytes of every jax.Array the process
                    holds (leaks show up as a monotonic climb)
  device memory     bytes_in_use / peak_bytes_in_use where the backend
                    reports them (TPU/GPU; absent on CPU)
  compile cache     programs the step's enqueue has built so far (the
                    same records, obs/trace.py:StepBuilds) — growth
                    beyond the expected warmup is the recompile storm
                    stepstats flags per event
  host rss          ru_maxrss, the host-side twin (prefetch buffers,
                    snapshot staging)

Emitted as ``memstats`` events next to each sampled ``step``/round, so
`sparknet report` and `sparknet monitor` can show memory next to step
time.
"""

from .trace import StepBuilds


def live_array_stats():
    """(count, total_bytes) over the process's live jax arrays; (None,
    None) when jax can't enumerate them (old vintage / torn-down
    backend)."""
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:
        return None, None
    total = 0
    for a in arrs:
        try:
            total += int(a.nbytes)
        except Exception:
            pass
    return len(arrs), total


def host_rss_bytes():
    """Peak host RSS in bytes (linux ru_maxrss is KiB), or None."""
    try:
        import resource
        kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(kb) * 1024
    except Exception:
        return None


class MemoryMonitor:
    """sample(it) on the solver's step-sample cadence; each sample emits
    one ``memstats`` event. Tracks peaks so flush() can summarize even if
    the JSONL tail is lost. ``tracer``: the one the solver's step spans
    record into, for the count of the step's programs."""

    def __init__(self, sink, sample_every=1, tracer=None):
        self.sink = sink
        self._builds = None if tracer is None else StepBuilds(tracer)
        self.sample_every = max(1, int(sample_every))
        self._n = 0
        self._last_cache = None
        self.peak_live_bytes = 0
        self.samples = 0

    def sample(self, it, force=False, **extra):
        self._n += 1
        if not force and (self._n - 1) % self.sample_every:
            return None
        count, nbytes = live_array_stats()
        ev = {"iter": it}
        if count is not None:
            ev["live_arrays"] = count
            ev["live_bytes"] = nbytes
            self.peak_live_bytes = max(self.peak_live_bytes, nbytes or 0)
        from .stepstats import device_memory
        mem = device_memory()
        if mem:
            ev.update({f"hbm_{k}": v for k, v in mem.items()})
        if self._builds is not None:
            # the step's programs so far, by the tracer's records
            self._builds.new()
            cache = self._builds.count
            ev["compile_cache"] = cache
            if self._last_cache is not None and cache > self._last_cache:
                ev["compile_cache_grew"] = cache - self._last_cache
            self._last_cache = cache
        rss = host_rss_bytes()
        if rss is not None:
            ev["host_rss_bytes"] = rss
        ev.update({k: v for k, v in extra.items() if v is not None})
        self.samples += 1
        if self.sink is not None:
            self.sink.log("memstats", **ev)
        return ev
