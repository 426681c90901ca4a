"""Step accounting: host dispatch vs device wall time, recompile
detection, and step-time percentiles.

The solver's async-dispatch discipline (solver.py) means the host-side
step time measures only *dispatch* — the device runs behind a queue and
fetching anything is a full round trip. So this module records the cheap
host dispatch time every step, and SAMPLES device wall time by blocking
on the step result at a low cadence (the first two observations, then
every ``sample_every``): the wall clock since the previous sample divided
by the steps in between is the true amortized per-step device time, queue
drain included.

Recompiles — the classic silent TPU perf killer (a shape change retraces
and recompiles mid-run) — come from the tracer, which hears every program
the step's enqueue builds as it happens (obs/trace.py: ``program.build``
under ``solver.enqueue``, with the ``cause`` the step span wrote): one
``recompile`` event a build (the step function's first is expected,
flagged first=True), with what differed in the step's arguments.
"""

import re
import time

from .trace import StepBuilds, default_tracer

#: a leaf's shape or dtype among what `trace.diff_signatures` says differs
_SHAPE_CHANGED = re.compile(r"[:,] (shape|dtype) ")


def percentiles(vals, qs=(50, 95, 99)):
    """Linear-interpolation percentiles of a sequence -> {"p50": ...}."""
    if not len(vals):
        return {}
    s = sorted(float(v) for v in vals)
    n = len(s)
    out = {}
    for q in qs:
        pos = q / 100.0 * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        out[f"p{q}"] = s[lo] + (s[hi] - s[lo]) * (pos - lo)
    return out


def device_memory(device=None):
    """HBM gauge where the backend exposes one (TPU/GPU; None on CPU)."""
    try:
        import jax
        d = device if device is not None else jax.local_devices()[0]
        ms = d.memory_stats()
    except Exception:
        return None
    if not ms:
        return None
    return {k: int(ms[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit", "largest_alloc_size")
            if k in ms}


class StepAccounting:
    """Per-step accounting the solver calls once per train_step.

    Emits to the JSONL sink:
      step         at sampled steps — host_ms (this dispatch), sync_ms
                   (block_until_ready wait), device_ms (amortized per-step
                   wall since the previous sample), steps_since_sync
      recompile    whenever the step's enqueue built a program
      hbm          at sampled steps, when the backend reports memory
      step_summary on flush() — full-histogram p50/p95/p99 + counts
    """

    def __init__(self, sink, sample_every=20, max_hist=8192, name="train",
                 tracer=None):
        self.sink = sink
        # the tracer the observed solver's step spans record into
        self.builds = StepBuilds(tracer if tracer is not None
                                 else default_tracer())
        self.sample_every = max(1, int(sample_every))
        self.max_hist = max_hist
        self.name = name
        self.host_s = []            # ring buffer of host dispatch seconds
        self.device_s = []          # amortized device seconds per sample
        self.steps = 0
        self.recompiles = 0         # beyond the expected first compile
        self._nobs = 0
        self._last_sample_it = None
        self._last_sample_t = None
        self._hbm_dead = False

    # -- internals ---------------------------------------------------------
    def _push_host(self, v):
        if len(self.host_s) < self.max_hist:
            self.host_s.append(v)
        else:                       # ring overwrite, keeps recent window
            self.host_s[self.steps % self.max_hist] = v

    def _check_recompile(self, it):
        new = self.builds.new()
        size = self.builds.count - len(new)
        for b in new:
            size += 1
            cause = b.get("cause") or []
            # a build no step span explained is first when none came before
            first = cause == ["first"] or (not cause and size == 1)
            if not first:
                self.recompiles += 1
            reason = "first_compile" if first else (
                "shape_change" if any(_SHAPE_CHANGED.search(c)
                                      for c in cause) else "retrace")
            self.sink.log("recompile", iter=b.get("iter", it),
                          cache_size=size, first=first, reason=reason,
                          cause=cause, lower_s=b["lower_s"],
                          backend_s=b["backend_s"], cache=b["cache"])

    # -- public API --------------------------------------------------------
    def observe(self, it, host_s, result=None, sample=None):
        """Record one step. host_s: dispatch wall seconds. result: the
        step's output (blocked on at sample points). sample: None for the
        automatic cadence, True/False to force. Returns True when this
        step was sampled (i.e. the host already paid the device sync) —
        callers piggyback other fetch-costly sampling on it."""
        self.steps += 1
        self._push_host(host_s)
        self._check_recompile(it)
        if sample is None:
            sample = self._nobs < 2 or self._last_sample_it is None \
                or (it - self._last_sample_it) >= self.sample_every
        self._nobs += 1
        if not sample or result is None:
            return False
        t0 = time.perf_counter()
        try:
            import jax
            jax.block_until_ready(result)
        except Exception:
            pass
        now = time.perf_counter()
        sync_s = now - t0
        ev = {"iter": it, "host_ms": round(host_s * 1e3, 3),
              "sync_ms": round(sync_s * 1e3, 3)}
        if self._last_sample_t is not None and self._last_sample_it is not None:
            k = max(1, it - self._last_sample_it)
            dev = (now - self._last_sample_t) / k
            self.device_s.append(dev)
            ev["device_ms"] = round(dev * 1e3, 3)
            ev["steps_since_sync"] = k
        else:
            # first sample: this step's full wall (dispatch + drain) is
            # the only device estimate available — dominated by compile
            dev = host_s + sync_s
            self.device_s.append(dev)
            ev["device_ms"] = round(dev * 1e3, 3)
            ev["steps_since_sync"] = 1
        self._last_sample_t = now
        self._last_sample_it = it
        self.sink.log("step", **ev)
        if not self._hbm_dead:
            mem = device_memory()
            if mem is None:
                self._hbm_dead = True       # CPU: don't re-probe per sample
            else:
                self.sink.log("hbm", iter=it, **mem)
        return True

    def summary(self):
        host = percentiles([v * 1e3 for v in self.host_s])
        dev = percentiles([v * 1e3 for v in self.device_s])
        out = {"steps": self.steps, "recompiles": self.recompiles,
               "device_samples": len(self.device_s)}
        out.update({f"host_ms_{k}": round(v, 3) for k, v in host.items()})
        out.update({f"device_ms_{k}": round(v, 3) for k, v in dev.items()})
        if self.host_s:
            out["host_ms_mean"] = round(
                sum(self.host_s) / len(self.host_s) * 1e3, 3)
            out["host_ms_max"] = round(max(self.host_s) * 1e3, 3)
        return out

    def flush(self, it=None):
        if self.steps:
            self.sink.log("step_summary", iter=it, name=self.name,
                          **self.summary())
