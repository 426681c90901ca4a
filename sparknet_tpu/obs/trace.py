"""Span tracer: the program's one span system.

Every span is three things at once:

  * a record in the tracer's in-memory ring (name, start, end, parent
    span, thread, small attrs such as a step's ``iter``) — always on, a
    few MB at most, oldest dropped first and counted;
  * a ``jax.profiler.TraceAnnotation("sparknet." + name)``: with a
    profiler session open it stands on the ``/host:CPU`` plane in the
    profiler's own nanoseconds, so an idle gap of the device can be laid
    against what the host was doing; with no session it is an idle
    TraceMe (well under a microsecond);
  * for ``span()`` only, one ``span`` event on the JSONL metrics stream
    (name, start/duration in ms relative to the tracer epoch, nesting
    depth, parent, thread id, caller attrs).

The hot loop (``Solver.train_step``, ``PrefetchIterator``) uses
``hot_span()`` / ``step()`` / ``record()``, which never touch the JSONL
sink: per-step cost is a few clock reads, idle TraceMes and ring appends.
Spans nest per thread on ONE process-wide stack, whichever tracer opened
them, so jax's compile events (``compile.trace`` / ``.lower`` /
``.backend`` / ``.cache_load``, heard through ``jax.monitoring``) find the
span open on their thread as parent and land in that span's tracer.

Beside spans the ring holds records that say what a trace of the program
chose or is made of, each written once where it is decided: ``attn.path``,
``gdn.path``, ``moe.path``, ``shortconv.path``, ``remat.kept``, ``moe.load``,
``lm.mtp`` (a net with multi-token-prediction modules: their ``depth``,
the ``loss_weight``, their ``losses`` and the names of the ``shared``
blobs) and ``net.parts`` — the net's name and, for every layer, the part
of a step its device time counts under (graph/compiler.py:PART_OF_TYPE has
the closed list), which is what lets a reader of a device trace add a step
up without knowing any model's layer names. An ``attn.path`` of a latent
layer says ``form`` = ``latent`` and its five sizes besides, with
``shared_key_bytes``, what one pass writes to give the one rotary key a
token a head axis. Every ``attn.path`` says ``heads`` and ``kv_heads`` (a
net may have layers of two head counts), the output gate's form ``gate``
(``none``, ``elementwise``, ``head``) and the rotary's table ``rope``
(``none``, ``plain``, ``yarn``) with ``rope_factor`` and ``rope_scale``,
the factor on cos and sin.

``default_tracer()`` is the process-wide tracer that ``Solver`` and
``PrefetchIterator`` use when none is passed; ``default_tracer().spans()``
reads the ring. "Off" means no profiler session is running: there is no
switch.

Ring timestamps are ``time.perf_counter_ns`` (monotonic: an NTP step or
a suspend mid-run cannot fold spans over each other).

``JaxProfiler`` packages the steady-state one-block device-trace toggle
that used to live inline in cli.cmd_train.
"""

import collections
import json
import os
import threading
import time

from jax import monitoring
from jax.profiler import StepTraceAnnotation, TraceAnnotation

#: records a ring holds: about 75 a second in a host-fed training loop
#: (three per step, three per prefetched batch), so over a minute and a
#: half of history in about 3 MB (370 B a record, measured)
RING = 8192

_tls = threading.local()


def _stack():
    """This thread's open spans, innermost last — shared by every tracer
    of the process."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _Span:
    """One open span: on the thread's stack, annotated for the profiler,
    recorded into its tracer's ring (and sink, for ``span()``) on exit."""

    __slots__ = ("tracer", "name", "attrs", "to_sink", "parent", "t0",
                 "_ann")

    def __init__(self, tracer, name, attrs, to_sink):
        self.tracer, self.name, self.attrs = tracer, name, attrs
        self.to_sink = to_sink

    def __enter__(self):
        st = _stack()
        self.parent = st[-1].name if st else None
        st.append(self)
        self._ann = TraceAnnotation("sparknet." + self.name)
        self._ann.__enter__()
        self.t0 = self.tracer.now_ns()
        return self.attrs

    def __exit__(self, *exc):
        t1 = self.tracer.now_ns()
        self._ann.__exit__(*exc)
        st = _stack()
        st.pop()
        self.tracer._put(self.name, self.t0, t1, len(st), self.parent,
                         self.attrs, self.to_sink)
        return False


class _Step(_Span):
    """A training step on the hot path: the step span (also a
    ``StepTraceAnnotation("train", step_num=iter)``, which profiler tools
    group device work by) split into consecutive phases, each a child
    span carrying the step's ``iter``. After exit ``host_s`` holds the
    seconds of the last phase — the enqueue of the jitted call, which is
    what step accounting reports as host dispatch time."""

    __slots__ = ("_step_ann", "_phase", "host_s")

    def __init__(self, tracer, name, it, first_phase):
        super().__init__(tracer, name, {"iter": it}, False)
        self._phase = first_phase

    def __enter__(self):
        self._step_ann = StepTraceAnnotation("train",
                                             step_num=self.attrs["iter"])
        self._step_ann.__enter__()
        super().__enter__()
        self._phase = self._open(self._phase)
        return self

    def _open(self, name):
        phase = _Span(self.tracer, name, self.attrs, False)
        phase.__enter__()
        return phase

    def phase(self, name):
        """End the current phase and begin ``name``."""
        self._phase.__exit__(None, None, None)
        self._phase = self._open(name)

    def __exit__(self, *exc):
        self._phase.__exit__(*exc)
        self.host_s = (self.tracer.now_ns() - self._phase.t0) * 1e-9
        super().__exit__(*exc)
        self._step_ann.__exit__(*exc)
        return False


class Tracer:
    """Nested spans into a ring, the profiler's host plane, and (for
    ``span()``) a MetricsLogger sink. sink=None -> nothing hits the JSONL."""

    now_ns = staticmethod(time.perf_counter_ns)    # the ring's clock

    def __init__(self, sink=None, max_buffer=RING):
        self.sink = sink
        self.t0 = self.now_ns()
        self._lock = threading.Lock()
        self._buf = collections.deque(maxlen=max_buffer)  # spk: guarded-by=_lock
        self.dropped = 0            # spk: guarded-by=_lock
        self.put = 0                # spk: guarded-by=_lock
        self.max_buffer = max_buffer
        _listen_for_compiles()

    _stack = staticmethod(_stack)

    def span(self, name, **attrs):
        """Context manager timing one phase; yields the attrs dict so the
        body can attach fields discovered mid-span (attrs["n"] = ...).
        Recorded in the ring and emitted as a ``span`` event."""
        return _Span(self, name, attrs, True)

    def hot_span(self, name, **attrs):
        """``span()`` for the hot loop: ring and profiler only, never the
        JSONL sink."""
        return _Span(self, name, attrs, False)

    def step(self, name, it, first_phase):
        """The hot loop's step span with phases (see ``_Step``)."""
        return _Step(self, name, it, first_phase)

    def record(self, name, start_ns, end_ns, **small):
        """A finished span from ``now_ns()`` stamps the caller already
        holds; its parent is the span open on this thread. Ring only."""
        st = _stack()
        self._put(name, start_ns, end_ns, len(st),
                  st[-1].name if st else None, small, False)

    def instant(self, name, **attrs):
        """A zero-duration mark (Chrome 'instant' event)."""
        now = self.now_ns()
        st = _stack()
        self._put(name, now, now, len(st), st[-1].name if st else None,
                  attrs, True)

    def _put(self, name, start_ns, end_ns, depth, parent, attrs, to_sink):
        rec = (name, start_ns, end_ns, depth, parent,
               threading.get_ident(), attrs)
        with self._lock:
            if len(self._buf) == self.max_buffer:
                self.dropped += 1   # the ring drops its oldest record
            self._buf.append(rec)
            self.put += 1
        if to_sink and self.sink is not None:
            self.sink.log("span", **self._as_dict(rec))

    def _as_dict(self, rec):
        name, start_ns, end_ns, depth, parent, tid, attrs = rec
        out = {"name": name,
               "start_ms": round((start_ns - self.t0) * 1e-6, 6),
               "dur_ms": round((end_ns - start_ns) * 1e-6, 6),
               "depth": depth, "parent": parent, "tid": tid}
        out.update(attrs)
        return out

    def spans(self, *names):
        """The ring's records, oldest first, as dicts (name, start_ms,
        dur_ms, depth, parent, tid + attrs); only those called one of
        ``names`` when any are given."""
        with self._lock:
            recs = list(self._buf)
        return [self._as_dict(r) for r in recs
                if not names or r[0] in names]

    def mark(self):
        """How many records the ring has taken so far, dropped ones
        among them: a place in the stream that `since` reads from. (A
        count of `spans(name)` is no such place: once the ring is full it
        drops from the left, and the count stands still.)"""
        with self._lock:
            return self.put

    def since(self, mark, *names):
        """`spans(*names)` of the records put after `mark()` gave
        `mark`, those the ring has dropped since left out."""
        with self._lock:
            recs = list(self._buf)
            first = self.put - len(recs)    # the oldest record's place
        return [self._as_dict(r) for r in recs[max(0, mark - first):]
                if not names or r[0] in names]

    def export_chrome(self, path):
        """Write buffered spans as a Chrome trace_event JSON file."""
        with self._lock:
            # one consistent snapshot: buffer and its drop count
            recs, dropped = list(self._buf), self.dropped
        return export_chrome(path, [self._as_dict(r) for r in recs],
                             dropped=dropped)


_default = None
_listening = False
# one lock for both module-level singletons; re-entrant because
# default_tracer() constructs a Tracer, which registers the listener
_module_lock = threading.RLock()


def default_tracer():
    """The process-wide tracer: what ``Solver`` and ``PrefetchIterator``
    record into when no tracer is passed, and the handle a reader in the
    same process uses (``default_tracer().spans("solver.step")``)."""
    global _default
    if _default is None:
        with _module_lock:
            if _default is None:
                _default = Tracer()
    return _default


# jax reports each trace, lowering, backend compile and persistent-cache
# load as it ends, on the thread that did it, with the jitted function's
# name (not for cache loads: the backend event that follows names it)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
#: jax reports a trace for every jitted helper it meets while tracing a
#: program — jnp functions by the thousand, under a millisecond each —
#: which would flush the ring; a trace is kept from 10 ms up (a training
#: step traces for seconds)
_MIN_TRACE_S = 0.01


def _on_compile(event, seconds, **kw):
    name = _COMPILE_EVENTS.get(event)
    if name is None or (name == "compile.trace" and seconds < _MIN_TRACE_S):
        return
    st = _stack()
    tracer = st[-1].tracer if st else default_tracer()
    end = tracer.now_ns()
    tracer.record(name, end - int(seconds * 1e9), end,
                  seconds=round(seconds, 6), **kw)


def _listen_for_compiles():
    """Register the compile listener, once per process."""
    global _listening
    if _listening:
        return
    with _module_lock:
        if not _listening:
            monitoring.register_event_duration_secs_listener(_on_compile)
            _listening = True


def chrome_from_spans(spans, pid=None):
    """span records (start_ms/dur_ms/name/tid + attrs) -> trace_event
    'X' (complete) events, timestamps in microseconds."""
    pid = pid if pid is not None else os.getpid()
    skip = {"name", "start_ms", "dur_ms", "tid", "depth", "parent",
            "event", "t", "run"}
    evs = []
    for s in spans:
        args = {k: v for k, v in s.items() if k not in skip}
        if s.get("parent"):
            args["parent"] = s["parent"]
        evs.append({"name": str(s.get("name", "?")),
                    "ph": "X" if s.get("dur_ms", 0) else "i",
                    "ts": round(float(s.get("start_ms", 0.0)) * 1e3, 1),
                    "dur": round(float(s.get("dur_ms", 0.0)) * 1e3, 1),
                    "pid": pid, "tid": int(s.get("tid", 0)) % (1 << 31),
                    "cat": "span", "args": args})
    return evs


def export_chrome(path, spans, pid=None, dropped=0):
    """Write span records to ``path`` in Chrome trace_event format."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    doc = {"traceEvents": chrome_from_spans(spans, pid=pid),
           "displayTimeUnit": "ms"}
    if dropped:
        doc["otherData"] = {"dropped_spans": dropped}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


class JaxProfiler:
    """The steady-state one-block jax.profiler toggle (formerly inline in
    cli.cmd_train): skip the compile-heavy first block of THIS process
    (fresh start or snapshot resume alike) so the trace shows steady-state
    device time (XLA ops, HBM, infeed); runs short enough to have only one
    block trace that block."""

    def __init__(self, logdir, log=print, block_iters=100):
        self.logdir = logdir
        self.log = log or (lambda *a: None)
        self.block_iters = block_iters
        self.active = False
        self.done = False

    def maybe_start(self, blocks_done, iters_remaining):
        if not self.logdir or self.done or self.active:
            return False
        if blocks_done >= 1 or iters_remaining <= self.block_iters:
            import jax
            jax.profiler.start_trace(self.logdir)
            self.active = True
        return self.active

    def maybe_stop(self):
        if not self.active:
            return
        import jax
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        self.log(f"Wrote profiler trace to {self.logdir} "
                 "(view with tensorboard or xprof)")

    def abort(self):
        """Flush the trace of a block that raised — it's the one most
        worth looking at."""
        if self.active:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.active = False
