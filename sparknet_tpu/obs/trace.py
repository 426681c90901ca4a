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

Set-up is a closed ledger too. Every executable the process obtains —
compiled, or loaded from the persistent cache — leaves ONE ``program.build``
record beside jax's ``compile.*`` four, from the start of its lowering to
the end of the backend's event: ``fun_name``, ``nth`` (the count of that
name's builds in the process), ``lower_s``, ``backend_s``, ``cache``
(``hit`` / ``miss`` / ``off``, with ``cache_load_s`` on a hit), the span
open on the thread as parent and the ``iter`` of its ``solver.step``;
``Tracer.builds`` counts them. A build under ``solver.enqueue`` also says
its ``cause`` when the step closes, a list of strings: ``["first"]`` for the
step function's first build, else what differs between the arguments the
solver handed the jitted call this time and at the build before
(``signature`` / ``diff_signatures``: path, shape, dtype, weak type,
sharding, committedness, and the layout of a leaf whose buffer is still
there — a donated leaf keeps the rest on its aval), at most ``MOST_CAUSES``
of them with ``changed``, the number of leaves that differ, or ``["same
signature"]``. The step span holds a reference to the arguments
(``_Step.watch``) and takes a signature only when ``Tracer.builds`` moved
while it was open. ``package.import`` (the package's first line to the
process's first ``Solver.__init__``), ``import.kernel`` (``kernel_import``
round a kernel module's in-branch import, written when the module was not
loaded yet) and ``solver.history`` are the other set-up records;
``benchmark/setup_parts.py`` adds them up.

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
import contextlib
import json
import os
import sys
import threading
import time
import weakref

from jax import monitoring, tree_util
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
    what step accounting reports as host dispatch time.

    ``watch`` keeps a reference to the jitted function and the arguments
    it is about to be called with. Only when the tracer counted a build
    while the step was open does the exit take their signature (from the
    avals, which outlive a donated buffer) and write the ``cause`` on the
    ``program.build`` records of the enqueue: a steady step pays one
    store and two reads of ``Tracer.builds``."""

    __slots__ = ("_step_ann", "_phase", "host_s", "_builds", "_watched",
                 "built")

    def __init__(self, tracer, name, it, first_phase):
        super().__init__(tracer, name, {"iter": it}, False)
        self._phase = first_phase
        self._watched = None
        self.built = None       # [(parent, attrs)] of the builds under it

    def __enter__(self):
        self._step_ann = StepTraceAnnotation("train",
                                             step_num=self.attrs["iter"])
        self._step_ann.__enter__()
        super().__enter__()
        self._builds = self.tracer.builds
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

    def watch(self, fn, args, names=None):
        """``fn(*args)`` is the jitted call this step is about to make;
        ``names`` of the arguments where they are not STEP_ARGS."""
        self._watched = (fn, args, names)

    def __exit__(self, *exc):
        self._phase.__exit__(*exc)
        self.host_s = (self.tracer.now_ns() - self._phase.t0) * 1e-9
        if self.tracer.builds != self._builds:
            self.tracer._explain_builds(self)
        self._watched = None    # the step is over: let go of its batch
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
        self.builds = 0             # spk: guarded-by=_lock
        # step function -> the signature of the arguments at its last build
        self._built_with = weakref.WeakKeyDictionary()
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
        holds; its parent is the span open on this thread. Ring only.
        -> the attrs as the ring holds them."""
        st = _stack()
        self._put(name, start_ns, end_ns, len(st),
                  st[-1].name if st else None, small, False)
        return small

    def _explain_builds(self, step):
        """``step`` is closing and a build was counted while it was open:
        write ``cause`` and ``changed`` on the ``program.build`` records of
        its enqueue, and keep the arguments' signature for the step
        function's next build."""
        built = [attrs for parent, attrs in step.built or ()
                 if parent == "solver.enqueue"]
        if not built or step._watched is None:
            return
        fn, args, names = step._watched
        sig = signature(args, names or STEP_ARGS)
        before = self._built_with.get(fn)
        self._built_with[fn] = sig
        if before is None:
            changed, cause = 0, ["first"]
        else:
            changed, cause = diff_signatures(before, sig)
        for b in built:
            b["cause"], b["changed"] = cause, changed

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

    def _since(self, mark):
        """(the raw records put after `mark`, the place after them)."""
        with self._lock:
            recs = list(self._buf)
            first = self.put - len(recs)    # the oldest record's place
            return recs[max(0, mark - first):], self.put

    def since(self, mark, *names):
        """`spans(*names)` of the records put after `mark()` gave
        `mark`, those the ring has dropped since left out."""
        return [self._as_dict(r) for r in self._since(mark)[0]
                if not names or r[0] in names]

    def export_chrome(self, path):
        """Write buffered spans as a Chrome trace_event JSON file."""
        with self._lock:
            # one consistent snapshot: buffer and its drop count
            recs, dropped = list(self._buf), self.dropped
        return export_chrome(path, [self._as_dict(r) for r in recs],
                             dropped=dropped)


class StepBuilds:
    """A reader's place in one tracer's stream of the programs a step's
    enqueue built (``program.build`` under ``solver.enqueue``): the one
    source of the ``recompile`` event and of ``memstats``' count. A look
    that finds ``Tracer.builds`` where it was reads nothing."""

    def __init__(self, tracer):
        self.tracer = tracer
        self._mark, self._builds = tracer.mark(), tracer.builds
        self.count = 0              # the step's builds seen so far

    def new(self):
        """The step's builds since the last look, oldest first."""
        tr = self.tracer
        if tr.builds == self._builds:
            return []
        self._builds = tr.builds
        recs, self._mark = tr._since(self._mark)
        new = [tr._as_dict(r) for r in recs
               if r[0] == "program.build" and r[4] == "solver.enqueue"]
        self.count += len(new)
        return new


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


# between a program's lowering and its backend event jax says, on the same
# thread, whether the persistent cache answered; a miss is said where the
# entry is then written, so a program that the cache's thresholds leave out
# (jax_persistent_cache_min_compile_time_secs) reads `off` like one
# obtained with no cache at all: the cache did not take it
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_nth = collections.Counter()    # fun_name -> builds; spk: guarded-by=_module_lock


def _pending():
    """What this thread has heard of the program it is obtaining: the
    lowering's (start, seconds, fun_name), the cache's answer, the load's
    seconds."""
    p = getattr(_tls, "pending", None)
    if p is None:
        p = _tls.pending = {"lower": None, "cache": "off", "load_s": None}
    return p


def _on_compile(event, seconds, **kw):
    name = _COMPILE_EVENTS.get(event)
    if name is None or (name == "compile.trace" and seconds < _MIN_TRACE_S):
        return
    st = _stack()
    tracer = st[-1].tracer if st else default_tracer()
    end = tracer.now_ns()
    start = end - int(seconds * 1e9)
    tracer.record(name, start, end, seconds=round(seconds, 6), **kw)
    if name == "compile.lower":
        _pending().update(lower=(start, seconds, kw.get("fun_name")),
                          cache="off", load_s=None)
    elif name == "compile.cache_load":
        _pending()["load_s"] = seconds
    elif name == "compile.backend":
        _program_build(tracer, st, start, end, seconds, kw.get("fun_name"))


def _on_cache_event(event, **kw):
    answer = _CACHE_EVENTS.get(event)
    if answer is not None:
        _pending()["cache"] = answer


def _program_build(tracer, st, start, end, backend_s, fun_name):
    """The backend event closes a program: one ``program.build`` from the
    start of its lowering (the lowering this thread heard last, when it
    was of the same name) to now."""
    p = _pending()
    lower_s = 0.0
    if p["lower"] is not None and p["lower"][2] == fun_name:
        start, lower_s = p["lower"][0], p["lower"][1]
    with _module_lock:
        _nth[fun_name] += 1
        nth = _nth[fun_name]
    attrs = {"fun_name": fun_name, "nth": nth, "lower_s": round(lower_s, 6),
             "backend_s": round(backend_s, 6), "cache": p["cache"]}
    if p["cache"] == "hit" and p["load_s"] is not None:
        attrs["cache_load_s"] = round(p["load_s"], 6)
    p.update(lower=None, cache="off", load_s=None)
    step = next((s for s in reversed(st) if isinstance(s, _Step)), None)
    if step is not None:
        attrs["iter"] = step.attrs["iter"]
    attrs = tracer.record("program.build", start, end, **attrs)
    with tracer._lock:
        tracer.builds += 1
    if step is not None:
        if step.built is None:
            step.built = []
        step.built.append((st[-1].name, attrs))


def _listen_for_compiles():
    """Register the compile listeners, once per process."""
    global _listening
    if _listening:
        return
    with _module_lock:
        if not _listening:
            monitoring.register_event_duration_secs_listener(_on_compile)
            monitoring.register_event_listener(_on_cache_event)
            _listening = True


# -- the arguments a step function was built with ---------------------------

#: the arguments of every solver's jitted step, in order (the mesh solvers
#: pass the last two)
STEP_ARGS = ("params", "state", "history", "batch", "iter", "key", "alive",
             "lag")
FIELDS = ("shape", "dtype", "weak_type", "sharding", "committed", "layout")
MOST_CAUSES = 8


def _key(k):
    """One element of a tree path, bare: a dict's key, a sequence's index,
    an attribute's name."""
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _leaf_signature(leaf, names):
    aval = getattr(leaf, "aval", None)
    if aval is None:        # a numpy array, a Python number
        return (getattr(leaf, "shape", ()),
                str(getattr(leaf, "dtype", type(leaf).__name__)),
                not hasattr(leaf, "dtype"), None, False, None)
    sharding = getattr(leaf, "sharding", None)
    if id(sharding) not in names:
        names[id(sharding)] = None if sharding is None else str(sharding)
    layout = None
    if not leaf.is_deleted():   # a donated buffer took its layout with it
        layout = getattr(leaf.format, "layout", None)
    return (aval.shape, str(aval.dtype), bool(aval.weak_type),
            names[id(sharding)], bool(getattr(leaf, "committed", False)),
            None if layout is None else str(layout))


def signature(args, arg_names=STEP_ARGS):
    """{path: FIELDS of the leaf} for every leaf of a step's arguments, the
    path as ``history/conv1/0/0`` with the argument's name in front. Reads
    no buffer: shape, dtype and weak type from the aval, sharding and
    committedness from the array, which a donated array keeps; the layout
    where the buffer is still there."""
    sig, names = {}, {}
    for i, arg in enumerate(args):
        top = arg_names[i] if i < len(arg_names) else f"arg{i}"
        for path, leaf in tree_util.tree_flatten_with_path(arg)[0]:
            sig["/".join([top] + [_key(k) for k in path])] = \
                _leaf_signature(leaf, names)
    return sig


def diff_signatures(before, now):
    """-> (the number of leaves that differ, what differs as at most
    MOST_CAUSES strings such as ``history/conv1/0/0: committed False -> True``). Leaves
    of one argument that differ alike are said once, with their number;
    nothing differs -> (0, ["same signature"])."""
    alike, changed = {}, 0
    for path in list(now) + [p for p in before if p not in now]:
        a, b = before.get(path), now.get(path)
        if a == b:
            continue
        changed += 1
        if a is None or b is None:
            what = "a new leaf" if a is None else "the leaf is gone"
        else:
            what = ", ".join(f"{f} {x} -> {y}"
                             for f, x, y in zip(FIELDS, a, b) if x != y)
        alike.setdefault((path.split("/", 1)[0], what), []).append(path)
    if not changed:
        return 0, ["same signature"]
    cause = [f"{paths[0]}: {what}" + (
        f" (and {len(paths) - 1} more of {top})" if len(paths) > 1 else "")
        for (top, what), paths in alike.items()]
    return changed, cause[:MOST_CAUSES]


# -- set-up outside any jitted call ----------------------------------------

@contextlib.contextmanager
def kernel_import(module):
    """Round the in-branch import of a kernel module (``with
    kernel_import("sparknet_tpu.ops.pallas_lrn"): from .pallas_lrn import
    ...``): one ``import.kernel`` record with ``module`` when the module
    was not loaded yet — the first such import of a process brings
    ``jax.experimental.pallas``, 1.4 s that a ``compile.trace`` hid."""
    if module in sys.modules:
        yield
        return
    st = _stack()
    tracer = st[-1].tracer if st else default_tracer()
    t0 = tracer.now_ns()
    try:
        yield
    finally:
        tracer.record("import.kernel", t0, tracer.now_ns(), module=module)


_package_import_said = False    # spk: guarded-by=_module_lock


def package_import(tracer, entry_ns):
    """The first ``Solver.__init__`` of the process says what came before
    it: one ``package.import`` from the stamp on the package's first line
    to ``entry_ns``, with the count of the package's ``modules`` loaded
    by then and whether ``pallas`` is among what they brought."""
    global _package_import_said
    with _module_lock:
        if _package_import_said:
            return
        _package_import_said = True
    stamp = getattr(sys.modules.get("sparknet_tpu"), "IMPORT_NS", None)
    if stamp is None:
        return
    tracer.record("package.import", stamp, entry_ns,
                  modules=sum(1 for m in list(sys.modules)
                              if m.startswith("sparknet_tpu.")),
                  pallas="jax.experimental.pallas" in sys.modules)


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
