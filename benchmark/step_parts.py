"""A step's device time as a closed ledger: every instant in which the
device is busy goes to ONE operation, that operation to one named part and
one phase, and what the program's names do not reach is a number
(`unscoped`), not a remainder found by subtraction.

Two halves, as in `trace_reduce`. `read_ops` parses the raw XSpace once a
run (the file `scope_seconds` parses) and keeps EVERY event of the device
planes' "XLA Ops" line, with its operation's path (`tf_op`, the name stack
jax wrote: `jit(step)/transpose(jvp(<layer>))/attn_proj_in/dot_general`)
or without one (what XLA made itself: a relayout, a copy). `partition` is a
pure function of plain tuples, which the tests drive with hand-made events.

**The partition.** On one chip the events of the line nest (a `while` spans
the operations of its body, which may hold a `while` again); every instant
goes to the operation that started last among those running then, so a
container (`while`, `conditional`, `call`) owns only what no operation of
its body covers: each operation's SELF time, a span less what its children
cover. Self times add up to the union of the intervals, which is `busy_s`
of `trace_reduce`; the mean over the chips is reported.

**The part** of an operation, from its path with the transformations taken
off (`jit(..)` elements dropped, `jvp(`, `transpose(`, `)` removed, the
primitive at the end left out), the first that applies:

1. the deepest element that is one of `INNER`, the scopes a mixer or an MoE
   layer opens inside its own (sparknet_tpu/graph/compiler.py:PART_OF_TYPE
   lists them with their modules);
2. the innermost layer of the net on the path -> that layer's part in the
   program's `net.parts` record (`conv`, `norm`, `proj`, `head`, ...: the
   program decides by the layer's type; no layer name is known here);
3. `update`, `input_transform`, `total_loss` (-> `loss`), `grad_accum`,
   `grad_exchange` (-> `collective`: a data-parallel step's all-reduce with
   the buckets laid flat and cut up again round it);
4. an element `layer_scan.<block>` and no layer -> `scan_carry`: the
   `while` of a scan over blocks, its carry, the groups' parameters stacked
   and the buffers jax stacks for the backward pass;
5. a collective by its HLO opcode that none of the above claims ->
   `collective` (the four-chip cell's `psum.N`);
6. else `unscoped`: no path at all, or a path with none of the above.

In a trace, an operation that answers with a CONTAINER's path and is none
itself (a `copy` or a `fusion` whose path ends in `while`: XLA made it
inside the loop's body and gave it the loop's metadata) is `unscoped` too,
whatever the path holds (`made_inside`): the program named the loop, not
this operation, and the loop's body spans many parts.

**The phase**: `update` under the `update` scope; `recompute` where the
path holds `rematted_computation` — the element `jax.checkpoint` writes
round the replay of a block's forward inside the backward pass
(`.../checkpoint/rematted_computation/<layer>/mul`; what it runs for the
backward pass proper has `checkpoint` alone) —; `backward` where it holds
`transpose(`, and for `collective` and `grad_accum` (the gradient's
exchange and sum); else `forward`. An operation WITHOUT a path takes the
phase of the container it runs inside, else of the last operation before it
on its chip whose path said one (not a `collective`'s: XLA schedules a
small one of the exchange's anywhere).

`ledger(ctx)` does this once a run for all readers (the parse is cached by
the trace's path) and says one `# parts` line through `harness.say`: ms a
step of every part by phase `[forward, recompute, backward, update]`, their
sum beside `trace_reduce`'s busy time, the ten largest `unscoped`
operations with where they ran, and the seconds the parse and the
partition took. It gives None — and the line says why — where
the program wrote no `net.parts` record (a parent commit), where the trace
holds no device operation (a rehearsal), and where the trace's names are
older than the program: jax leaves metadata out of the compile cache's key,
so a step loaded from a cache that an earlier commit wrote answers with
that commit's scopes, and a mixer's time would be read under the wrong
part. A fusion answers with the path of its root, so a part's time is that
of the fusions rooted in it: milliseconds add up, shares of a peak would
not (PERF.md sets the FLOPs beside them).
"""

import functools
import importlib.util
import json
import os
import re
import sys
import time

import trace_reduce

INNER = frozenset((
    "attn_proj_in", "rope", "attn_core", "attn_proj_out",
    "gdn_proj_in", "gdn_conv", "gdn_scan", "gdn_gate_norm", "gdn_proj_out",
    "shortconv_in", "shortconv_mix", "shortconv_out",
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
    "moe_glue"))
STEP_SCOPES = {"update": "update", "input_transform": "input_transform",
               "total_loss": "loss", "grad_accum": "grad_accum",
               "grad_exchange": "collective"}
SCAN_PREFIX = "layer_scan."
WHAT = 96                   # characters of an unscoped operation's HLO text
PHASES = ("forward", "recompute", "backward", "update")
#: a net with such a layer traces this scope since the scopes were closed;
#: a trace of it without one was compiled before
NEWEST_SCOPE = {"attn": "attn_proj_in", "gdn": "gdn_proj_in"}

# an HLO text's opcode stands before the '(' of its operands, after a
# result shape that may be a tuple with commas of its own; a name alone
# (an asynchronous line's, a test's) is its instruction's
_COLLECTIVE = re.compile(r"\s(all-reduce|reduce-scatter|all-gather|"
                         r"collective-permute|all-to-all)(-start|-done)?\(|"
                         r"^%?(all-reduce|reduce-scatter|all-gather|"
                         r"collective-permute|all-to-all)[-.\w]*$")
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAP = re.compile(r"\w+\(|\)")
#: primitives whose operation holds other operations, and the HLO opcodes
#: they become
_CONTAINER_PRIMS = frozenset(("while", "cond", "closed_call", "remat2",
                              "checkpoint", "custom_vjp_call",
                              "custom_jvp_call"))
_CONTAINER_OP = re.compile(r"\s(while|conditional|call)\(|"
                           r"^(while|conditional|call)[.\d]*$")

_cache = {}


@functools.lru_cache(maxsize=None)
def elements(path):
    """The scopes of a path, outermost first: the primitive at the end
    left out (a call of a jitted function ends in its `jit(..)`), the
    transformations taken off. A path may be several joined by ';'
    (operations XLA merged): the first speaks."""
    path = path.split(";", 1)[0].rstrip(":")
    els = _WRAP.sub("", _JIT.sub("\0", path)).split("/")
    return tuple(e for e in els[:-1] if e and e != "\0")


def made_inside(name, path):
    """True where an operation answers with the path of a loop, a
    conditional or the call of a loop's or a checkpoint's body, and is no
    container itself. (Not so under a jitted helper's `jit(..)`: its body
    lies inside one scope.)"""
    last = path.split(";", 1)[0].rstrip(":").rsplit("/", 1)[-1]
    if last not in _CONTAINER_PRIMS:
        return False
    return not _CONTAINER_OP.search(name.split(" = ", 1)[-1])


def phase_of(path):
    """The phase a path says, or None where there is no path."""
    if not path:
        return None
    if "update" in elements(path):
        return "update"
    if "rematted_computation" in path:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


class Parts:
    """`net.parts` indexed for the paths of one trace: part_of(name, path)
    answers from a memo, one entry an operation."""

    def __init__(self, layers):
        self.by_last = {}
        for name, part in (layers or {}).items():
            els = tuple(name.split("/"))
            self.by_last.setdefault(els[-1], []).append((els, part))
        for found in self.by_last.values():
            found.sort(key=lambda lp: -len(lp[0]))      # the longest first
        self.memo = {}

    def layer_part(self, els):
        """The part of the innermost layer whose name lies on `els`."""
        for end in range(len(els), 0, -1):
            for name, part in self.by_last.get(els[end - 1], ()):
                if tuple(els[end - len(name):end]) == name:
                    return part
        return None

    def part_of(self, name, path):
        key = (name, path)
        if key not in self.memo:
            self.memo[key] = self._part_of(name, path)
        return self.memo[key]

    def _part_of(self, name, path):
        els = elements(path) if path else ()
        for e in reversed(els):
            if e in INNER:
                return e
        part = self.layer_part(els)
        if part:
            return part
        for e in reversed(els):
            if e in STEP_SCOPES:
                return STEP_SCOPES[e]
        if any(e.startswith(SCAN_PREFIX) for e in els):
            return "scan_carry"
        text = name.split(" = ", 1)[-1]
        return "collective" if _COLLECTIVE.search(text) else "unscoped"


def self_times(spans):
    """spans [(start, end)] of one chip, sorted by (start, -end) -> ([the
    time in which each is the one that started last among those running],
    [the index of the span it started inside, or None]). The self times
    add up to the length of the union of the spans."""
    own, inside = [0] * len(spans), [None] * len(spans)
    stack, at = [], None                 # indices running, innermost last
    for i, (start, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= start:
            j = stack.pop()
            own[j] += max(spans[j][1] - at, 0)
            at = max(at, spans[j][1])
        if stack:
            own[stack[-1]] += max(start - at, 0)
            inside[i] = stack[-1]
        at = start if at is None else max(at, start)
        stack.append(i)
    while stack:
        j = stack.pop()
        own[j] += max(spans[j][1] - at, 0)
        at = max(at, spans[j][1])
    return own, inside


def partition(ops, window, layers, top=10):
    """ops [(chip, start, end, name, path)] (any one unit of time, `path`
    "" where the operation has none), window (lo, hi), layers {layer name:
    part} -> {"chips", "busy": mean over the chips of the time some
    operation ran inside the window, "parts": {part: {phase: time}} (mean
    over the chips; adds up to busy), "unscoped": the `top` largest
    [operation, time, where, what]}, where = the container it ran inside,
    else the first element of its path; what = the start of its HLO
    text."""
    lo, hi = window
    table = Parts(layers)
    by_chip = {}
    for chip, s, e, name, path in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_chip.setdefault(chip, []).append((s, -e, name, path))
    parts, loose = {}, {}
    for events in by_chip.values():
        events.sort()
        own, inside = self_times([(s, -neg_e) for s, neg_e, _, _ in events])
        phases, last_phase = [], "forward"
        for i, (_, _, name, path) in enumerate(events):
            part = "unscoped" if path and made_inside(name, path) \
                else table.part_of(name, path)
            outer = inside[i]
            if part in ("collective", "grad_accum"):
                # wherever XLA schedules it (a shard's index may be read
                # in the middle of the forward pass): it says nothing of
                # the operations without a path that follow
                phase = "backward"
            else:
                phase = phase_of(path) or (last_phase if outer is None
                                           else phases[outer])
                if path:
                    last_phase = phase
            phases.append(phase)
            row = parts.setdefault(part, {})
            row[phase] = row.get(phase, 0) + own[i]
            if part == "unscoped" and own[i]:
                where = path.split("/", 1)[0] if outer is None else \
                    "in " + trace_reduce.op_name(events[outer][2])
                key = (trace_reduce.op_name(name), where,
                       name.split(" = ", 1)[-1][:WHAT])
                loose[key] = loose.get(key, 0) + own[i]
    n = max(len(by_chip), 1)
    largest = sorted(loose.items(), key=lambda kv: -kv[1])[:top]
    parts = {p: {ph: t / n for ph, t in row.items() if t}
             for p, row in parts.items()}
    parts = {p: row for p, row in parts.items() if row}
    return {"chips": len(by_chip), "parts": parts,
            "busy": sum(t for row in parts.values() for t in row.values()),
            "unscoped": [[name, t / n, where, what]
                         for (name, where, what), t in largest]}


def _xplane_pb2():
    """The XSpace message classes: tensorflow's generated module, loaded
    from its file without importing the package around it (10 s of a
    traced run) unless a reader before this one already has; None where
    there is none."""
    name = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    if name in sys.modules:
        return sys.modules[name]
    try:
        spec = importlib.util.find_spec("tensorflow")
        path = os.path.join(spec.submodule_search_locations[0], "tsl",
                            "profiler", "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        try:
            return importlib.import_module(name)
        except ImportError:
            return None


def read_ops(path):
    """([(chip, start_ps, end_ps, name, tf_op or "")] of every event of the
    device planes' ops line, (lo_ps, hi_ps) of the host's bench.unit
    spans, of all the operations where the host wrote none: a trace of
    `sparknet train --profile`), or None: no protobuf module, no device
    operation."""
    xplane_pb2 = _xplane_pb2()
    if xplane_pb2 is None:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, units = [], []
    for plane in space.planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        known = {}
        unit_ids = () if device else {
            mid for mid, meta in plane.event_metadata.items()
            if meta.name == trace_reduce.UNIT_SPAN}
        if not device and not unit_ids:
            continue
        for line in plane.lines:
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            base = line.timestamp_ns * 1000
            for ev in line.events:
                start = base + ev.offset_ps
                if not device:
                    if ev.metadata_id in unit_ids:
                        units.append((start, start + ev.duration_ps))
                    continue
                if ev.metadata_id not in known:
                    meta = plane.event_metadata[ev.metadata_id]
                    tf_op = ""
                    for st in meta.stats:
                        if stat_names.get(st.metadata_id) == "tf_op":
                            tf_op = st.str_value or stat_names.get(
                                st.ref_value, "")
                    known[ev.metadata_id] = (meta.name, tf_op)
                ops.append((plane.name, start, start + ev.duration_ps,
                            *known[ev.metadata_id]))
    if not ops:
        return None
    if not units:               # no harness round the steps: all that ran
        units = [(min(op[1] for op in ops), max(op[2] for op in ops))]
    return ops, (min(s for s, _ in units), max(e for _, e in units))


def net_parts():
    """The newest `net.parts` record of this process's ring, or None."""
    import program_spans
    tracer = program_spans.default_tracer()
    recs = tracer.spans("net.parts") if tracer is not None else []
    return recs[-1] if recs else None


def _ledger(ctx):
    import scope_seconds
    steps, xplane = scope_seconds.steps(ctx), ctx.get("xplane")
    if not xplane or not steps:
        return None, "no traced step"
    rec = net_parts()
    if rec is None:
        return None, "the program wrote no net.parts record"
    got = read_ops(xplane)
    if got is None:
        return None, "no device operation in the trace"
    out = partition(*got, rec["parts"])
    missing = sorted(scope for part, scope in NEWEST_SCOPE.items()
                     if part in rec["parts"].values()
                     and scope not in out["parts"])
    if missing:
        return None, (f"the net has layers that open {missing} and the "
                      "trace has no such scope: the step came from a "
                      "compile cache that an earlier commit wrote")
    per_step = 1e-9 / steps                  # picoseconds -> ms a step
    out["net"], out["steps"] = rec["net"], steps
    out["busy_ms"] = out["busy"] * per_step
    out["ms"] = {p: [row.get(ph, 0) * per_step for ph in PHASES]
                 for p, row in out["parts"].items()}
    out["unscoped"] = [[n, t * per_step, *w] for n, t, *w in out["unscoped"]]
    return out, None


def ledger(ctx):
    """The run's ledger (`partition` in ms a step under "ms", with
    "busy_ms", "steps", "unscoped"), or None; computed and said once."""
    key = ctx.get("xplane")
    if key not in _cache:
        import harness
        t0 = time.perf_counter()
        out, why = _ledger(ctx)
        took = time.perf_counter() - t0
        _cache.clear()
        _cache[key] = out
        if out is None:
            harness.say(f"# parts none: {why}")
        else:
            rows = sorted(out["ms"].items(), key=lambda kv: -sum(kv[1]))
            # beside the sum, what `trace_reduce` reads as busy: the check
            busy_s = (ctx.get("trace") or {}).get("busy_s")
            harness.say("# parts " + json.dumps({
                "net": out["net"], "steps": out["steps"],
                "chips": out["chips"], "sum_ms": round(out["busy_ms"], 4),
                "busy_ms": busy_s and round(busy_s * 1e3 / out["steps"], 4),
                "phases": list(PHASES),
                "parts": {p: [round(x, 4) for x in row] for p, row in rows},
                "unscoped": [[n, round(t, 4), *w]
                             for n, t, *w in out["unscoped"]],
                "seconds": round(took, 3)}))
    return _cache[key]


def ms(ctx, parts=None, phases=None):
    """Device ms a step in `parts` (all where None) and `phases` (all where
    None), or None where there is no ledger."""
    led = ledger(ctx)
    if led is None:
        return None
    cols = [i for i, ph in enumerate(PHASES) if phases is None
            or ph in phases]
    return sum(row[i] for p, row in led["ms"].items()
               if parts is None or p in parts for i in cols)
