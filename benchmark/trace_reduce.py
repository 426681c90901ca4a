"""From a profiler trace to busy time, idle share, top operations and idle
gaps. Two halves: `read_events` turns an `.xplane.pb` into plain tuples
with nothing but jax.profiler.ProfileData; `reduce_events` is a pure
function of those tuples, which the tests drive with hand-made events.

What a real trace of this program on a TPU v5e holds (PR 24, looked at by
hand): one plane per chip, "/device:TPU:<n>", with the lines "Steps" and
"XLA Modules" (one event per executed program: jit_step, jit__threefry_split
...), "XLA Ops" (one event per executed HLO operation, named by its whole
HLO text: "%fusion.306 = (f32[96,3,11,11]{...}) fusion(...)") and "Async XLA
Ops" (copy-start/slice-start ... spans that overlap the ops); one plane
"/host:CPU" with a line per thread, where `jax.profiler.TraceAnnotation`
spans stand under their own names on the line "python3": the harness's
`bench.*` and, inside them, the program's `sparknet.*` (PR 25), a worker
thread's on that thread's line, which has the same name (PR 27). All lines share one clock, in nanoseconds
from the start of the trace.
"""

import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "sparknet."
UNIT_SPAN = "bench.unit"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path, device_plane=DEVICE_PLANE):
    """[(plane, line, name, start_ns, dur_ns)] of the device planes' lines
    and of the host's `bench.*` and `sparknet.*` spans. Every thread's line
    of a host plane carries the process's name, so a host line is given as
    name/position in its plane: that tells the threads apart."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        on_device = plane.name.startswith(device_plane)
        for i, line in enumerate(plane.lines):
            where = line.name if on_device else f"{line.name}/{i}"
            for ev in line.events:
                if on_device or ev.name.startswith((SPAN_PREFIX,
                                                    PROGRAM_PREFIX)):
                    out.append((plane.name, where, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def op_name(text):
    """"%fusion.306 = (f32[...]) fusion(...)" -> "fusion.306"."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class _Spans:
    """Host spans (name, start, end), looked up by the interval they
    overlap."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def overlapping(self, lo, hi):
        first = bisect.bisect_left(self.starts, lo - self.longest)
        last = bisect.bisect_left(self.starts, hi)
        return [(n, s, e) for n, s, e in self.spans[first:last]
                if min(e, hi) > max(s, lo)]


def _by_most_cover(spans, lo, hi):
    """{span: ns}: the whole gap to the span that covers most of it."""
    cover = {}
    for n, s, e in spans.overlapping(lo, hi):
        cover[n] = cover.get(n, 0.0) + min(e, hi) - max(s, lo)
    return {max(cover, key=cover.get) if cover else "unattributed": hi - lo}


def _by_innermost(spans, lo, hi):
    """{span: ns}: every stretch of the gap to the shortest span that
    covers it (of nested spans the innermost)."""
    over = spans.overlapping(lo, hi)
    cuts = sorted({lo, hi, *(min(max(t, lo), hi)
                             for _, s, e in over for t in (s, e))})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        inside = [(e - s, n) for n, s, e in over if s <= a and e >= b]
        label = min(inside)[1] if inside else "unattributed"
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def reduce_events(events, device_plane=DEVICE_PLANE, ops_line=OPS_LINE,
                  top=10):
    """-> {"devices", "window_s", "busy_s", "idle_pct", "units",
    "device_ops": [[name, s]], "idle_gaps": [[span, s]], "op_seconds":
    {name: s}, "idle_gaps_program": [[span, s]]} or None when no device
    operation was traced.

    The window runs from the start of the first `bench.unit` span to the
    end of the last (all traced events where the host wrote no such span).
    busy_s is the union of the device operations' intervals inside it,
    averaged over the devices. op_seconds holds every operation's seconds
    inside the window by name, device_ops the `top` of them; operations
    that overlap on one device count each in full there, once in busy_s.
    An idle gap belongs to the `bench.*` span (other than bench.unit) that
    covers most of it, and stretch by stretch to the innermost `sparknet.*`
    span that covers it on the thread that wrote the `bench.*` spans (on
    any thread where there is none)."""
    dev = {}
    bench, program, units, bench_lines = [], [], [], set()
    for plane, line, name, start, dur in events:
        if plane.startswith(device_plane):
            if line == ops_line:
                dev.setdefault(plane, []).append(
                    (op_name(name), start, start + dur))
        elif name.startswith(PROGRAM_PREFIX):
            program.append(((plane, line), name, start, start + dur))
        elif name.startswith(SPAN_PREFIX):
            bench_lines.add((plane, line))
            if name == UNIT_SPAN:
                units.append((start, start + dur))
            else:
                bench.append((name, start, start + dur))
    if not dev:
        return None
    bench = _Spans(bench)
    program = _Spans([sp[1:] for sp in program
                      if not bench_lines or sp[0] in bench_lines])
    if units:
        lo, hi = min(s for s, _ in units), max(e for _, e in units)
    else:
        ops = [iv for evs in dev.values() for iv in evs]
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    window = hi - lo
    busy_total, by_name, gap_by_span, gap_by_program = 0.0, {}, {}, {}
    for evs in dev.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        for n, s, e in inside:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
        merged = _union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            for total, part in ((gap_by_span, _by_most_cover(bench, gs, ge)),
                                (gap_by_program,
                                 _by_innermost(program, gs, ge))):
                for label, ns in part.items():
                    total[label] = total.get(label, 0.0) + ns
    n_dev = len(dev)
    busy = busy_total / n_dev

    def ranked(d):
        return [[k, v / n_dev * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]
    ops = ranked(by_name)
    return {"devices": n_dev, "window_s": window * 1e-9,
            "busy_s": busy * 1e-9,
            "idle_pct": 100.0 * (1.0 - busy / window),
            "units": sum(1 for s, e in units if s >= lo and e <= hi),
            "device_ops": ops[:top],
            "idle_gaps": ranked(gap_by_span)[:top],
            "op_seconds": dict(ops),
            "idle_gaps_program": ranked(gap_by_program)}
