"""All-reduce time a step that no compute on the same chip covers.

Read from the trace file itself: on each device plane, the intervals of
every collective (an operation of the "XLA Ops" line or a span of an
asynchronous line whose HLO opcode is `all-reduce`, `reduce-scatter`,
`all-gather` or `collective-permute`, their `-start` / `-done` halves
included; jax names the instruction after its primitive, `%psum.79 =
f32[...] all-reduce(...)`, so the opcode is read and not the name) less the
union of the intervals of every other operation of the "XLA Ops" line,
inside the window of whole traced units; the mean over the chips, divided
by the traced steps. Nothing to read (one chip: no collective in the
trace) gives None."""

import re

import trace_reduce

META = {"name": "allreduce_exposed_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "collectives",
        "moves": "train_rate"}

COLLECTIVE = re.compile(r"(^|[\s%])(all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute)(-start|-done)?[.\d]*(\(|$)")


def is_collective(text):
    """An event's name is its HLO text ("%psum.79 = f32[64]{0}
    all-reduce(%x), ...") on the ops line, or an instruction's name on an
    asynchronous line ("all-reduce-start.3")."""
    head = text.split(", ", 1)[0] if " = " in text else text
    return bool(COLLECTIVE.search(head))


def exposed_ns(collective, compute):
    """Length of the union of `collective` intervals that lies outside the
    union of `compute` intervals."""
    total, busy = 0.0, trace_reduce._union(compute)
    for s, e in trace_reduce._union(collective):
        at = s
        for bs, be in busy:
            if be <= at:
                continue
            if bs >= e:
                break
            total += max(0.0, bs - at)
            at = max(at, be)
            if at >= e:
                break
        total += max(0.0, e - at)
    return total


def read(ctx):
    path, tr = ctx.get("xplane"), ctx.get("trace")
    if not path or not tr or not tr["units"]:
        return None
    events = trace_reduce.read_events(path)
    units = [(s, s + d) for _, _, n, s, d in events
             if n == trace_reduce.UNIT_SPAN]
    if not units:
        return None
    lo, hi = min(s for s, _ in units), max(e for _, e in units)
    planes = {}
    for plane, line, name, start, dur in events:
        if not plane.startswith(trace_reduce.DEVICE_PLANE):
            continue
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s:
            continue
        coll, comp = planes.setdefault(plane, ([], []))
        if is_collective(name):
            coll.append((s, e))
        elif line == trace_reduce.OPS_LINE:
            comp.append((s, e))
    if not any(coll for coll, _ in planes.values()):
        return None
    steps = tr["units"] * ctx["sync_every"]
    total = sum(exposed_ns(coll, comp) for coll, comp in planes.values())
    return total / len(planes) / steps * 1e-6
