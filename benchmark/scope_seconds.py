"""Device seconds by the program's `jax.named_scope`, for the per-layer
readers that cannot go by an operation's name.

An event of a device plane's "XLA Ops" line carries its operation's path
(`jit(step)/transpose(jvp(block3/moe))/moe_experts/dot_general`) as the
`tf_op` stat of its metadata; `jax.profiler.ProfileData` does not show it,
the raw XSpace does (PERF.md section 3). `seconds(ctx, scopes)` gives, for
each scope name, the seconds inside the window of whole traced units during
which an operation under that scope ran, as the union of their intervals (a
`while` and the operations of its body overlap), mean over the chips. None
when the trace cannot be read that way: no file, no protobuf module, no
`tf_op` in it (a program without the scopes gives an empty reading, and
the metric is left out)."""

import trace_reduce

_cache = {}


def _read(path):
    """[(plane, start_ns, end_ns, tf_op)] of the device planes' ops line,
    and the window (lo, hi) of the host's bench.unit spans."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, units = [], []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        paths = {}
        if device:
            for mid, meta in plane.event_metadata.items():
                for st in meta.stats:
                    if names.get(st.metadata_id) == "tf_op":
                        paths[mid] = st.str_value or names.get(
                            st.ref_value, "")
        for line in plane.lines:
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            base = line.timestamp_ns
            for ev in line.events:
                start = base + ev.offset_ps * 1e-3
                end = start + ev.duration_ps * 1e-3
                if device:
                    if ev.metadata_id in paths:
                        ops.append((plane.name, start, end,
                                    paths[ev.metadata_id]))
                elif plane.event_metadata[ev.metadata_id].name == \
                        trace_reduce.UNIT_SPAN:
                    units.append((start, end))
    if not ops or not units:
        return None
    return ops, (min(s for s, _ in units), max(e for _, e in units))


def seconds(ctx, scopes):
    """{scope: seconds a step's window}, or None."""
    path = ctx.get("xplane")
    if not path:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = _read(path)
    if _cache[path] is None:
        return None
    ops, (lo, hi) = _cache[path]
    planes = sorted({p for p, *_ in ops})
    out = {}
    for scope in scopes:
        mark, total = f"/{scope}/", 0.0
        for plane in planes:
            merged = trace_reduce._union(
                [(max(s, lo), min(e, hi)) for p, s, e, path_ in ops
                 if p == plane and mark in path_ + "/"
                 and min(e, hi) > max(s, lo)])
            total += sum(e - s for s, e in merged)
        out[scope] = total / len(planes) * 1e-9
    return out


def steps(ctx):
    tr = ctx.get("trace")
    return tr["units"] * ctx["sync_every"] if tr and tr["units"] else 0


def roofline_pct(ctx, cost, window_seconds):
    """A kernel's share of its roofline in the LM cell: the least time the
    chip could take for a step's `cost(config, batch)` = (operations,
    bytes) — the larger of operations over its peak and bytes over its
    bandwidth — over the device seconds a step spent, `window_seconds`
    being those of all the window's steps. None where nothing was read."""
    n = steps(ctx)
    if not n or not window_seconds or window_seconds <= 0:
        return None
    import harness
    config = harness.load_json(harness.HERE, "configs",
                               "qwen3_next_80b_a3b.json")
    ops, bytes_ = cost(config, ctx["batch"])
    peak = ctx["peak"]
    least = max(ops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * least / (window_seconds / n)


def scope_roofline_pct(ctx, scope, cost):
    got = seconds(ctx, [scope])
    return roofline_pct(ctx, cost, got[scope]) if got else None
