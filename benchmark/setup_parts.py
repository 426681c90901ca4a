"""A run's set-up as a closed ledger, from the program's own records: every
instant of the main thread between the package's first import and the
window's first step goes to ONE part, so the parts add up to the interval
by construction (`step_parts.py` does the same for a step's device time).

The interval runs from the start of the program's `package.import` record
to the start of the window's first `solver.step`; it is read from the ring
of `sparknet_tpu.obs.trace.default_tracer()` alone. Every instant goes to
the innermost record open over it on the main thread (the one that started
last among those running then: `step_parts.self_times`), and that record,
with the records round it, to a part:

- `import`: `package.import` (the package's imports, the builder's net),
  or an `import.kernel` wherever it stands;
- `net.build`: `net.build` under `solver.init`, outside any program's build;
- `init.build`: a `program.build` under `solver.init` or `solver.history`,
  the one-blob fill programs;
- `init.rest`: the rest of `solver.init` and `solver.history`;
- `step.prep`: `solver.prep`;
- `step.trace`: a `compile.trace` under `solver.enqueue` (their union: a
  jitted function traced inside the step nests in the step's own trace),
  less `import.kernel`;
- `step.lower`: a `compile.lower` under `solver.enqueue`;
- `step.backend`: a `compile.backend` under `solver.enqueue`, the compile
  or the cache's load;
- `step.enqueue_rest`: the rest of `solver.enqueue` (and of `solver.step`):
  the call itself, a build's time between its lowering and its backend
  event;
- `fetch`: `solver.fetch`;
- `outside`: no span of the program open — the harness's weights and feed,
  its waits for a loss, its readings; a record of the program that is none
  of the above (`prefetch.wait`, a program built under no span) counts here
  too.

`outside` is split by the harness's marks (`harness.MARKS`, on the same
clock) in the `# setup parts` line, which every traced run prints once: the
interval, the parts, the counts, `outside_build_s` (programs built under no
span: the harness's own), `harness_setup_s` (what `run.py` reports as
`setup_s`, from the marks), `before_import_s` and `device_s`, the two
things that stand between it and the interval, and `setup_after_import_s`,
the harness's reading less what precedes the package's first import: the
number the interval should agree with.

`ledger(ctx)` gives None to every reader, and the line says why, where the
program writes no `program.build` (a parent commit from before PR 51), where
there is no `package.import` on the ring (the ring dropped it: set-up is no
longer whole; or the first solver of the process recorded elsewhere) and
where the window holds no step.
"""

import json

PARTS = ("import", "net.build", "init.build", "init.rest", "step.prep",
         "step.trace", "step.lower", "step.backend", "step.enqueue_rest",
         "fetch", "outside")
INIT = ("solver.init", "solver.history")

_cache = []


def part_of(chain):
    """chain: the names of the records open over an instant, outermost
    first -> its part."""
    if "import.kernel" in chain or chain[0] == "package.import":
        return "import"
    top = chain[0]
    if top in INIT:
        if "program.build" in chain:
            return "init.build"
        return "net.build" if "net.build" in chain else "init.rest"
    if top == "solver.step":
        if "solver.prep" in chain:
            return "step.prep"
        for name, part in (("compile.trace", "step.trace"),
                           ("compile.lower", "step.lower"),
                           ("compile.backend", "step.backend")):
            if name in chain:
                return part
        return "step.enqueue_rest"
    return "fetch" if top == "solver.fetch" else "outside"


def partition(records, lo, hi):
    """records [dict(name, start_ms, dur_ms, ...)] of ONE thread, (lo, hi)
    in the ring's ms -> ({part: ms} adding up to hi - lo, [(the chain of
    every record that reaches into the interval, the record)])."""
    import step_parts
    kept = []
    for r in records:
        s, e = max(r["start_ms"], lo), min(r["start_ms"] + r["dur_ms"], hi)
        if e > s:
            kept.append((s, -e, r))
    kept.sort(key=lambda k: k[:2])
    own, inside = step_parts.self_times([(s, -neg_e) for s, neg_e, _ in kept])
    parts = dict.fromkeys(PARTS, 0.0)
    chains = []
    for i, (_, _, r) in enumerate(kept):
        chain = (chains[inside[i]][0] if inside[i] is not None else ()) \
            + (r["name"],)
        chains.append((chain, r))
        parts[part_of(chain)] += own[i]
    # the records' own times add up to the union of their intervals: the
    # rest of the interval had no record open
    parts["outside"] += max(hi - lo, 0.0) - sum(own)
    return parts, chains


def marks(tracer):
    """The harness's marks as [(name, start, end)] in the ring's ms, []
    where it made none. A mark is the seconds since the one before, the
    first counted from the process's start, on `perf_counter`: the ring's
    clock in seconds."""
    import harness
    spans = list(harness.MARKS.spans)
    t = harness.MARKS.last - sum(d for _, d in spans)
    out = []
    for name, d in spans:
        out.append((name, *((x * 1e9 - tracer.t0) * 1e-6
                            for x in (t, t + d))))
        t += d
    return out


def _ledger(ctx):
    import program_spans
    tracer = program_spans.default_tracer()
    steps = program_spans.last(ctx, "solver.step")
    if tracer is None or not steps:
        return None, "no step of the program in the window"
    recs = tracer.spans()
    if not any(r["name"] == "program.build" for r in recs):
        return None, ("the program writes no program.build record (a "
                      "commit from before PR 51)")
    start = next((r for r in recs if r["name"] == "package.import"), None)
    if start is None:
        dropped = getattr(tracer, "dropped", 0)
        return None, (f"the ring dropped {dropped} records, set-up's among "
                      "them" if dropped else "no package.import record on "
                      "the default tracer's ring")
    lo, hi = start["start_ms"], steps[0]["start_ms"]
    mine = [r for r in recs if r["tid"] == start["tid"]]
    parts, chains = partition(mine, lo, hi)
    built = [(chain, r) for chain, r in chains if r["name"] == "program.build"]
    # programs built under no span of the program: the harness's own
    loose = [r for chain, r in built if part_of(chain) == "outside"]
    made = marks(tracer)
    # `outside` under each of the harness's marks: the same partition, of
    # the mark's share of the interval
    outside_by_mark = {}
    for name, a, b in made:
        if min(b, hi) > max(a, lo):
            ms = partition(mine, max(a, lo), min(b, hi))[0]["outside"]
            outside_by_mark[name] = outside_by_mark.get(name, 0.0) + ms
    device_ms = sum(b - a for name, a, b in made if name == "device")
    device_after = sum(max(0.0, b - max(a, lo)) for name, a, b in made
                       if name == "device")
    settled = next((b for name, a, b in made if name == "settle"), None)
    out = {
        "interval_s": (hi - lo) * 1e-3,
        "parts": {p: ms * 1e-3 for p, ms in parts.items()},
        "kernel_import_s": sum(
            min(r["start_ms"] + r["dur_ms"], hi) - max(r["start_ms"], lo)
            for _, r in chains if r["name"] == "import.kernel") * 1e-3,
        "kernel_modules": [r["module"] for _, r in chains
                           if r["name"] == "import.kernel"],
        "init_programs": sum(1 for chain, _ in built if chain[0] in INIT),
        "step_builds": [
            {k: r.get(k) for k in ("iter", "nth", "cache", "lower_s",
                                   "backend_s", "cause", "changed")}
            for _, r in built if r["parent"] == "solver.enqueue"],
        "outside_build_s": sum(r["dur_ms"] for r in loose) * 1e-3,
        "outside_programs": len(loose),
        "outside_by_mark": {n: ms * 1e-3 for n, ms in outside_by_mark.items()},
        "modules": start.get("modules"), "pallas": start.get("pallas"),
        "device_s": device_ms * 1e-3,
        # what run.py reports as setup_s: the process's start to the
        # `settle` mark, less the accelerator runtime's start
        "harness_setup_s": None if settled is None
        else (settled - made[0][1] - device_ms) * 1e-3,
        "before_import_s": (lo - made[0][1]) * 1e-3 if made else None,
        # the same less what precedes the package's first import: what the
        # interval should agree with
        "setup_after_import_s": None if settled is None
        else (settled - lo - device_after) * 1e-3,
        "dropped": getattr(tracer, "dropped", 0),
    }
    out["step_rebuilds"] = sum(1 for b in out["step_builds"]
                               if b["cause"] != ["first"])
    return out, None


def _rounded(x):
    """`x` with every float in it to the microsecond, for the line."""
    if isinstance(x, float):
        return round(x, 6)
    if isinstance(x, dict):
        return {k: _rounded(v) for k, v in x.items()}
    return [_rounded(v) for v in x] if isinstance(x, list) else x


def ledger(ctx):
    """The run's set-up ledger, or None; computed and said once a
    process."""
    if not _cache:
        import harness
        out, why = _ledger(ctx)
        _cache.append(out)
        if out is None:
            harness.say(f"# setup parts none: {why}")
        else:
            harness.say("# setup parts " + json.dumps(_rounded(
                dict(out, sum_s=sum(out["parts"].values())))))
    return _cache[0]


def seconds(ctx, part):
    """Seconds of set-up in `part`, or None where there is no ledger."""
    led = ledger(ctx)
    return None if led is None else led["parts"][part]


def count(ctx, key):
    """One of the ledger's counts (`init_programs`, `step_rebuilds`) or
    sums (`kernel_import_s`), or None where there is no ledger."""
    led = ledger(ctx)
    return None if led is None else led[key]
