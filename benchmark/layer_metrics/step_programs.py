"""How many programs the step's enqueue had built before the window: the
`compile.backend` records under `solver.enqueue` that ended before the
window's first step began. jax reports one for every program it obtains,
compiled or loaded from the persistent cache (a load is reported as a
`compile.cache_load` as well, inside it: counting both would count a warm
program twice)."""

import program_spans

META = {"name": "step_programs", "unit": "programs", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    steps = program_spans.last(ctx, "solver.step")
    if not steps:
        return None
    opens = steps[0]["start_ms"]
    built = program_spans.default_tracer().spans("compile.backend")
    return sum(1 for b in built if b["parent"] == "solver.enqueue"
               and b["start_ms"] + b["dur_ms"] <= opens)
