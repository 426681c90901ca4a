"""Duration of `solver.init`: building the nets and initialising their
parameters inside `Solver.__init__`."""

import program_spans

META = {"name": "solver_init_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    init = program_spans.first("solver.init")
    return None if init is None else init["dur_ms"] / 1e3
