"""How many programs `Solver.__init__` obtained: the `program.build` records
under `solver.init` and `solver.history` (the one-blob fill programs of
`net.init` and `Updater.init`, compiled or loaded)."""

import setup_parts

META = {"name": "setup_init_programs", "unit": "programs", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.count(ctx, "init_programs")
