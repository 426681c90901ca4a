"""Seconds of set-up in the part `init.build` of the set-up ledger: inside
a `program.build` under `solver.init` or `solver.history`, lowering to the
end of the backend's compile or load."""

import setup_parts

META = {"name": "setup_init_build_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "init.build")
