"""Seconds from the process's first `train_step` call until its result is
ready: trace plus compile, or trace plus a load from the compile cache."""

META = {"name": "compile_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return ctx.get("compile_s")
