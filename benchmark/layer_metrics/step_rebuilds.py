"""How many programs the step's enqueue built AGAIN before the window: the
`program.build` records under `solver.enqueue` whose `cause` is not
`first` (the `# setup parts` line quotes each build's cause)."""

import setup_parts

META = {"name": "step_rebuilds", "unit": "programs", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.count(ctx, "step_rebuilds")
