"""Seconds of set-up in the part `outside` of the set-up ledger: no span of
the program open — the harness's weights and feed, its waits for a loss,
its readings (the `# setup parts` line splits it by the harness's marks)."""

import setup_parts

META = {"name": "setup_outside_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "outside")
