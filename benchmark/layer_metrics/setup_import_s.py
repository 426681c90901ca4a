"""Seconds of set-up in the part `import` of the set-up ledger
(`setup_parts.py`): the program's `package.import` record — from the
package's first line to the process's first `Solver.__init__`: its imports,
the builder's `NetParameter` — and every `import.kernel`."""

import setup_parts

META = {"name": "setup_import_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "graph compiler", "moves": "setup_s"}


def read(ctx):
    return setup_parts.seconds(ctx, "import")
