"""Size of the profiler's trace file, read while it is still on disk:
`ctx["xplane"]` is its path, and `run.py` deletes it after the readers."""

import os

META = {"name": "xplane_bytes", "unit": "B", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "train_rate"}


def read(ctx):
    path = ctx.get("xplane")
    return os.path.getsize(path) if path and os.path.exists(path) else None
