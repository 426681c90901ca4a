"""Share of the Laguna cell's traced window in which no operation ran on
the device (the accepted readers of the same reading list the cells they
were accepted with; this cell brings its own)."""

META = {"name": "laguna_device_idle_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "train_rate"}


def read(ctx):
    tr = ctx.get("trace")
    return None if not tr else tr["idle_pct"]
