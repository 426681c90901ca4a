"""Share of the traced window in which no operation ran on the device."""

META = {"name": "device_idle_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "train_rate"}


def read(ctx):
    tr = ctx.get("trace")
    return None if not tr else tr["idle_pct"]
