"""Share of the SmallThinker cell's traced window in which no operation ran
on the device (`device_idle_pct` lists the cells it was accepted with; this
cell brings its own reader)."""

META = {"name": "st_device_idle_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "train_rate"}


def read(ctx):
    tr = ctx.get("trace")
    return None if not tr else tr["idle_pct"]
