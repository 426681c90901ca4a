"""The configuration's conv/fc FLOPs of the traced steps over (device busy
time x the chip's published bf16 peak): the whole step's share of its
compute roofline. Cannot pass 100% unless flops.py counts too much or the
trace misses device time."""

META = {"name": "mxu_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["units"] or tr["busy_s"] <= 0:
        return None
    steps = tr["units"] * ctx["sync_every"]
    flops = steps * ctx["flops_per_step"] / ctx["chips"]
    # busy_s spans the whole traced window; only whole units are counted,
    # and the window is cut to whole units, so the two cover the same steps
    return 100.0 * flops / (tr["busy_s"] * ctx["peak"]["bf16_flops"])
