"""Mean time per step that the loop waited in `next(feed)` (host clock)."""

META = {"name": "input_wait_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "input", "moves": "train_rate"}


def read(ctx):
    waits = ctx["feed_wait_s"]
    if not waits or not ctx["traffic"].get("host_fed"):
        return None
    return 1e3 * sum(waits) / len(waits)
