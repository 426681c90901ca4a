"""Mean over the window's gets of `prefetch.wait`: the time the consumer
stood blocked in `PrefetchIterator.__next__`, measured inside it."""

import program_spans

META = {"name": "prefetch_wait_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "input", "moves": "train_rate"}


def read(ctx):
    waits = program_spans.last(ctx, "prefetch.wait")
    if not waits:
        return None
    return sum(w["dur_ms"] for w in waits) / len(waits)
