"""Mean over the last window-many batches of `prefetch.produce`: what the
prefetch worker takes to make one batch (next(source) plus the transform).
Against the step time it is the input layer's headroom: the layer starts
to bind when a step gets shorter than this."""

import program_spans

META = {"name": "prefetch_produce_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "input", "moves": "train_rate"}


def read(ctx):
    made = program_spans.last(ctx, "prefetch.produce")
    if not made:
        return None
    return sum(m["dur_ms"] for m in made) / len(made)
