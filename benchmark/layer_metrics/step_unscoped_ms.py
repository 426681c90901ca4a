"""Device milliseconds a step that the program's names do not reach: the
part `unscoped` of `step_parts`' ledger, every phase — operations with no
path at all (what XLA made itself: a relayout, a copy) or with a path that
holds no layer of the net and no scope of the program (another program's
operations between the steps: the key split). The check on every other
reader of a scope: what they read is complete only as far as this is small.
The `# parts` line of the run names the ten largest."""

import step_parts

META = {"name": "step_unscoped_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops kernels",
        "moves": "train_rate"}

PARTS = ("unscoped",)


def read(ctx):
    return step_parts.ms(ctx, PARTS)
