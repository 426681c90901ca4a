"""What the per-layer metrics read of the program's own spans: the ring of
`sparknet_tpu.obs.trace.default_tracer()`, which the solver and the
prefetch iterator of this process record into when the harness hands them
no tracer. A program without that tracer (a parent commit from before it)
gives every reader nothing to read, and its metric is left out of the line.
"""


def default_tracer():
    """The program's process-wide tracer, or None where it has none."""
    try:
        from sparknet_tpu.obs.trace import default_tracer as get
    except ImportError:
        return None
    return get()


def last(ctx, *names):
    """The window's records called one of `names`: one per step is
    recorded and no step runs after the window, so they are the last
    len(ctx["dispatch_s"]) of them. [] when the tracer holds none."""
    tracer, n = default_tracer(), len(ctx["dispatch_s"])
    if tracer is None or not n:
        return []
    return tracer.spans(*names)[-n:]


def first(*names):
    """The oldest record called one of `names` that the ring still holds."""
    tracer = default_tracer()
    recs = tracer.spans(*names) if tracer is not None else []
    return recs[0] if recs else None
