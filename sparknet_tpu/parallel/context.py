"""Trace-time parallelism context.

Layers that can exploit a mesh axis (Attention's ring mode, sharded
InnerProduct) need to know, while being traced, which named axes the
surrounding shard_map provides. jax deliberately hides this, so the
distributed runners publish it here before tracing the net body. The axis
names get baked into the traced computation — exactly once, at compile time.
"""

import contextlib
import threading

_state = threading.local()


def current_axes():
    """Mapping {logical_axis: mesh_axis_name or None} in effect."""
    return getattr(_state, "axes", {})


@contextlib.contextmanager
def axis_context(**axes):
    """e.g. with axis_context(data="data", seq="seq"): trace the step."""
    prev = current_axes()
    merged = dict(prev)
    merged.update(axes)
    _state.axes = merged
    try:
        yield merged
    finally:
        _state.axes = prev


def axis(name):
    return current_axes().get(name)


def current_world():
    """Trace-time world info published by the distributed solvers:
    {"axis": mesh axis name, "size": N workers, "elastic": bool}.
    Layers that fold per-worker statistics across the data axis (e.g. a
    cross-replica batch norm) consult ``elastic`` to know that the
    surrounding round masks invalid workers out of its collectives —
    and that they should do the same rather than a plain pmean."""
    return getattr(_state, "world", {})


@contextlib.contextmanager
def world_context(**info):
    """e.g. with world_context(axis="data", size=8, elastic=True): trace
    the round body."""
    prev = current_world()
    _state.world = dict(prev, **info)
    try:
        yield _state.world
    finally:
        _state.world = prev


def batch_shard():
    """(mesh axis, shards) while a per-step data-parallel solver traces
    one shard's share of a global batch, else None. A layer that draws a
    random number per sample (Dropout) draws for the whole batch and keeps
    its shard's rows, so that the mesh step equals the one-device step on
    the same global batch; any other random layer takes `shard_key`."""
    return getattr(_state, "batch_shard", None)


@contextlib.contextmanager
def batch_shard_context(axis, shards):
    prev = batch_shard()
    _state.batch_shard = (axis, int(shards))
    try:
        yield
    finally:
        _state.batch_shard = prev


def shard_key(rng):
    """`rng` folded with this shard's index under `batch_shard_context`
    (a stream of its own per shard), `rng` itself elsewhere."""
    shard = batch_shard()
    if shard is None or rng is None:
        return rng
    import jax
    return jax.random.fold_in(rng, jax.lax.axis_index(shard[0]))


def shard_rows(draw, rng, shape):
    """`draw(rng, shape)` for this shard's rows of a per-sample draw: under
    `batch_shard_context` the draw is made for the global batch (`shape[0]`
    x shards rows) and this shard's rows are cut out of it."""
    shard = batch_shard()
    if shard is None:
        return draw(rng, shape)
    import jax
    axis, n = shard
    rows = shape[0]
    full = draw(rng, (rows * n,) + tuple(shape[1:]))
    return jax.lax.dynamic_slice_in_dim(
        full, jax.lax.axis_index(axis) * rows, rows, axis=0)


# -- host topology (multi-host runtime) -------------------------------------
# Unlike the trace-time axis/world contexts above, the host topology is a
# process-wide constant: one process == one fault domain, fixed at
# jax.distributed bring-up. parallel/multihost.py publishes it once;
# everything host-side (heartbeats, coordinated restart, per-host data
# slicing) reads it from here instead of re-deriving it from jax.
_host_topology = None


def publish_host_topology(info):
    """Record this process's host topology (parallel/multihost.py calls
    this after jax.distributed bring-up). ``info``: a mapping with at
    least process_id / num_processes / local_device_count /
    global_device_count."""
    global _host_topology
    _host_topology = dict(info)
    return _host_topology


def current_host():
    """The published host topology dict, or a single-host default when
    the multihost runtime never initialized (the common dev path)."""
    if _host_topology is not None:
        return dict(_host_topology)
    return {"process_id": 0, "num_processes": 1,
            "local_device_count": None, "global_device_count": None}
