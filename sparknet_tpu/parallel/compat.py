"""The two jax entry points every mesh solver goes through.

Both are plain ``jax`` API in the one installation this tree runs on
(jax 0.9: ``jax.shard_map`` with ``check_vma``, ``jax.lax.axis_size``);
the names stay so the parallel layer has one place to look when jax
moves them again.
"""

import jax


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=None):
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def axis_size(name):
    """Size of a mesh axis — only valid inside shard_map, like psum."""
    return jax.lax.axis_size(name)
