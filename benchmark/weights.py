"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights (not the program), so that the program
under test and the plain reference start from the same numbers and neither
takes anything the other has made. Fillers follow Caffe: gaussian(std),
xavier = uniform(+-sqrt(3 / fan_in)), uniform(lo, hi), constant.
"""

import math

import jax
import jax.numpy as jnp


def _fill(key, shape, filler):
    kind = filler[0]
    if kind == "constant":
        return jnp.full(shape, filler[1], jnp.float32)
    if kind == "gaussian":
        return filler[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "xavier":
        scale = math.sqrt(3.0 / (math.prod(shape) // shape[0]))
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, filler[1],
                                  filler[2])
    raise ValueError(f"unknown filler {filler!r}")


def seed_key(seed, stream):
    """A PRNG key for one of a run's streams (weights, inputs, steps) from
    any whole seed, beyond 32 bits too."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


WEIGHTS, INPUTS, STEPS = 1, 2, 3


def make_weights(specs, seed):
    """{layer: [blob, ...]} in float32 for `specs` (a reference's: per
    layer its blobs' (shape, filler, ...)) from `seed`; the same seed gives
    the same weights."""

    @jax.jit
    def build(key):
        out = {}
        for i, (name, blobs) in enumerate(specs):
            lkey = jax.random.fold_in(key, i)
            out[name] = [_fill(jax.random.fold_in(lkey, j), shape, filler)
                         for j, (shape, filler, *_) in enumerate(blobs)]
        return out
    return build(seed_key(seed, WEIGHTS))
