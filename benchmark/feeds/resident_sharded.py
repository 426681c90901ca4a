"""`resident_sharded`: the `resident` batch for a solver that trains over a
mesh. One batch is made from the seed, each chip making its own rows, and
laid over the solver's mesh with the solver's own batch sharding
(`parallel.data_parallel.shard_batch` over `solver.mesh` / `solver.axis`),
in bf16; every step trains on it, so the input layer and the link do
nothing in the window and what is left beside the one-chip step is the
gradient exchange."""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


class Feed:
    def __init__(self, traffic, config, seed, solver, data_shape,
                 num_classes):
        from weights import seed_key, INPUTS
        mesh, axis = solver.mesh, solver.axis
        rows = NamedSharding(mesh, P(axis))

        def make(key):
            kd, kl = jax.random.split(key)
            return {"data": jax.random.normal(kd, data_shape, jnp.bfloat16),
                    "label": jax.random.randint(kl, (data_shape[0],), 0,
                                                num_classes, jnp.int32)}
        # made where it will live: no chip ever holds the whole batch
        self.batch = jax.jit(make, out_shardings={"data": rows,
                                                  "label": rows})(
            seed_key(seed, INPUTS))
        # the solver's own placement: where the rows already lie as it
        # wants them, this moves nothing
        from sparknet_tpu.parallel.data_parallel import shard_batch
        self.batch = shard_batch(self.batch, mesh, axis)

    def __iter__(self):
        return self

    def __next__(self):
        return self.batch

    def reference_inputs(self, i):
        """(data, labels) of draw `i`, the whole batch, for the one-device
        reference: gathered onto the first chip."""
        dev = jax.devices()[0]
        return (jax.device_put(self.batch["data"], dev),
                jax.device_put(self.batch["label"], dev))

    def stats(self):
        return {"rows_per_chip": int(self.batch["data"].addressable_shards[0]
                                     .data.shape[0])}

    def close(self):
        self.batch = None


def build(**kw):
    return Feed(**kw)
