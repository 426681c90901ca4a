"""`resident`: one batch made from the seed on the device, in bf16; every
step trains on it (the shape of bench.py's bench_synthetic). The input layer
does nothing in the window."""

import jax
import jax.numpy as jnp


class Feed:
    def __init__(self, traffic, config, seed, solver, data_shape,
                 num_classes):
        @jax.jit
        def make(key):
            kd, kl = jax.random.split(key)
            return {"data": jax.random.normal(kd, data_shape, jnp.bfloat16),
                    "label": jax.random.randint(kl, (data_shape[0],), 0,
                                                num_classes, jnp.int32)}
        from weights import seed_key, INPUTS
        self.batch = make(seed_key(seed, INPUTS))

    def __iter__(self):
        return self

    def __next__(self):
        return self.batch

    def reference_inputs(self, i):
        """(data, labels) of draw `i` as the net sees them."""
        return self.batch["data"], self.batch["label"]

    def stats(self):
        return {}

    def close(self):
        self.batch = None


def build(**kw):
    return Feed(**kw)
