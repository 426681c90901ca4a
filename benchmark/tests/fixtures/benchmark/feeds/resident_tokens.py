"""`resident_tokens`: one (B, S) int32 batch of token ids made from the seed
on the device, labels the next tokens of the same draw; every step trains
on it. All rows differ."""

import jax
import jax.numpy as jnp


class Feed:
    def __init__(self, traffic, config, seed, solver, data_shape,
                 num_classes):
        from weights import seed_key, INPUTS
        b, s = data_shape
        draw = jax.random.randint(seed_key(seed, INPUTS), (b, s + 1), 0,
                                  config["builder_args"]["vocab_size"],
                                  jnp.int32)
        self.batch = {"data": draw[:, :-1], "label": draw[:, 1:]}

    def __iter__(self):
        return self

    def __next__(self):
        return self.batch

    def reference_inputs(self, i):
        return self.batch["data"], self.batch["label"]

    def stats(self):
        return {}

    def close(self):
        self.batch = None


def build(**kw):
    return Feed(**kw)
