"""`resident_tokens`: one (B, S) int32 batch of token ids made from the seed
on the device, the labels the next tokens of the same draw; every step
trains on it. The ids come from inside the rows of the vocabulary that the
configuration holds (`vocab_size` as sized for the run: a sliced vocabulary
is a smaller vocabulary), each row is a draw of its own, and the input
layer does nothing in the window."""

import jax
import jax.numpy as jnp


class Feed:
    def __init__(self, traffic, config, seed, solver, data_shape,
                 num_classes):
        from weights import seed_key, INPUTS
        b, s = data_shape
        rows = config.get("builder_args", {}).get("vocab_size",
                                                  config.get("vocab_size"))
        draw = jax.random.randint(seed_key(seed, INPUTS), (b, s + 1), 0,
                                  rows, jnp.int32)
        self.rows = int(rows)
        self.batch = {"data": draw[:, :-1], "label": draw[:, 1:]}

    def __iter__(self):
        return self

    def __next__(self):
        return self.batch

    def reference_inputs(self, i):
        return self.batch["data"], self.batch["label"]

    def stats(self):
        return {"vocabulary_rows": self.rows}

    def close(self):
        self.batch = None


def build(**kw):
    return Feed(**kw)
