"""Virtual channel-concat ("inception fission") — a TPU-first graph pass.

Profiling GoogLeNet on a TPU chip shows the training step dominated not by
convolutions but by data movement the Concat layers induce: the gradient
of every inception concatenate is a set of big channel `slice`s (~1 GB/step
at batch 128 across the 9 modules), pure HBM traffic with zero FLOPs. The
reference pays the same cost structure on GPU (concat_layer.cu copies in
both directions) and simply eats it; on TPU, where HBM bandwidth is the
binding resource, it is worth removing structurally.

The pass makes channel-concats *virtual*: a Concat over dim 1 yields a
`Branches` value (the list of branch tensors) instead of one fused array.
Consumers that can consume branches directly do so:

  * Convolution (group=1) fissions over input channels:
        conv(concat(x_1..x_k), W) == sum_i conv(x_i, W[:, o_i:o_i+c_i])
    — same single weight blob (checkpoint format unchanged), the slices
    now taken from the *small* weights instead of the huge activations,
    and the concat gradient disappears entirely: each branch gets its
    input gradient straight from its own conv's backward.
  * Pooling (MAX/AVE) is per-channel, so it maps over branches and stays
    virtual (the branch then reaches the pool-proj conv, which fissions).

Any other consumer (LRN, InnerProduct, Dropout, Slice, losses, ...)
materializes the real concatenate lazily; XLA CSE dedups repeated
materializations and DCE removes unused ones. Numerics: fission reorders
the input-channel summation (k partial convs instead of one), so outputs
match the fused form to accumulation rounding, not bit-exactly.

On for every net (``CompiledNet.fission``); tests/test_fission.py clears
the attribute on the net it builds to compile the literal graph.
"""

import jax.numpy as jnp

MAX_POOL, AVE_POOL = 0, 1


class Branches:
    """A channel-concat that was never materialized: an ordered list of
    4D arrays agreeing on every dim but the channel axis (1)."""

    __slots__ = ("parts",)
    axis = 1

    def __init__(self, parts):
        flat = []
        for p in parts:
            if isinstance(p, Branches):
                flat.extend(p.parts)
            else:
                flat.append(p)
        self.parts = flat

    @property
    def channels(self):
        return [p.shape[self.axis] for p in self.parts]

    def concat(self):
        return jnp.concatenate(self.parts, axis=self.axis)


def materialize(v):
    return v.concat() if isinstance(v, Branches) else v


def try_apply(lp, impl, lparams, bvals, train, rng):
    """Fission-aware dispatch for one layer. Returns the layer's top values
    (which may contain Branches), or None to mean "run the normal path"
    (the caller materializes any Branches bottoms first)."""
    if lp.type == "Concat" and getattr(impl, "axis", None) == 1 \
            and len(bvals) > 1 \
            and all(getattr(v, "ndim", 4) == 4 or isinstance(v, Branches)
                    for v in bvals):
        return [Branches(bvals)]
    if not any(isinstance(v, Branches) for v in bvals):
        return None
    if lp.type == "Convolution" and impl.group == 1 \
            and isinstance(bvals[0], Branches):
        return [impl.apply_fissioned(lparams, bvals[0], train, rng)]
    if lp.type == "Pooling" and impl.method in (MAX_POOL, AVE_POOL) \
            and isinstance(bvals[0], Branches):
        return [Branches([impl.apply(lparams, [p], train, rng)[0]
                          for p in bvals[0].parts])]
    return None
