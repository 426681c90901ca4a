"""The comparison that decides `correct` for a training cell.

The timed object itself — the solver the window drives — takes its first
three steps through the window's own call and feed. From it: each step's
loss; per weight blob the first gradient as the optimizer got it, worked
out from its state after one step by the formula of the configuration's
solver type; per blob the weights' change after the three. The plain
reference follows the same three steps from the same weights and inputs.

Blobs are compared by the worst one: the norm of the difference over the
reference's norm of that blob or of the median blob, whichever is larger
(some gradients are all but zero). The gap between the two norms, which
the difference bounds from above, does not tell float8 from bfloat16: both
round without bias, and a norm averages that out (PERF.md, PR 24 readings).
"""

import statistics

import jax
import jax.numpy as jnp


def leaf_order(specs):
    return [(name, i) for name, blobs in specs for i in range(len(blobs))]


def _from_momentum(h, w0, solver, lr_mult, decay_mult):
    """Caffe's SGD leaves history = lr x lr_mult x (g + weight_decay x
    decay_mult x w0) after one step from a zero history."""
    return h / (solver["base_lr"] * lr_mult) \
        - solver["weight_decay"] * decay_mult * w0


def _from_first_moment(m1, w0, solver, lr_mult, decay_mult):
    """Caffe's Adam leaves its first moment m1 = (1 - momentum) x (g +
    weight_decay x decay_mult x w0) after one step from zero moments: the
    decay is added before the moments are taken."""
    return m1 / (1.0 - solver["momentum"]) \
        - solver["weight_decay"] * decay_mult * w0


GRADIENT_FROM_SLOT0 = {"SGD": _from_momentum, "Adam": _from_first_moment}


def first_gradients(slot0, w0, specs, solver):
    """[blob] of the first step's gradients from the optimizer's state:
    `slot0` is {layer: [blob]} of the first history slot after one step
    from a zero history, and the formula is that of `solver["type"]`
    ("SGD" when absent); each blob's multipliers stand in `specs`."""
    kind = solver.get("type", "SGD")
    if kind not in GRADIENT_FROM_SLOT0:
        raise SystemExit(f"benchmark: check.py cannot work the first "
                         f"gradient out of the state of a {kind!r} solver")
    gradient = GRADIENT_FROM_SLOT0[kind]
    mults = {name: [b[2] for b in blobs] for name, blobs in specs}

    @jax.jit
    def f(slot0, w0):
        return [gradient(slot0[name][i], w0[name][i], solver,
                         *mults[name][i])
                for name, i in leaf_order(specs)]
    return f(slot0, w0)


def leaves(tree, specs, minus=None):
    """[blob] of `tree` (minus `minus`) in the order of `specs`."""
    @jax.jit
    def f(tree, minus):
        return [tree[n][i] - (0.0 if minus is None else minus[n][i])
                for n, i in leaf_order(specs)]
    return f(tree, minus)


@jax.jit
def _diff_norms(got, want):
    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return (jnp.stack([norm(g - w) for g, w in zip(got, want)]),
            jnp.stack([norm(w) for w in want]))


def leaf_shares(got, want):
    """Per blob: |got - want| / max(|want|, median blob's |want|)."""
    diffs, norms = jax.device_get(_diff_norms(
        [jnp.asarray(g) for g in got], list(want)))
    floor = statistics.median(float(n) for n in norms)
    return [float(d) / max(float(n), floor) for d, n in zip(diffs, norms)]


def worst_leaf_diff(got, want):
    """(share, index) of the worst blob."""
    shares = leaf_shares(got, want)
    worst = max(range(len(shares)), key=shares.__getitem__)
    return shares[worst], worst


def compare(got, want, limits, specs):
    """got/want: {"losses": [3], "grads": [blobs], "dparams": [blobs]}.
    -> rows of (name, value, limit, ok, note): every number compared
    stands beside its limit."""
    names = leaf_order(specs)
    rows = []
    for t, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        rows.append((f"loss_step{t + 1}_rel_gap", abs(a - b) / abs(b),
                     limits["loss_rel_gap"],
                     f"program {a:.6f} reference {b:.6f}"))
    for key in ("grads", "dparams"):
        share, idx = worst_leaf_diff(got[key], want[key])
        name = key[:-1] + "_worst_leaf_rel_diff"
        rows.append((name, share, limits[name],
                     f"worst blob {names[idx][0]}[{names[idx][1]}]"))
    # a NaN compares false with everything, so it fails its limit
    return [(n, v, lim, bool(v <= lim), note) for n, v, lim, note in rows]
