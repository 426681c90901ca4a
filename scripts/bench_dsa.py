#!/usr/bin/env python3
"""The four kernels of ops/pallas_dsa.py alone, one layer at the Keye cell's
shape: what the selection, the masked core, L_I's value and the backward
cost, and whether the set comes out alike in all of them (every query past
`topk` must count exactly `topk` keys in the core's own recomputed tiles,
ties aside).

    chiprun --chips 1 -- python3 scripts/bench_dsa.py
    JAX_PLATFORMS=cpu python3 scripts/bench_dsa.py --toy      # rehearsal

Each row is the median of `--reps` calls of one jitted function after a
warm-up call, the host's clock round `block_until_ready`. Printed and
written as JSON to `--out`, with the selection's two results and the
backward's six as sums and digests: a copy of this script in another
commit's tree, run with the same `--seed`, says whether that commit's
threshold, its index logsumexp and its backward give the same bits (the
first key block's dk and dv apart: a sum through HBM that loses a tile
loses it there first). `dsa_scores_write_ms` is the selection's kernel
stopped after the scores' write (no bit planes, no search): the rest of
`dsa_index_select_ms` is the search's; a tree whose kernel has no such
stop leaves the row out.
"""

import argparse
import hashlib
import inspect
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from sparknet_tpu.ops import pallas_dsa as pd  # noqa: E402

# (batch, heads, kv heads, sequence, head, index heads, index head, topk)
SHAPE = (1, 32, 4, 32768, 128, 16, 64, 2048)
TOY = (1, 4, 2, 256, 16, 4, 8, 32)


def timed(fn, args, reps):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), out


def digest(x):
    """A result's sum and absolute sum (float64, on the host, the finite
    entries: a threshold is -inf while a query takes every key) and its
    bytes' SHA-256: equal digests are equal bits."""
    x = np.asarray(x)
    wide = x.astype(np.float64)
    wide = wide[np.isfinite(wide)]
    return {"sum": float(wide.sum()), "abs_sum": float(np.abs(wide).sum()),
            "sha256": hashlib.sha256(x.tobytes()).hexdigest()[:16]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/bench_dsa.json")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--blocks", default=None,
                    help="block_q,block_k,select_q,select_k")
    args = ap.parse_args()
    b, h, hk, s, d, hi, di, topk = TOY if args.toy else SHAPE
    s = args.seq or s
    dt = jnp.float32 if args.toy else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 32), 8)
    q = jax.random.normal(ks[0], (b, h, s, d), dt)
    k = jax.random.normal(ks[1], (b, hk, s, d), dt)
    v = jax.random.normal(ks[2], (b, hk, s, d), dt)
    qi = jax.random.normal(ks[3], (b, hi, s, di), dt)
    ki = jax.random.normal(ks[4], (b, s, di), dt)
    w = jax.random.normal(ks[5], (b, hi, 1, s), jnp.float32) \
        / (hi * di) ** 0.5
    g = jax.random.normal(ks[6], (b, h, s, d), dt)
    given = [int(x) for x in args.blocks.split(",")] if args.blocks else []
    bq, bk, sq, sk = pd.blocks(s, *given)
    interp, scale = pd._should_interpret(), d ** -0.5
    rows = {"shape": [b, h, hk, s, d, hi, di, topk],
            "blocks": [bq, bk, sq, sk], "seed": args.seed,
            "device": jax.devices()[0].device_kind}

    ms, (thr, lse_i) = timed(
        lambda qi, ki, w: pd._select(qi, ki, w, topk, sq, sk, interp),
        (qi, ki, w), args.reps)
    rows["dsa_index_select_ms"] = ms
    rows["select"] = {"thr": digest(thr), "lse_i": digest(lse_i)}
    if "search" in inspect.signature(pd._select).parameters:
        rows["dsa_scores_write_ms"] = timed(
            lambda qi, ki, w: pd._select(qi, ki, w, topk, sq, sk, interp,
                                         search=False),
            (qi, ki, w), args.reps)[0]
    ms, (o, lse) = timed(
        lambda *a: pd._forward(*a, scale, bq, bk, interp),
        (q, k, v, qi, ki, w, thr), args.reps)
    rows["flash_sparse_fwd_ms"] = ms
    ms, kl = timed(
        lambda *a: pd._kl_rows(*a, scale, bq, bk, interp),
        (q, k, qi, ki, w, thr, lse, lse_i), args.reps)
    rows["dsa_kl_ms"] = ms
    rows["kl_mean"] = float(jnp.mean(kl))
    ms, grads = timed(
        lambda *a: pd._backward(*a, scale, bq, bk, interp),
        (q, k, v, qi, ki, w, thr, lse_i, o, lse, g), args.reps)
    rows["backward_ms"] = ms
    rows["backward"] = {name: digest(x) for name, x in zip(
        ("dq", "dk", "dv", "dqi", "dki", "dw"), grads)}
    rows["backward"]["dk_block0"] = digest(grads[1][:, :, :bk])
    rows["backward"]["dv_block0"] = digest(grads[2][:, :, :bk])
    # the set as the core's kernels see it: a forward whose main scores
    # are all 0 has exp(logsumexp) = the number of keys in a query's set
    _, lse0 = jax.jit(lambda *a: pd._forward(*a, scale, bq, bk, interp))(
        jnp.zeros_like(q), k, v, qi, ki, w, thr)
    count = jnp.exp(lse0[:, 0, 0, :])           # (B, S): keys in the set
    want = jnp.minimum(jnp.arange(s) + 1, topk)
    off = jnp.abs(jnp.round(count) - want)
    rows["queries_off_topk"] = int(jnp.sum(off > 0))
    rows["largest_miscount"] = float(jnp.max(off))
    print(json.dumps(rows, indent=1))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
