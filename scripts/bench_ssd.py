#!/usr/bin/env python3
"""The Mamba-2 scan alone, one layer at the Nemotron cell's shape: what the
kernel pair of ops/pallas_ssd.py costs beside XLA's chunked form of
ops/mamba2.py, and how far the two are apart.

    chiprun --chips 1 -- python3 scripts/bench_ssd.py
    JAX_PLATFORMS=cpu python3 scripts/bench_ssd.py --toy      # rehearsal

Each row is the median of `--reps` calls of one jitted function after a
warm-up call, the host's clock round `block_until_ready`: the forward
alone (as traced where nothing is differentiated), and the forward with
its backward (y and the last state against fixed cotangents; the
gradients of x, delta, A, B, C). The differences are the largest absolute
difference over the largest absolute value of XLA's result. Printed, and
written as JSON to `--out`.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from sparknet_tpu.ops import mamba2, pallas_ssd  # noqa: E402

# (batch, sequence, heads, head, groups, state)
SHAPE = (2, 8192, 64, 64, 8, 128)
TOY = (1, 256, 4, 64, 2, 128)


def timed(fn, args, reps):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), out


def apart(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--forms", default="kernel,chunked")
    ap.add_argument("--out", default="chiprun_out/bench_ssd.json")
    args = ap.parse_args()
    bsz, s, h, p, g, n = TOY if args.toy else SHAPE
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 7)
    dtype = jnp.bfloat16
    x = jax.random.normal(ks[0], (bsz, s, h, p)).astype(dtype)
    b = jax.random.normal(ks[1], (bsz, s, g, n)).astype(dtype)
    c = jax.random.normal(ks[2], (bsz, s, g, n)).astype(dtype)
    # delta log-uniform in [1e-3, 1e-1] and A in [1, 16): the layer's fill
    dt = jnp.exp(jax.random.uniform(ks[3], (bsz, s, h), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), maxval=np.log(16.0)))
    cot = jax.random.normal(ks[5], (bsz, s, h, p))
    cot_last = jax.random.normal(ks[6], (bsz, h, p, n))
    forms = {"kernel": pallas_ssd.chunk_scan, "chunked": mamba2.ssd_chunked}
    rows, outs = {"device": str(jax.devices()[0]),
                  "shape": [bsz, s, h, p, g, n]}, {}
    for name in args.forms.split(","):
        scan = forms[name]

        def both(cot, cot_last, *v, scan=scan):
            def loss(*v):
                y, last, _ = scan(*v)
                return jnp.sum(cot * y) + jnp.sum(cot_last * last)
            return jax.grad(loss, range(5))(*v)
        ms_f, fwd = timed(scan, (x, dt, a, b, c), args.reps)
        ms_b, grads = timed(both, (cot, cot_last, x, dt, a, b, c), args.reps)
        rows[name] = {"forward_ms": ms_f, "forward_backward_ms": ms_b}
        outs[name] = list(fwd) + list(grads)
        print(f"{name:8s} forward {ms_f:9.3f} ms   forward+backward "
              f"{ms_b:9.3f} ms", flush=True)
    if len(outs) == 2:
        names = ("y", "last", "survive", "dx", "ddelta", "dA", "dB", "dC")
        rows["apart"] = {k: apart(u, v) for k, u, v in zip(
            names, outs["kernel"], outs["chunked"])}
        print("apart", json.dumps(rows["apart"]))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
