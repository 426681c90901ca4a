#!/usr/bin/env python3
"""The held experts' grouped products alone, one MoE layer's window at each
LM cell's shape, by row tile: what `ops/moe.py:fit_tile`'s rule was settled
by (PR 47).

    chiprun --chips 1 --timeout 1800 -- python3 scripts/bench_gmm.py
    JAX_PLATFORMS=cpu python3 scripts/bench_gmm.py --toy      # rehearsal

One process, so the compilations share a cache. For every shape and every
tile of `--tiles`: the three kernels alone (`moe_gmm_fwd`: a window's
forward products, two of E -> F or one where an expert has two matrices,
and one of F -> E; `moe_gmm_bwd`: dh and dx, the same widths the other way
round; `moe_gmm_dw`: the weight gradients, `tgmm` onto a running float32
total), each the median of `--reps` calls of a jitted loop of `--inner`
calls, the host's clock round `block_until_ready`, in ms a call set; and
the whole of `held_experts` (dispatch, products, combine), forward and
forward with backward, where the products run as a step runs them.
`products_ms` is what one layer's kernels cost a step: 2 x fwd + bwd + dw
(the backward recomputes the window's forward; a block's replay under
remat leaves no third, its result is not used). `visits` counts the row
tiles `gmm` walks (a tile that two groups share once for each). The
routing is seeded: every token's
top_k of uniform scores plus a per-expert bias (`--skew` x top_k / experts
standard deviations: at 0.03 the held groups lie within a tenth or so of
the even share), so the groups are even with noise. `apart` is the largest
absolute difference from the first tile's result over its largest absolute
value (forward, and the worst of the gradients). Printed, and written as
JSON to `--out`. PERF.md section 6 (PR 47) says which of its two tables is
this file's as committed and which an earlier form's, whose `products_ms`
counted 3 x fwd.
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
from jax import lax  # noqa: E402

from sparknet_tpu.ops import moe as moe_ops  # noqa: E402

# cell -> (tokens, top_k, held, experts, embed, hidden as the kernels see
# it, activation, matrices an expert)
SHAPES = {
    "lfm2moe_ep4_s8192_b3": (24576, 4, 8, 32, 2048, 1792, "silu", 3),
    "smallthinker_ep8_s16384_b2": (32768, 6, 8, 64, 2560, 768, "relu", 3),
    "keye_ep8_s32768_b1": (32768, 8, 16, 128, 2048, 768, "silu", 3),
    "qwen3next_ep32_s8192_b2": (16384, 10, 16, 512, 2048, 512, "silu", 3),
    # 1,856 padded to 1,920 as the layer pads its cast copies
    "nemotron_tt_ep16_s8192_b2": (16384, 6, 8, 128, 2688, 1920, "relu2", 2),
    "glm47flash_ep8_s8192_b1": (8192, 4, 8, 64, 2048, 1536, "silu", 3),
    # no cell: Nemotron's net at batch 3, 1,152 rows an expert, between the
    # shapes the rule was settled at (512 misses VMEM at these widths and
    # reads FAILED here: `ops/moe.py:kernel_tile` holds such a layer at 256)
    "nemotron_batch_3": (24576, 6, 8, 128, 2688, 1920, "relu2", 2)}
TOY = {"toy_three": (96, 4, 4, 8, 128, 128, "silu", 3),
       "toy_two": (96, 2, 4, 8, 128, 128, "relu2", 2)}


def median_ms(fn, args, reps):
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


def save(rows, path):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as out:
        json.dump(rows, out, indent=1)


def routing(key, tokens, top_k, held, experts, skew):
    """(tokens x top_k,) local expert of every pair, `held` = not held
    here, and the pairs' weights."""
    ks, kb, kw = jax.random.split(key, 3)
    score = jax.random.uniform(ks, (tokens, experts)) \
        + skew * top_k / experts * jax.random.normal(kb, (experts,))
    _, idx = lax.top_k(score, top_k)
    local = idx.reshape(-1)
    weight = jax.random.uniform(kw, (tokens * top_k,), minval=0.1) / top_k
    return jnp.where(local < held, local, held).astype(jnp.int32), weight


def kernel_rows(sizes, tile, window, e, f, matrices, keys, inner, reps):
    """ms of a window's calls of each kernel alone, `inner` of them in one
    jitted loop (the sizes read the loop's index, so no call leaves it)."""
    from sparknet_tpu.ops import pallas_moe
    held = sizes.shape[0]
    bf = jnp.bfloat16
    xe = jax.random.normal(keys[0], (window, e)).astype(bf)
    xf = jax.random.normal(keys[1], (window, f)).astype(bf)
    w_fe = (0.02 * jax.random.normal(keys[2], (held, f, e))).astype(bf)
    w_ef = (0.02 * jax.random.normal(keys[3], (held, e, f))).astype(bf)

    def gmm_loop(lhs, rhs, transpose_rhs, name):
        @jax.jit
        def many(sizes, lhs, rhs):
            def body(i, acc):
                out = pallas_moe.grouped_dot(sizes + jnp.minimum(i, 0), tile,
                                             lhs, rhs, transpose_rhs, name)
                return acc + out[0, 0]
            return lax.fori_loop(0, inner, body, jnp.float32(0))
        return median_ms(many, (sizes, lhs, rhs), reps)[0] / inner

    def tgmm_loop(lhs, rhs):
        @jax.jit
        def many(sizes, lhs, rhs):
            def body(i, acc):
                out = pallas_moe.grouped_dot_t(sizes + jnp.minimum(i, 0),
                                               tile, lhs, rhs, "moe_gmm_dw")
                return acc + out[0, 0, 0]
            return lax.fori_loop(0, inner, body, jnp.float32(0))
        return median_ms(many, (sizes, lhs, rhs), reps)[0] / inner
    wide = matrices - 1             # products between E and F a direction
    return {
        "moe_gmm_fwd_ms": wide * gmm_loop(xe, w_fe, True, "moe_gmm_fwd")
        + gmm_loop(xf, w_ef, True, "moe_gmm_fwd"),
        "moe_gmm_bwd_ms": gmm_loop(xe, w_ef, False, "moe_gmm_bwd")
        + wide * gmm_loop(xf, w_fe, False, "moe_gmm_bwd"),
        "moe_gmm_dw_ms": wide * tgmm_loop(xf, xe) + tgmm_loop(xe, xf)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=8)
    ap.add_argument("--tiles", default=None,
                    help="row tiles, comma-separated (128,256,512; toy "
                    "8,16,32)")
    ap.add_argument("--shapes", default=None,
                    help="cells of SHAPES, comma-separated (all)")
    ap.add_argument("--skew", type=float, default=0.03)
    ap.add_argument("--out", default="chiprun_out/bench_gmm.json")
    args = ap.parse_args()
    shapes = TOY if args.toy else SHAPES
    names = args.shapes.split(",") if args.shapes else list(shapes)
    tiles = [int(t) for t in (args.tiles or (
        "8,16,32" if args.toy else "128,256,512")).split(",")]
    rows = {"device": str(jax.devices()[0]), "seed": args.seed,
            "skew": args.skew, "cells": {}}
    for name in names:
        tokens, top_k, held, experts, e, f, act, matrices = shapes[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 10)
        pair_expert, weight = routing(keys[0], tokens, top_k, held, experts,
                                      args.skew)
        bf = jnp.bfloat16
        x = jax.random.normal(keys[1], (tokens, e)).astype(bf)
        wg, wu = ((0.02 * jax.random.normal(k, (held, f, e))).astype(bf)
                  for k in keys[2:4])
        wd = (0.02 * jax.random.normal(keys[4], (held, e, f))).astype(bf)
        if matrices == 2:
            wg = None
        cot = jax.random.normal(keys[5], (tokens, e))
        cell = {"rows_an_expert": tokens * top_k / experts,
                "shape": list(shapes[name][:6]), "tiles": {}}
        rows["cells"][name] = cell
        first = None
        for tile in tiles:
            window = moe_ops.window_rows(tokens, top_k, held, experts, tile)
            plan = jax.jit(moe_ops.plan_windows, static_argnums=(1, 2))(
                pair_expert, held, window)
            sizes = moe_ops._window(plan, 0, window, top_k)[3]
            counts = np.asarray(plan["count"])
            bounds = np.asarray(plan["bounds"])
            visits = int(sum(
                -(-hi // tile) - lo // tile
                for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo))

            def fwd(x, pw, wg, wu, wd, tile=tile, window=window, plan=plan):
                # the kernels, which interpret themselves on a CPU
                return moe_ops.held_experts(x, pw, plan, wg, wu, wd, tile,
                                            top_k, window, True, act)

            def both(cot, *v, fwd=fwd):
                y, vjp = jax.vjp(fwd, *v)
                return (y, *vjp(cot))
            v = (x, weight, wg, wu, wd)
            row = {"window": window, "windows": int(plan["windows"]),
                   "visits": visits, "largest_group": int(counts.max()),
                   "smallest_group": int(counts.min())}
            try:
                row.update(kernel_rows(sizes, tile, window, e, f, matrices,
                                       keys[6:], args.inner, args.reps))
                row["products_ms"] = 2 * row["moe_gmm_fwd_ms"] \
                    + row["moe_gmm_bwd_ms"] + row["moe_gmm_dw_ms"]
                row["layer_forward_ms"], _ = median_ms(jax.jit(fwd), v,
                                                       args.reps)
                row["layer_forward_backward_ms"], outs = median_ms(
                    jax.jit(both), (cot, *v), args.reps)
            except Exception as err:    # a block that does not fit VMEM
                row["failed"] = str(err)[:400]
                cell["tiles"][str(tile)] = row
                print(f"{name:28s} tile {tile:4d} FAILED {row['failed']}",
                      flush=True)
                save(rows, args.out)
                continue
            outs = [o for o in outs if o is not None]
            if first is None:
                first = outs
            else:
                row["apart_forward"] = apart(outs[0], first[0])
                row["apart_gradients"] = max(
                    apart(a, b) for a, b in zip(outs[1:], first[1:]))
            cell["tiles"][str(tile)] = row
            print(f"{name:28s} r {cell['rows_an_expert']:6.0f} tile {tile:4d}"
                  f" window {window:6d} x{row['windows']} visits {visits:4d}"
                  f" | fwd {row['moe_gmm_fwd_ms']:7.3f} bwd "
                  f"{row['moe_gmm_bwd_ms']:7.3f} dw "
                  f"{row['moe_gmm_dw_ms']:7.3f}"
                  f" products {row['products_ms']:8.3f} | layer fwd "
                  f"{row['layer_forward_ms']:8.3f} fwd+bwd "
                  f"{row['layer_forward_backward_ms']:8.3f} ms"
                  + (f" | apart {row['apart_forward']:.1e} "
                     f"{row['apart_gradients']:.1e}"
                     if "apart_forward" in row else ""), flush=True)
            save(rows, args.out)


if __name__ == "__main__":
    main()
