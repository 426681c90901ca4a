#!/usr/bin/env python3
"""The no-drop MoE's combine alone, at the three LM cells' shapes: what a
window's rows cost on their way back to their tokens as the scatter-add
that `ops/moe.py` had until PR 39 and as the gathers it has since, step by
step, and what the plan's `pos` costs (PERF.md section 7's table).

    chiprun --chips 1 -- python3 scripts/bench_moe_combine.py
    JAX_PLATFORMS=cpu python3 scripts/bench_moe_combine.py --toy   # rehearsal

Each row is the median of `--reps` calls of one jitted function after a
warm-up call, the host's clock round `block_until_ready`; a form that
starts from zeros pays its zero start. Indices come from a seeded routing
(top_k of uniform scores), which gives the cells' held shares (an eighth,
a quarter, a thirty-second). The table is printed and written as JSON
under chiprun_out/.
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

from sparknet_tpu.ops import moe, pallas_moe  # noqa: E402

# (tokens, top_k, held, experts, embed) with the layer's default tile
SHAPES = {"smallthinker": (32768, 6, 8, 64, 2560),
          "lfm2_moe": (24576, 4, 8, 32, 2048),
          "qwen3_next": (16384, 10, 16, 512, 2048)}
TOY = {"toy": (96, 4, 4, 8, 128)}
TILE = 128


def timed(fn, args, reps):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def routing(key, n, top_k, held, experts):
    _, idx = jax.lax.top_k(jax.random.uniform(key, (n, experts)), top_k)
    idx = idx.reshape(-1)
    return jnp.where(idx < held, idx, held).astype(jnp.int32)


def scatter_add(n):
    """What `_held_fwd` ended a window with until PR 39."""
    def run(tok, valid, rows):
        return jnp.zeros((n, rows.shape[1]), jnp.float32).at[tok].add(
            jnp.where(valid[:, None], rows, 0.0))
    return run


def bench_shape(name, shape, reps, seed, tile):
    n, top_k, held, experts, embed = shape
    window = moe.window_rows(n, top_k, held, experts, tile)
    segment = min(top_k, held)
    kr, kx, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    pair_expert = routing(kr, n, top_k, held, experts)
    rows = jax.random.normal(kx, (window, embed), jnp.float32)
    weight = jax.random.uniform(kw, (n * top_k,), jnp.float32)
    plan = jax.jit(moe.plan_windows, static_argnums=(1, 2))(
        pair_expert, held, window)
    pair, tok, valid, _, lo = moe._window(plan, 0, window, top_k)
    tm = jax.jit(moe._token_major, static_argnums=(4,))(
        plan, pair, valid, lo, top_k)
    out = {"tokens": n, "window": window, "embed": embed, "segment": segment,
           "held_rows": int(plan["bounds"][-1]),
           "windows": int(plan["windows"])}

    def row(label, fn, *args):
        out[label] = round(timed(fn, args, reps), 4)
        print(f"{name:13s} {label:34s} {out[label]:9.3f} ms", flush=True)

    # what every row includes: a call's dispatch and the wait for it
    row("a call that adds two numbers", lambda v: v[0, 0] + 1.0, rows)
    # (i) the scatter-add, as it was and with what it could be told
    row("scatter_add", scatter_add(n), tok, valid, rows)
    z = jnp.where(valid[:, None], rows[tm["row"]], 0.0)
    row("scatter_add token-sorted rows",
        lambda t, r: jnp.zeros((n, embed), jnp.float32).at[t].add(
            r, indices_are_sorted=True, mode="drop"), tm["tok"], z)
    # one row a token, pre-summed: sorted AND unique (their number is the
    # seed's, so the host counts it)
    firsts = np.asarray(tm["first"])[np.asarray(tm["count"]) > 0]
    utok = jnp.asarray(np.flatnonzero(np.asarray(tm["count"]) > 0), jnp.int32)
    summed = moe._shifted_adds(z, tm["tok"], segment)[jnp.asarray(firsts)]
    out["distinct_tokens"] = int(utok.shape[0])
    row("scatter_add pre-summed sorted unique",
        lambda t, r: jnp.zeros((n, embed), jnp.float32).at[t].add(
            r, indices_are_sorted=True, unique_indices=True), utok, summed)
    # (ii) the gathers: whole (a window's sum by token, without the zero
    # start and the add of `_held_fwd`'s loop, which the gather 2 rows
    # price), then step by step
    def whole(kernel):
        def run(plan, pair, valid, rows, weight):
            tm = moe._token_major(plan, pair, valid, 0, top_k)
            wt = weight[jnp.minimum(tm["pair"], n * top_k - 1)]
            return moe._combine(rows, wt, tm, segment, kernel)
        return run
    if jax.default_backend() == "tpu" or tile == 8:
        row("gather combine (whole, kernel)", whole(True),
            plan, pair, valid, rows, weight)
    row("gather combine (whole, XLA's adds)", whole(False),
        plan, pair, valid, rows, weight)
    row("  integers (_token_major)",
        lambda plan, pair, valid: moe._token_major(plan, pair, valid, 0,
                                                   top_k), plan, pair, valid)
    row("  gather 1 (window rows)", lambda rows, r: rows[r], rows, tm["row"])
    wt = weight[jnp.minimum(tm["pair"], n * top_k - 1)]
    raw = rows[tm["row"]]
    block = pallas_moe.segment_block(window, segment)
    if block and (jax.default_backend() == "tpu" or tile == 8):
        row("  segment add, the kernel",
            lambda z, wt, tok: pallas_moe.segment_add(
                z, wt, tok, n, segment, block), raw, wt, tm["tok"])
    row("  segment add, XLA straight",
        lambda z, tok: moe._shifted_adds(z, tok, segment), z, tm["tok"])
    first = jnp.where(tm["count"] > 0, tm["first"], window - 1)
    row("  gather 2 (token rows)", lambda s, first: s[first], z, first)
    row("  gather 2 + select + add onto y",
        lambda y, s, first, count: y + jnp.where(
            (count > 0)[:, None], s[first], 0.0),
        jnp.zeros((n, embed), jnp.float32), z, first, tm["count"])
    # d pair_weight: the scatter of `window` scalars, or a gather
    dwt = rows[:, 0]
    row("dpw scatter of window scalars",
        lambda pair, dwt: jnp.zeros((n * top_k,), jnp.float32).at[pair].add(
            dwt), pair, dwt)
    row("dpw gather by pos",
        lambda pos, dwt: jnp.where(
            pos < window, dwt[jnp.minimum(pos, window - 1)], 0.0),
        plan["pos"], dwt)
    # the plan with `pos`, and its sort and counts without it
    row("plan_windows (with pos)",
        lambda pe: moe.plan_windows(pe, held, window), pair_expert)

    def plan_before(pe):
        order = jnp.argsort(pe, stable=True).astype(jnp.int32)
        return order, jnp.sum(pe[None, :] < jnp.arange(held + 1)[:, None],
                              axis=1, dtype=jnp.int32)
    row("plan before PR 39 (sort, count)", plan_before, pair_expert)
    row("  argsort of the pairs alone",
        lambda pe: jnp.argsort(pe, stable=True), pair_expert)

    def pos_by_token(pe):
        # a running count over tokens and not pairs (a token's pairs in
        # slot order): top_k times fewer elements
        onto = pe.reshape(n, top_k)[None] == jnp.arange(held)[:, None, None]
        per = jnp.sum(onto, axis=2, dtype=jnp.int32)          # (held, n)
        before = jnp.cumsum(per, axis=1, dtype=jnp.int32) - per
        inside = jnp.cumsum(onto, axis=2, dtype=jnp.int32) - 1
        return jnp.sum(jnp.where(onto, before[:, :, None] + inside, 0),
                       axis=0).reshape(-1)
    row("  pos: running count over tokens", pos_by_token, pair_expert)
    return out


def bench_widths(reps, seed):
    """Whose is the 8.4 ms a call that the 2,560-wide scatter-add costs
    whatever its rows (PR 33)? Rows, width and tokens varied one at a
    time over the SmallThinker routing."""
    n, top_k, held, experts, _ = SHAPES["smallthinker"]
    out = {}
    for tokens, rows, embed in [(n, 30720, 2048), (n, 30720, 2560),
                                (n, 30720, 3072), (n, 7680, 2048),
                                (n, 7680, 2560), (n, 7680, 3072),
                                (n, 1024, 2560), (n // 2, 7680, 2560),
                                (n // 4, 7680, 2560), (n, 7680, 1024),
                                (n, 7680, 1536), (n, 7680, 1792),
                                (n, 7680, 2304), (n, 7680, 2816),
                                (n, 7680, 3584), (n, 7680, 4096)]:
        kr, kx = jax.random.split(jax.random.PRNGKey(seed + rows + embed))
        pair_expert = routing(kr, tokens, top_k, held, experts)
        plan = moe.plan_windows(pair_expert, held, rows)
        _, tok, valid, _, _ = moe._window(plan, 0, rows, top_k)
        vals = jax.random.normal(kx, (rows, embed), jnp.float32)
        label = f"scatter_add n={tokens} rows={rows} width={embed}"
        out[label] = round(timed(scatter_add(tokens), (tok, valid, vals),
                                 reps), 4)
        print(f"{'widths':13s} {label:44s} {out[label]:9.3f} ms", flush=True)
        zero = f"zeros alone n={tokens} width={embed}"
        if zero not in out:
            out[zero] = round(timed(
                lambda v: jnp.zeros((tokens, embed), jnp.float32)
                + v[0, 0], (vals,), reps), 4)
            print(f"{'widths':13s} {zero:44s} {out[zero]:9.3f} ms",
                  flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--toy", action="store_true",
                    help="one tiny shape, for a rehearsal off the chip")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--out", default="chiprun_out/bench_moe_combine.json")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    table = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    print(f"# device {dev.platform} {dev.device_kind}")
    shapes, tile = (TOY, 8) if args.toy else (SHAPES, TILE)
    for name, shape in shapes.items():
        table[name] = bench_shape(name, shape, args.reps, args.seed, tile)
    if not args.toy:
        table["widths"] = bench_widths(args.reps, args.seed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
