"""Mixture-of-experts FFN: the top-1 Switch form with expert parallelism,
and the no-drop top-k form of today's language models.

Two forms, chosen by moe_param.gated_experts:

* False (the default): top-1 routing with a capacity cut, ReLU experts
  with biases, optional all_to_all expert parallelism: everything below
  the next rule. `top_k = 1` with a `capacity_factor` is this form.
* True: p = softmax(W_r x) over ALL num_experts in float32; the top_k
  largest, their weights divided by their sum (norm_topk_prob). Four
  fields, unset in the nets that came first, make the route another
  model's: `score_function` "sigmoid" scores each expert by
  sigmoid(W_r x) alone; `selection_bias` adds a blob b (num_experts,),
  zeros that no gradient trains (lr_mult and decay_mult 0), and the top_k
  are then the largest of p + b while their weights stay the UNBIASED p
  (the bias picks the experts and does not weigh them); `topk_eps` is
  added to the chosen weights' sum before it divides them;
  `routed_scaling_factor` multiplies them. The update that balances the
  load through b belongs to a solver and is not here.
  routed = sum over the chosen experts THAT THIS LAYER HOLDS of
  p_e W_down,e (act(W_gate,e x) * W_up,e x), no bias anywhere, act SiLU
  or, with moe_param.expert_activation "relu", ReLU (the forward, its
  recomputation and the backward alike: the derivative is a mask). With
  a SECOND BOTTOM the router reads that one and the experts the first
  (a block that routes from its pre-attention norm while its experts
  read the post-attention one): p = softmax(W_r bottom[1]), and the
  router's gradient goes to the second bottom alone. The layer
  holds `experts_held` experts starting at the router's output
  `first_expert` (0 held = all of them: the whole layer); the router keeps
  num_experts outputs either way, and what the absent experts would add is
  left out — one chip's share of an expert-parallel group, run without
  its exchange. NO TOKEN IS DROPPED, whatever the imbalance: the
  token-expert pairs are sorted by expert (one stable sort), so the held
  experts' pairs come first and each expert's are one contiguous group,
  unpadded; they run a WINDOW of rows at a time (`window_rows`, static:
  the pairs an even routing sends here and a quarter more, in whole
  tiles of `tile_rows`, at most `WINDOW_TILES` tiles; a layer that names no
  `tile_rows` takes `fit_tile`'s, from the rows an even routing sends one
  held expert: 512 from 3,072 rows, 256 from 512, else 128, as the cells'
  steps were timed; never one so small that the quarter more misses one
  window, and on the kernels' path none whose blocks miss VMEM at the
  layer's widths: `kernel_tile`), as many windows
  as the routing needs: a loop of dynamic length (in the backward pass
  the first window, which an even routing fills and every step runs, on
  its own, and the loop over the windows after it, skew's spill), so
  memory is bound by
  one window's buffers and work by the routing, neither by the worst
  case of all tokens x top_k rows. A window is one gather of its rows of
  x, a grouped product over its ragged groups (gate, up, SiLU x up, down;
  backward the same recomputed, then dh, dx and the three weight
  gradients as per-group lhs^T rhs accumulated in float32) and the
  COMBINE, which brings the window's rows back to their tokens and
  gathers (PR 39; it was one scatter-add of the window's float32 rows,
  which on a v5e at a width of 2,560 costs by the size of the token array
  it adds onto, not by its rows: 17 ms a call against 5 at 2,048, PERF.md
  section 7): the window's pairs sorted by pair index, which is token by
  token (`_token_major`: one sort of `window` keys that
  carries each row's place; the plan's `pos`, the inverse of `order` on
  the held pairs, says which pairs the window holds, so how many rows
  each token has in it and where the first lies), gather 1 of the
  window's rows into that order, a SEGMENT ADD (a token's top_k experts
  differ, so at most J = min(top_k, held) of its rows lie in a window,
  side by side: J - 1 shifted dense adds in float32, ascending by pair
  index, leave each token's sum on its first row), gather 2 of each
  token's first row, added onto the result (zeros before the first
  window). In the BACKWARD pass the first window's results are the start
  (PR 48): dx is its combine's gather, with nothing zeroed before it and
  nothing added onto it, and the weight gradients' float32 totals are its
  own, written once; a later window's are added onto them. Every
  pair is added once, in float32, in an order fixed
  by the pair index: two runs agree to the bit. The only scatter left is
  d pair_weight's, `window` scalars. The product has two implementations
  behind `_grouped`, chosen at trace time from what the layer can see
  (`_why_xla`): on a TPU backend, with embed and hidden widths multiples
  of 128 and tile_rows of 8, the megablox kernels through ops/pallas_moe.py
  (`moe_gmm_fwd`, `moe_gmm_bwd`, `moe_gmm_dw` in a device trace, the
  row tile `tile_rows`, the number of row tiles a traced value);
  elsewhere XLA's `lax.ragged_dot_general` over the same window; the
  segment add goes with it (`moe_segment_add`, one read and one write of
  the window, beside XLA's shifted adds, which copy the window once a
  shift). Which one is in the ring of obs/trace.py, one `moe.path` record
  a trace of the layer (`path` = `kernel` or `xla`, with the `reason`,
  the experts' `activation`, the route's `score`, whether it has a
  `selection_bias`, the combine's form: `combine` = `gather`,
  `segment` = J, the row `tile` with the `rows_an_expert` it was fitted to
  and the `window`'s rows, `first_window` = `backward`: the pass in which
  the first window stands outside the loop over windows, the expert's
  `matrices`, 3 or 2, and the shared expert's gate, `shared_gate`). With
  shared_hidden_dim > 0 a shared expert sees every token:
  shared = sigmoid(w_s . x) W_down (SiLU(W_gate x) * W_up x), and
  y = routed + shared. Tops: [output] or [output, stats]; stats (weight
  0, kept as layer state so that the solver can read it where it already
  waits for a loss, as `moe.load`) = [share of the token-expert pairs
  that land on held experts, largest over mean load of the held experts,
  windows run: more than 1 is skew spilling past a window, and the
  backward's spill loop ran that many less 1].
  Blobs:
    router (num_experts, E) | w_gate (held, F, E) | w_up (held, F, E)
    | w_down (held, E, F) | then with a shared expert: ws_gate (Fs, E)
    | ws_up (Fs, E) | ws_down (E, Fs) | shared gate (1, E)
    | then with selection_bias: bias (num_experts,), always the last
  AN EXPERT OF TWO MATRICES (`expert_gate_matrix` false): W_down,e
  act(W_up,e x), act SiLU, ReLU or, with `expert_activation` "relu2",
  relu(u)^2 with derivative 2 relu(u), in the forward, its recomputation
  and the backward alike; a window is two grouped products (backward: the
  same recomputed, dh, dx and TWO weight gradients). The blob list has no
  w_gate, and a shared expert is the same form with no ws_gate: W_sd
  act(W_su x). `shared_gate` false adds the shared expert as it is, with
  no sigmoid(w_s . x) before it and no blob for it (either form of the
  expert). A hidden width off the lane width (1,856 = 14.5 x 128) still
  takes the kernels: the held weights' copies in the compute type are
  padded with zero columns to the next multiple of 128 where they are cast
  (a zero column's activation is 0 in every form, its gradient is cut off
  again); the blobs, their gradients and the optimizer's state keep the
  published width.
    router | w_up (held, F, E) | w_down (held, E, F) | then with a shared
    expert: ws_up (Fs, E) | ws_down (E, Fs) | with shared_gate: (1, E)
    | with selection_bias: bias (num_experts,)
  Scopes inside the layer's own: moe_route (softmax, top-k, sort, the
  plan with its `pos`), moe_dispatch (gathering a window's rows),
  moe_experts (the grouped products), moe_combine (the token-major sort,
  both gathers, the weights and the segment add; d pair_weight's scalar
  scatter), moe_shared, and moe_glue for what is left, so that the
  layer's device time adds up by them: the held weights cast to the
  compute type (and their gradients cast back), the window loop itself
  with its zero start (backward: the spill loop and its adds onto the
  first window's results), the result's cast. The
  top-1 form below opens no scope.

The top-1 form:

sparknet_tpu extension (no reference twin — SURVEY.md section 2c lists
EP/MoE as absent from the CNN-era reference); the expert-parallel half of
the framework's distributed story, alongside dp (pmean), tp (gspmd) and
sp (ring/Ulysses).

Routing is top-1 (Switch Transformer) with a capacity limit: each token
goes to its argmax expert; an expert accepts at most
C = ceil(tokens/num_experts * capacity_factor) tokens and overflow tokens
pass through as zeros (the surrounding residual connection carries them).
Tops: [output] or [output, aux] where aux is the Switch load-balancing
loss (num_experts * sum_e fraction_e * mean_gate_e) — give the second top
a loss_weight to train against expert collapse.

Expert parallelism: under a mesh axis named "expert" (published via
parallel.context, like "seq" for ring attention) and
moe_param.expert_parallel, the (num_experts, capacity, embed) dispatch
buffer is exchanged with ONE tiled all_to_all so each device runs only
its own num_experts/ep_size experts, then a second all_to_all returns
expert outputs to their source tokens. Dispatch/combine are sort-based
scatter/gather (O(n log n + n*C), not an O(n^2) one-hot mask) and run
identically on 1 device and on an N-way expert mesh, so the two paths
agree exactly (tested).

EP shards compute, not just weights, when tokens arrive SHARDED along
the expert axis (batch or sequence dim split over the same mesh axis,
the usual dp-x-ep composition): routing/capacity math runs on the LOCAL
token count, so each device builds an (X, C/ep, E) dispatch buffer and
after the all_to_all runs its X/ep experts over ep*(C/ep) = C capacity
slots — per-device expert FLOPs drop ep-fold with the axis
(tested: test_moe.py asserts the traced buffer shape shrinks ep-fold on
an 8-way mesh, and that the token-sharded forward equals the
single-device forward). Capacity is enforced per SOURCE device (each
peer may send at most C_local = ceil(n_local/X * capacity_factor)
tokens to any one expert), which equals the global rule whenever
routing doesn't overflow; under overflow the drop priority is
per-device arrival order rather than global order. Tokens may also be
passed REPLICATED across the axis — then the layer still shards expert
weight memory (each device runs X/ep experts over every peer's
identical slots) but per-device FLOPs don't shrink; that mode is only
for weight-memory relief.

Weight blobs (expert-major so a GSPMD param_rule or shard_map in_spec can
shard dim 0 across the expert axis):
  router (num_experts, E) | w1 (num_experts, F, E) | b1 (num_experts, F)
  | w2 (num_experts, E, F) | b2 (num_experts, E)
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..proto import Message
from ..graph.registry import Layer, register
from ..obs.trace import default_tracer, kernel_import
from ..parallel import context
from .convolution import _param_mults


# -- the no-drop form: held experts over ragged groups, a window at a time ---

# the most rows of a window, in tiles (32,768 rows at tile_rows 128: a share
# whose even routing fits one window should get it in one, PR 33: a second
# window pays the sort, both gathers and the kernels' fixed parts for a few
# rows; the scatter-add that cost 8 ms a window besides its rows left at
# PR 39)
WINDOW_TILES = 256

_NT = (((1,), (2,)), ((), ()))      # lhs (m, k) . rhs (g, n, k)
_NN = (((1,), (1,)), ((), ()))      # lhs (m, k) . rhs (g, k, n)
_TN = (((0,), (0,)), ((), ()))      # lhs (m, k), rhs (m, n) -> (g, k, n)


def window_rows(n_tokens, top_k, held, num_experts, tile):
    """Static rows of a window, in whole tiles: the pairs that an even
    routing sends here (n_tokens x top_k x held / num_experts) and a
    quarter more, so that one window takes them and skew spills into a
    second; never more than WINDOW_TILES tiles, nor than every pair that
    can land here (a token's top_k experts differ: at most n_tokens x
    min(top_k, held)). Gathers, the segment add and buffers are paid for
    every row of a window, filled or not."""
    even = n_tokens * top_k * held / num_experts
    most = n_tokens * min(top_k, held)
    return min(WINDOW_TILES, math.ceil(min(1.25 * even, most) / tile)) * tile


# the row tiles a layer that names none may get, and the fewest rows an
# expert from which it takes each: where the tile read faster than the one
# below it INSIDE a cell's step, settled by timing the products alone at
# the six LM cells' shapes and then the cells (`scripts/bench_gmm.py`;
# PERF.md section 6, PR 47). By the products 256 reads a fifth faster than
# 128 at 512 and 768 rows an expert and no faster at 320; 512 reads 14% and
# 7% faster than 256 at 3,072 and 5% slower at 512. At 2,048 the products
# alone read 6% faster at 512, the layer whole and the cell's step tie
# (2,041.7 -> 2,040.9 ms, the traced scope 55.3 -> 54.9): a tie keeps the
# smaller tile, and nothing was timed between 2,048 and 3,072
ROW_TILES = (128, 256, 512)
LEAST_ROWS = (0, 512, 3072)


def fit_tile(n_tokens, top_k, held, num_experts):
    """The row tile of a layer that names none, from the rows an even
    routing sends ONE held expert, n_tokens x top_k / num_experts: the
    largest of ROW_TILES whose LEAST_ROWS such a group reaches. The grouped
    product streams a group's whole weight matrices through VMEM once for
    every row tile it visits, so a tile of t rows does t operations for
    every byte of bfloat16 weights, and a v5e wants 240: at 128 the
    products wait for the weights, from 256 on the MXU sets the pace and a
    larger tile saves a visit's fixed part. But a group's last tile is
    shared with the next group and visited once for each, every visit a
    whole tile's products, so a tile that is a large part of a group wastes
    what it saves: 256 pays from two whole tiles a group, 512 was seen to
    pay at six and not at four. Never a tile so small that an even
    routing's pairs and a quarter more miss the WINDOW_TILES tiles of one
    window: at 128 rows a share of 32,768 even pairs is the cap itself,
    and every other step spills a few rows into a second window (18 ms, two
    step times in one run, PR 40)."""
    rows, least = n_tokens * top_k / num_experts, ROW_TILES[0]
    by_group = max(t for t, r in zip(ROW_TILES, LEAST_ROWS) if rows >= r)
    by_window = least * math.ceil(
        1.25 * rows * held / (WINDOW_TILES * least))
    # the next of ROW_TILES, so that a tile nobody timed or compiled (384)
    # comes out only where even the largest is too small for one window
    by_window = min((t for t in ROW_TILES if t >= by_window),
                    default=by_window)
    return max(by_group, by_window)


def kernel_tile(tile, embed, hidden, itemsize):
    """`tile` where the kernels' blocks at these widths and this compute
    type fit VMEM at that row tile (`pallas_moe.fits`), else the largest of
    ROW_TILES below it at which they do: `fit_tile` reads the routing
    alone, and the float32 output block of the wider product grows with the
    tile (2,688 x 1,920 takes 256 and not 512 in bfloat16 and 128 in
    float32, 2,048 x 1,792 in float32 256). Where none fits the tile
    stays, and the compiler says so as it did before any tile was fitted."""
    with kernel_import("sparknet_tpu.ops.pallas_moe"):
        from .pallas_moe import fits
    return next((t for t in (tile, *reversed(ROW_TILES))
                 if t <= tile and fits(t, embed, hidden, itemsize)), tile)


def plan_windows(pair_expert, held, window):
    """From each pair's local expert (`held` = not held here), the plan
    the window loops follow: `order` (pairs sorted by expert, the held
    ones first, so each held expert's pairs are one contiguous group;
    padded so that every window is a slice of it), `bounds` (group e is
    order[bounds[e]:bounds[e + 1]]; bounds[held] pairs land here), per held
    expert its `count`, the number of `windows` of `window` rows of
    `order` that hold them all, and `pos`, each held pair's place in
    `order` (order[pos[p]] == p: its group's start and the earlier pairs of
    its expert, a running count; the pairs not held read the number of
    pairs, past every window). One sort and one running count: no gather,
    no scatter."""
    pairs = pair_expert.shape[0]
    order = jnp.argsort(pair_expert, stable=True).astype(jnp.int32)
    onto = pair_expert[None, :] == jnp.arange(held)[:, None]   # (held, pairs)
    upto = jnp.cumsum(onto, axis=1, dtype=jnp.int32)
    count = upto[:, -1]
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(count, dtype=jnp.int32)])
    pos = jnp.sum(jnp.where(onto, bounds[:-1, None] + upto - 1, 0), axis=0,
                  dtype=jnp.int32)
    return {"order": jnp.pad(order, (0, -pairs % window)),
            "bounds": bounds, "count": count,
            "windows": -(-bounds[-1] // window),
            "pos": jnp.where(pair_expert < held, pos, pairs)}


def _window(plan, w, window, top_k):
    """Window w of the sorted pairs: (pair index, token index, valid, each
    group's rows inside the window, the first row `lo`). Rows past the last
    held pair are not valid and belong to no group; the valid rows come
    first."""
    lo = w * window
    pair = lax.dynamic_slice(plan["order"], (lo,), (window,))
    edges = jnp.clip(plan["bounds"], lo, lo + window)
    return (pair, pair // top_k, jnp.arange(window) < edges[-1] - lo,
            edges[1:] - edges[:-1], lo)


def _token_major(plan, pair, valid, lo, top_k):
    """The same window with each token's rows side by side, for the
    combine: `pair` (the window's pair indices ascending, which is token by
    token; the rows of no pair last, reading the number of pairs), `row`
    (where each lies in the expert-sorted window), `tok` (its token; the
    number of tokens on the rows of no pair), and per token its `count` of
    rows in this window and the `first` of them. One sort of `window` keys
    and one running count over the tokens."""
    pos = plan["pos"]
    pairs, window = pos.shape[0], pair.shape[0]
    tm, row = lax.sort((jnp.where(valid, pair, pairs),
                        jnp.arange(window, dtype=jnp.int32)), num_keys=1)
    here = (pos >= lo) & (pos < lo + jnp.sum(valid, dtype=jnp.int32))
    count = jnp.sum(here.reshape(-1, top_k), axis=1, dtype=jnp.int32)
    return {"pair": tm, "row": row, "tok": tm // top_k, "count": count,
            "first": jnp.cumsum(count, dtype=jnp.int32) - count}


def _shifted_adds(z, tok, segment):
    """XLA's form of the segment add: z (rows, E) float32 sorted by token
    `tok` -> row i plus the rows i + 1 .. i + segment - 1 of i's token,
    added in ascending order."""
    total = z
    for d in range(1, min(segment, z.shape[0])):
        same = jnp.pad(tok[d:] == tok[:-d], (0, d))
        total = total + jnp.where(
            same[:, None], jnp.pad(z[d:], ((0, d), (0, 0))), 0.0)
    return total


def _combine(rows, weight, tm, segment, kernel):
    """Each token's sum of its rows of this window -> (n, E) float32, zeros
    for a token with none: `rows` (window, E) float32 in the expert-sorted
    order, times `weight` per token-major row where given. Without a
    scatter: gather the rows token by token, add each token's adjacent
    rows onto its first (a token has at most `segment` rows here; shifted
    dense adds in float32, ascending by pair index), gather each token's
    first row. The rows of no pair add nothing, whatever they hold. The
    shifted adds are one kernel where `kernel` says so and a block fits
    (ops/pallas_moe.py:segment_add), else XLA's."""
    window, tokens = rows.shape[0], tm["count"].shape[0]
    z, tok, block = rows[tm["row"]], tm["tok"], 0
    if kernel:
        with kernel_import("sparknet_tpu.ops.pallas_moe"):
            from . import pallas_moe
        block = pallas_moe.segment_block(window, segment)
    if block:
        total = pallas_moe.segment_add(z, weight, tok, tokens, segment,
                                       block)
    else:
        if weight is not None:
            z = z * weight[:, None]
        z = jnp.where((tok < tokens)[:, None], z, 0.0)
        total = jnp.pad(_shifted_adds(z, tok, segment), ((0, 1), (0, 0)))
    # the rows past the window are zeros: a token with no row here gathers
    # the first of them
    return total[jnp.where(tm["count"] > 0, tm["first"], window)]


def _grouped(kernel, tile, sizes):
    """The grouped product over a window's ragged groups, in one of two
    implementations: `dot(lhs, rhs, transpose_rhs, name)` -> (m, n) float32
    and `dot_t(lhs, rhs, name)` -> per group lhs^T rhs, (groups, k, n)
    float32, zeros for a group of no row. The kernels leave the rows of no
    group unwritten, the XLA form zeroes them: the caller masks."""
    if kernel:
        # here and not at the top: a process without such a layer never
        # imports pallas (1.4 s of every cell's set-up, PR 29)
        with kernel_import("sparknet_tpu.ops.pallas_moe"):
            from . import pallas_moe
        return (functools.partial(pallas_moe.grouped_dot, sizes, tile),
                functools.partial(pallas_moe.grouped_dot_t, sizes, tile))

    def ragged(lhs, rhs, dims, group_dims, name):
        with jax.named_scope(name):
            return lax.ragged_dot_general(
                lhs, rhs, sizes, lax.RaggedDotDimensionNumbers(
                    dot_dimension_numbers=dims, lhs_ragged_dimensions=[0],
                    rhs_group_dimensions=group_dims),
                preferred_element_type=jnp.float32)

    def dot(lhs, rhs, transpose_rhs, name):
        return ragged(lhs, rhs, _NT if transpose_rhs else _NN, [0], name)

    def dot_t(lhs, rhs, name):
        return ragged(lhs, rhs, _TN, [], name)
    return dot, dot_t


def _gate(a, act):
    """act(a) of the gate's float32 product (of the up product, where an
    expert has two matrices)."""
    if act == "relu2":
        return jnp.square(jnp.maximum(a, 0.0))
    return jnp.maximum(a, 0.0) if act == "relu" else jax.nn.silu(a)


def _gate_and_slope(a, act):
    """(act(a), act'(a)): ReLU's derivative is a mask, 0 at 0, and
    relu(a)^2's is 2 relu(a)."""
    if act == "relu2":
        on = jnp.maximum(a, 0.0)
        return jnp.square(on), 2.0 * on
    if act == "relu":
        on = a > 0
        return jnp.where(on, a, 0.0), on.astype(a.dtype)
    sa = jax.nn.sigmoid(a)
    silu = a * sa
    return silu, sa + silu * (1.0 - sa)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def held_experts(x, pair_weight, plan, wg, wu, wd, tile, top_k, window,
                 kernel, act="silu"):
    """sum over each token's held experts e of pair_weight x W_down,e
    (act(W_gate,e x) * W_up,e x), `act` "silu" or "relu"; with `wg` None
    an expert is two matrices, W_down,e act(W_up,e x), and `act` may be
    "relu2". x (n, E) and the
    weights in the
    compute type, pair_weight (n x top_k,) float32 (token-major), `plan`
    from `plan_windows(.., window)`, `window` a multiple of `tile`, the row
    tile of the grouped product; `kernel`: the pallas product, else XLA's.
    -> (n, E) float32. A `fori_loop` over the windows in use: one gather,
    the grouped products and the gathering combine (`_token_major`,
    `_combine`) a window, added onto the result. The backward pass
    recomputes each window, so nothing is stored per window, and its
    FIRST window, which an even routing fills and every step runs, stands
    outside any loop: its dx and weight gradients are the start, nothing
    is zeroed before them and nothing added onto them, and a `fori_loop`
    over the windows 1 .. plan["windows"] - 1, skew's spill, adds each
    later window's onto them; one jitted function (`_window_bwd`) is the
    window at both places. (The forward's first window stays in its
    loop: outside it bought half the backward's gain in the LFM2 cell's
    step for twice its cost in the cell's set-up, a second place that
    holds the window's kernels: PERF.md section 6, PR 48.) A
    token's pairs lie on different experts (as `top_k` gives them), or at
    least no more than min(top_k, held) of them are held: the segment add
    reaches no further."""
    return _held_fwd(x, pair_weight, plan, wg, wu, wd, tile, top_k, window,
                     kernel, act)[0]


# jitted, so that the layers of one shape (and a layer's recomputation)
# trace and lower the window's body once between them: the kernels'
# tracing is seconds of a step's first call
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _held_fwd(x, pair_weight, plan, wg, wu, wd, tile, top_k, window, kernel,
              act="silu"):
    segment = min(top_k, wd.shape[0])

    def body(w, y):
        with jax.named_scope("moe_dispatch"):
            pair, tok, valid, sizes, lo = _window(plan, w, window, top_k)
            xw = x[tok]
        with jax.named_scope("moe_experts"):
            dot, _ = _grouped(kernel, tile, sizes)
            if wg is None:
                h = _gate(dot(xw, wu, True, "moe_gmm_fwd"),
                          act).astype(x.dtype)
            else:
                a = dot(xw, wg, True, "moe_gmm_fwd")
                b = dot(xw, wu, True, "moe_gmm_fwd")
                h = (_gate(a, act) * b).astype(x.dtype)
            out = dot(h, wd, True, "moe_gmm_fwd")
        with jax.named_scope("moe_combine"):
            tm = _token_major(plan, pair, valid, lo, top_k)
            wt = pair_weight[jnp.minimum(tm["pair"], pair_weight.shape[0] - 1)]
            return y + _combine(out, wt, tm, segment, kernel)

    y = lax.fori_loop(0, plan["windows"], body,
                      jnp.zeros(x.shape, jnp.float32))
    return y, (x, pair_weight, plan, wg, wu, wd)


# The backward pass's window: jitted under a name of its own, so that the
# first window and the spill loop's body (and every trace of `_held_bwd` a
# step makes) trace it ONCE: both places bind one jaxpr. As a plain inner
# function it is the same executable and 1.1 s more of a warm first step
# on the chip's host (jax's own log, LFM2 cell: the step's trace 6.25-6.29
# s so, 5.09-5.19 s jitted, 5.01-5.36 s at the parent: PERF.md section 6,
# PR 48). Both places hand it `w` as a plain int32: a Python 0 and a
# loop's index from a Python 1 are weakly typed, another type to jit, and
# the window would trace twice.


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _window_bwd(x, pair_weight, plan, wg, wu, wd, dy, w, tile, top_k, window,
                kernel, act):
    """Window `w` of the backward pass, recomputed: (its contribution to
    dx (n, E) float32, the window's `pair` indices and d pair_weight on
    each of its rows, 0 on the rows of no pair, for the caller's scalar
    scatter, then d w_gate (None for an expert of two matrices), d w_up,
    d w_down: per group lhs^T rhs in float32, written once)."""
    with jax.named_scope("moe_dispatch"):
        pair, tok, valid, sizes, lo = _window(plan, w, window, top_k)
        xw, dyr = x[tok], dy[tok]
    with jax.named_scope("moe_experts"):
        dot, dot_t = _grouped(kernel, tile, sizes)
        if wg is None:
            gate, dgate = _gate_and_slope(
                dot(xw, wu, True, "moe_gmm_fwd"), act)
            h = gate.astype(x.dtype)
        else:
            a = dot(xw, wg, True, "moe_gmm_fwd")
            b = dot(xw, wu, True, "moe_gmm_fwd")
            gate, dgate = _gate_and_slope(a, act)
            h = (gate * b).astype(x.dtype)
        wt = jnp.where(valid, pair_weight[pair], 0.0)
        # d pair_weight = dy . (h W_down^T), row by row
        out = dot(h, wd, True, "moe_gmm_fwd")
        dwt = jnp.where(valid, jnp.sum(dyr * out, -1), 0.0)
        dyw = (dyr * wt[:, None]).astype(x.dtype)
        dh = dot(dyw, wd, False, "moe_gmm_bwd")
        dwg = None
        if wg is None:
            db = (dh * dgate).astype(x.dtype)
            dxw = dot(db, wu, False, "moe_gmm_bwd")
        else:
            da = (dh * b * dgate).astype(x.dtype)
            db = (dh * gate).astype(x.dtype)
            dxw = dot(da, wg, False, "moe_gmm_bwd") \
                + dot(db, wu, False, "moe_gmm_bwd")
            dwg = dot_t(da, xw, "moe_gmm_dw")
        dwu = dot_t(db, xw, "moe_gmm_dw")
        dwd = dot_t(dyw, h, "moe_gmm_dw")
    with jax.named_scope("moe_combine"):
        tm = _token_major(plan, pair, valid, lo, top_k)
        dx = _combine(dxw, None, tm, min(top_k, wd.shape[0]), kernel)
    return dx, pair, dwt, dwg, dwu, dwd


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _held_bwd(tile, top_k, window, kernel, act, res, dy):
    x, pair_weight, plan, wg, wu, wd = res
    dy = dy.astype(jnp.float32)

    def one(w, dpw):
        dx, pair, dwt, *dws = _window_bwd(
            x, pair_weight, plan, wg, wu, wd, dy,
            lax.convert_element_type(w, jnp.int32), tile, top_k, window,
            kernel, act)
        with jax.named_scope("moe_combine"):
            # a row of no group may repeat a real pair's index: add 0 there
            return dx, dpw.at[pair].add(dwt), dws

    def spill(w, totals):
        dx, dpw, dws = totals
        more, dpw, more_dws = one(w, dpw)
        # a later window's weight gradients onto the totals, one more pass
        # over them in a window that skew alone brings
        return dx + more, dpw, jax.tree.map(jnp.add, dws, more_dws)

    # the first window's results are the start: nothing zeroed but d
    # pair_weight's scalars, nothing added
    dx, dpw, (dwg, dwu, dwd) = lax.fori_loop(
        1, plan["windows"], spill,
        one(0, jnp.zeros(pair_weight.shape, jnp.float32)))
    return (dx.astype(x.dtype), dpw, None,
            None if wg is None else dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype))


held_experts.defvjp(_held_fwd, _held_bwd)


@register
class MoE(Layer):
    type_name = "MoE"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.moe_param
        self.p = p
        b, s, e = bottom_shapes[0]
        self.embed = int(e)
        self.num_experts = int(p.num_experts)
        if self.num_experts < 2:
            raise ValueError(f"{lp.name}: moe_param.num_experts must be >= 2")
        self.hidden = int(p.hidden_dim) or 4 * self.embed
        self.capacity_factor = float(p.capacity_factor)
        self.expert_parallel = bool(int(p.expert_parallel))
        self.gated = bool(int(p.gated_experts))
        self.top_k = int(p.top_k)
        self.norm_topk = bool(int(p.norm_topk_prob))
        self.held = int(p.experts_held) or self.num_experts
        self.first = int(p.first_expert)
        self.shared_hidden = int(p.shared_hidden_dim)
        self.tile = int(p.tile_rows) if p.has("tile_rows") else fit_tile(
            b * s, self.top_k, self.held, self.num_experts)
        self.act = str(p.expert_activation)
        self.gate_matrix = bool(int(p.expert_gate_matrix))
        self.shared_gate = bool(int(p.shared_gate))
        self.router_bottom = len(bottom_shapes) > 1
        self.score = str(p.score_function)
        self.selection_bias = bool(int(p.selection_bias))
        self.topk_eps = float(p.topk_eps)
        self.scaling = float(p.routed_scaling_factor)
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"{lp.name}: score_function {self.score!r}: "
                             "want softmax or sigmoid")
        if self.act not in ("silu", "relu", "relu2"):
            raise ValueError(f"{lp.name}: expert_activation {self.act!r}: "
                             "want silu or relu (or relu2, an expert "
                             "without a gate matrix)")
        if self.act == "relu2" and self.gate_matrix:
            raise ValueError(
                f"{lp.name}: expert_activation relu2 is the two-matrix "
                "expert's, W_down relu(W_up x)^2 (expert_gate_matrix "
                "false); with a gate matrix it has no meaning here")
        if not self.gated and (self.act != "silu" or self.router_bottom
                               or not self.gate_matrix
                               or not self.shared_gate
                               or p.has("down_filler")):
            raise ValueError(
                f"{lp.name}: expert_activation, expert_gate_matrix, "
                "shared_gate, down_filler and a second bottom for the "
                "router belong to the no-drop form (moe_param.gated_experts); "
                "the Switch form's expert is W_2 relu(W_1 x + b_1) + b_2")
        if not self.shared_gate and not self.shared_hidden:
            raise ValueError(
                f"{lp.name}: shared_gate is the shared expert's and "
                "shared_hidden_dim names none")
        if not self.gated and (self.score != "softmax" or self.selection_bias
                               or self.topk_eps or self.scaling != 1.0):
            raise ValueError(
                f"{lp.name}: score_function, selection_bias, topk_eps and "
                "routed_scaling_factor belong to the no-drop form "
                "(moe_param.gated_experts)")
        if self.router_bottom and tuple(bottom_shapes[1]) != (b, s, e):
            raise ValueError(
                f"{lp.name}: the router's bottom {tuple(bottom_shapes[1])} "
                f"is not the experts' {(b, s, e)}")
        if not self.gated and (self.top_k != 1 or p.has("experts_held")
                               or self.shared_hidden):
            raise ValueError(
                f"{lp.name}: top_k > 1, experts_held and a shared expert "
                "belong to the no-drop form (moe_param.gated_experts)")
        if self.gated:
            if self.first + self.held > self.num_experts \
                    or self.top_k > self.num_experts:
                raise ValueError(
                    f"{lp.name}: experts {self.first}..{self.first + self.held}"
                    f" and top_k {self.top_k} of {self.num_experts}")
            # the statistics live in the layer's state: the solver reads
            # them where it already waits for a loss
            self.has_state = len(lp.top) > 1
            if self.has_state:
                self.monitor = ("moe.load", ("held_share", "max_over_mean",
                                             "windows"))

    def state_shapes(self):
        return [((3,), 0.0)] if self.gated and len(self.lp.top) > 1 else []

    def _why_xla(self, dtype):
        """Why the held experts' grouped product keeps the XLA form here,
        or None when the kernels of ops/pallas_moe.py take it."""
        if jax.default_backend() != "tpu":
            return f"the backend is {jax.default_backend()}, not a TPU"
        if self.embed % 128:
            return (f"the width {self.embed} is not a multiple of the lane "
                    "width 128")
        if self.tile % 8:
            return f"tile_rows {self.tile} is not a multiple of 8"
        if dtype not in (jnp.bfloat16, jnp.float32):
            return f"the compute type is {jnp.dtype(dtype).name}"
        return None

    def _capacity(self, n):
        return max(1, math.ceil(n / self.num_experts * self.capacity_factor))

    def param_shapes(self):
        if self.gated:
            return self._gated_param_shapes()
        mults = _param_mults(self.lp, 5)
        X, E, F = self.num_experts, self.embed, self.hidden

        def xavier(fan_in):
            # explicit uniform(+-sqrt(3/fan)) — the generic xavier filler
            # would read fan_in off the FULL 3-d blob shape (F*E), not the
            # per-expert matmul contraction, under-scaling by sqrt(F)
            lim = math.sqrt(3.0 / fan_in)
            return Message("FillerParameter", type="uniform",
                           min=-lim, max=lim)

        wf = self.p.weight_filler if self.p.has("weight_filler") else None
        return [((X, E), wf or xavier(E), *mults[0]),       # router
                ((X, F, E), wf or xavier(E), *mults[1]),    # w1
                ((X, F), None, *mults[2]),                  # b1
                ((X, E, F), wf or xavier(F), *mults[3]),    # w2
                ((X, E), None, *mults[4])]                  # b2

    def blob_names(self):
        """The no-drop form's blobs in order, by what each is."""
        gate, shared = self.gate_matrix, bool(self.shared_hidden)
        return (["router"] + ["w_gate"] * gate + ["w_up", "w_down"]
                + (["ws_gate"] * gate + ["ws_up", "ws_down"]
                   + ["shared_gate"] * self.shared_gate) * shared
                + ["bias"] * self.selection_bias)

    def _gated_param_shapes(self):
        names = self.blob_names()
        mults = _param_mults(self.lp, len(names))
        E, F, Fs = self.embed, self.hidden, self.shared_hidden
        wf = self.p.weight_filler if self.p.has("weight_filler") \
            else Message("FillerParameter", type="gaussian", std=0.02)
        df = self.p.down_filler if self.p.has("down_filler") else wf
        shape = {"router": ((self.num_experts, E), wf),
                 "w_gate": ((self.held, F, E), wf),
                 "w_up": ((self.held, F, E), wf),
                 "w_down": ((self.held, E, F), df),
                 "ws_gate": ((Fs, E), wf), "ws_up": ((Fs, E), wf),
                 "ws_down": ((E, Fs), df), "shared_gate": ((1, E), wf)}
        # the bias is a buffer: zeros, and neither a rate nor a decay
        # moves it
        return [((self.num_experts,), None, 0.0, 0.0) if name == "bias"
                else (*shape[name], *mults[i])
                for i, name in enumerate(names)]

    def out_shapes(self):
        shapes = [tuple(self.bottom_shapes[0])]
        if self.gated:
            return shapes + [(3,)] * (len(self.lp.top) - 1)
        if len(self.lp.top) > 1:
            shapes.append(())                     # aux load-balancing loss
        if len(self.lp.top) > 2:
            # routing diagnostics (stop-gradient): per-expert token
            # fractions + the overflow (dropped-token) fraction
            shapes.append((self.num_experts + 1,))
        return shapes

    def apply(self, params, bottoms, train, rng):
        if self.gated:
            return self.apply_stateful(params, [], bottoms, train, rng)[0]
        x = bottoms[0]
        router, w1, b1, w2, b2 = params
        b, s, e = x.shape
        n = b * s
        X = self.num_experts
        xt = x.reshape(n, e)

        logits = xt.astype(jnp.float32) @ router.T.astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)            # (n, X)
        idx = jnp.argmax(gates, axis=-1)                   # (n,)
        gate = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0]

        # sort-based dispatch, O(n log n + n*C*e) — a dense (n, X, C)
        # one-hot mask would be O(n^2) at long-context token counts.
        # Stable sort by expert; rank within expert = position - first
        # occurrence; earlier tokens win capacity slots (same priority rule
        # as the reference Switch implementation's cumsum).
        C = self._capacity(n)
        order = jnp.argsort(idx, stable=True)              # (n,)
        idx_sorted = idx[order]
        starts = jnp.searchsorted(idx_sorted, jnp.arange(X))
        rank = jnp.arange(n) - starts[idx_sorted]
        keep_s = rank < C
        # dropped/overflow tokens route to a trash row past the buffer
        dest = jnp.where(keep_s, idx_sorted * C + rank, X * C)
        buf = jnp.zeros((X * C + 1, e), jnp.float32) \
            .at[dest].set(xt[order].astype(jnp.float32))
        xe = buf[:-1].reshape(X, C, e)

        ep_axis = context.axis("expert") if self.expert_parallel else None
        if ep_axis is not None:
            # (X, C_local, e): split expert-major across the mesh, gather
            # every peer's tokens for OUR experts along the capacity axis.
            # With tokens sharded along the axis C_local = C/ep and this
            # is the compute-sharded buffer; with tokens replicated it is
            # (X/ep, ep*C, e) and only weight memory shrinks.
            xe = lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        # trace-time introspection for tests/tools: the per-device expert
        # workload is exactly this shape's product
        self._last_dispatch_shape = tuple(xe.shape)

        w1l, b1l, w2l, b2l = (w.astype(jnp.float32)
                              for w in (w1, b1, w2, b2))
        h = jax.nn.relu(jnp.einsum("xce,xfe->xcf", xe, w1l)
                        + b1l[:, None, :])
        ye = jnp.einsum("xcf,xef->xce", h, w2l) + b2l[:, None, :]

        if ep_axis is not None:
            ye = lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                                tiled=True)                # (X, C, e)

        # combine: gather each token's expert output back (dropped tokens
        # hit the zero trash row), weight by its gate
        inv = jnp.argsort(order, stable=True)              # token -> sorted pos
        token_slot = dest[inv]                             # (n,)
        padded = jnp.concatenate(
            [ye.reshape(X * C, e), jnp.zeros((1, e), jnp.float32)])
        y = padded[token_slot] * gate[:, None]
        tops = [y.reshape(b, s, e).astype(x.dtype)]
        if len(self.lp.top) > 1:
            # Switch aux loss: X * sum_e (token fraction)*(mean gate)
            frac = jnp.mean(jax.nn.one_hot(idx, X, dtype=jnp.float32),
                            axis=0)
            tops.append(jnp.asarray(X, jnp.float32)
                        * jnp.sum(frac * jnp.mean(gates, axis=0)))
            if len(self.lp.top) > 2:
                # diagnostics top [frac_0..frac_{X-1}, overflow_fraction]
                # — LOCAL statistics (this shard's tokens); training
                # drivers pmean/log them per step
                overflow = 1.0 - jnp.mean(keep_s.astype(jnp.float32))
                tops.append(lax.stop_gradient(
                    jnp.concatenate([frac, overflow[None]])))
        return tops

    # -- the no-drop form ---------------------------------------------------
    def route(self, xt, router, bias=None):
        """-> (expert indices (n, k) into all the router's outputs, their
        weights (n, k) float32). With `bias` (num_experts,) the experts
        are the top_k of score + bias, the weights their unbiased
        scores."""
        logits = jnp.dot(xt.astype(jnp.float32),
                         router.astype(jnp.float32).T,
                         precision=lax.Precision.HIGHEST)
        score = jax.nn.sigmoid(logits) if self.score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        if bias is None:
            top, idx = lax.top_k(score, self.top_k)
        else:
            _, idx = lax.top_k(score + bias.astype(jnp.float32), self.top_k)
            top = jnp.take_along_axis(score, idx, axis=-1)
        if self.norm_topk:
            total = jnp.sum(top, -1, keepdims=True)
            if self.topk_eps:
                total = total + self.topk_eps
            top = top / total
        if self.scaling != 1.0:
            top = top * self.scaling
        return idx, top

    def _shared(self, xt, blob):
        """The shared expert of every token, float32: behind its sigmoid
        gate unless `shared_gate` is off; of the experts' form."""
        names = ["ws_gate"] * self.gate_matrix + ["ws_up", "ws_down"] \
            + ["shared_gate"] * self.shared_gate
        w = {n: blob[n].astype(xt.dtype) for n in names}
        if self.gate_matrix:
            h = jax.nn.silu(xt @ w["ws_gate"].T) * (xt @ w["ws_up"].T)
        else:
            h = _gate(jnp.dot(xt, w["ws_up"].T,
                              preferred_element_type=jnp.float32),
                      self.act).astype(xt.dtype)
        if not self.shared_gate:
            return jnp.dot(h, w["ws_down"].T,
                           preferred_element_type=jnp.float32)
        open_ = jax.nn.sigmoid(jnp.dot(
            xt, w["shared_gate"].T, preferred_element_type=jnp.float32))
        return open_ * jnp.dot(h, w["ws_down"].T,
                               preferred_element_type=jnp.float32)

    def apply_stateful(self, params, state, bottoms, train, rng):
        x = bottoms[0]
        b, s, e = x.shape
        n, k, held = b * s, self.top_k, self.held
        why_xla = self._why_xla(x.dtype)
        # a hidden width off the lane width: the kernels see zero columns
        # up to the next multiple of 128, the blobs do not
        pad = -self.hidden % 128 if why_xla is None else 0
        tile = self.tile
        if why_xla is None and not self.p.has("tile_rows"):
            tile = kernel_tile(tile, e, self.hidden + pad, x.dtype.itemsize)
        with jax.named_scope("moe_glue"):
            xt = x.reshape(n, e)
        with jax.named_scope("moe_route"):
            routed = bottoms[1].reshape(n, e) if self.router_bottom else xt
            idx, top = self.route(
                routed, params[0],
                params[-1] if self.selection_bias else None)
            local = idx.reshape(n * k) - self.first
            pair_expert = jnp.where((local >= 0) & (local < held), local,
                                    held).astype(jnp.int32)
            window = window_rows(n, k, held, self.num_experts, tile)
            plan = plan_windows(pair_expert, held, window)
        tracer = default_tracer()
        now = tracer.now_ns()
        tracer.record("moe.path", now, now, layer=self.lp.name,
                      path="xla" if why_xla else "kernel",
                      reason=why_xla or "backend, widths and tile_rows fit"
                      + (f"; the hidden width {self.hidden} padded by {pad} "
                         "zero columns in the cast copies" if pad else ""),
                      activation=self.act, score=self.score,
                      selection_bias=self.selection_bias,
                      combine="gather", segment=min(k, held),
                      tile=tile, window=window, first_window="backward",
                      rows_an_expert=round(n * k / self.num_experts),
                      matrices=3 if self.gate_matrix else 2,
                      shared_gate=bool(self.shared_hidden)
                      and self.shared_gate)
        blob = dict(zip(self.blob_names(), params))

        def cast(name, axis):       # `axis`: where the hidden width lies
            w = blob.get(name)
            if w is None:
                return None
            w = w.astype(x.dtype)
            return jnp.pad(w, [(0, pad if d == axis else 0)
                               for d in range(3)]) if pad else w
        # what the window loop costs beside its body's three scopes: the
        # held weights cast to the compute type, the loop's zero start
        # (backward: the spill loop's adds onto the first window's results)
        with jax.named_scope("moe_glue"):
            wg, wu, wd = cast("w_gate", 1), cast("w_up", 1), cast("w_down", 2)
            y = held_experts(xt, top.reshape(n * k), plan, wg, wu, wd,
                             tile, k, window, why_xla is None, self.act)
        if self.shared_hidden:
            with jax.named_scope("moe_shared"):
                y = y + self._shared(xt, blob)
        with jax.named_scope("moe_glue"):
            tops = [y.reshape(b, s, e).astype(x.dtype)]
        if len(self.lp.top) > 1:
            with jax.named_scope("moe_route"):
                load = plan["count"].astype(jnp.float32)
                here = jnp.sum(load)
                stats = lax.stop_gradient(jnp.stack([
                    here / (n * k),
                    jnp.max(load) * held / jnp.maximum(here, 1.0),
                    plan["windows"].astype(jnp.float32)]))
            tops.append(stats)
            return tops, [stats]
        return tops, state
