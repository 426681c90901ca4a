"""Mamba-2 — the state-space mixer of a hybrid language model.

sparknet_tpu extension (no CNN-era twin): the layer that most blocks of a
Nemotron-H style hybrid use in place of attention. Bottom (B, S, E), top
(B, S, E); no bias on the projections. With H heads of P channels (inner
width H P, sized by the heads, NOT by an expansion factor of E), a state of
N a channel and G groups (head i reads group i // (H / G)):

  [z | xBC | dt] = W_in h          widths H P | H P + 2 G N | H
  xBC = silu(conv(xBC) + b_c)      causal depthwise, K taps, zeros before 0
  x (H heads of P) | B | C (G groups of N) = xBC
  delta_t = softplus(dt_t + dt_bias)   one a head, no clamp     (float32)
  A = -exp(A_log)                      a scalar a head;  a_t = exp(delta_t A)
  per head, with a state H in R^(P x N) that starts at zero:
      H_t = a_t H_{t-1} + delta_t x_t B_t^T
      y_t = H_t C_t + D x_t            D a scalar a head
  g = y * silu(z);  y = w * g / rms(g) over each GROUP's H P / G channels
  (the gated RMSNorm: the gate first, then the norm, a group at a time)
  out = W_out y

Blobs: W_in (2 H P + 2 G N + H, E) | conv (H P + 2 G N, K) | conv bias
(H P + 2 G N,) | A_log (H,) | D (H,) | dt_bias (H,) | norm (H P,) | W_out
(E, H P). Fillers: matrices `weight_filler` (unset: gaussian(0.02);
`out_filler` for W_out alone), taps and their bias uniform(+-1/sqrt(K))
(what a depthwise torch Conv1d fills unasked), A_log uniform(0, log 16) (A
in [1, 16), the state-space convention), D and the norm 1, dt_bias uniform
between the inverse softplus of `dt_min` and of `dt_max`, so that delta
starts inside [dt_min, dt_max], all but log-uniform there.

The recurrence is computed in chunks of `chunk` tokens (the published SSD
form), never token by token and never as an S x S matrix. With l_t the
running sum of delta A inside a chunk (inclusive), H_{c-1} the state at the
end of the chunk before and dx_s = delta_s x_s:

  Y_intra = ((C B^T) * L) dx,   L[t, s] = exp(l_t - l_s) for s <= t, else 0
  S_c     = sum_s exp(l_end - l_s) dx_s B_s^T      the chunk's own state
  H_c     = exp(l_end) H_{c-1} + S_c               the carry
  Y_inter[t] = exp(l_t) H_{c-1} C_t

The heads of a group share one C B^T tile a chunk. The carry is no loop:
every chunk's H_{c-1} is the chunks' own states times the (chunks x chunks)
matrix of the decays between them, one product a head (64 x 64 at 8,192
tokens; it grows with the square of S / chunk and is the first thing a
much longer sequence would turn into a scan over chunks). S is padded to
whole chunks with tokens whose delta is 0: they move no state and their
rows are cut off.

In float32 whatever the compute type: softplus, delta A and its running
sums, every decay (L, exp(l_end - l_s), exp(l_t), the decays between
chunks), the chunk states, their carry and the product that reads the
carried state (at the highest matmul precision: a state is never rounded),
every product's accumulation, the gate and the group norm. In the compute
type, as operands of the MXU: x, B and C as the conv leaves them, and the
two decay-weighted operands, (C B^T) * L and exp(l_end - l_s) dx, each
formed in float32 and rounded ONCE (what a flash kernel does with its
probabilities).

Two forms compute this, chosen where the layer is traced from what it can
see there (no switch, no argument):

- the pallas kernels of ops/pallas_ssd.py (`ssd_chunk_fwd`,
  `ssd_chunk_bwd`) when `state_size` and `chunk` are both 128 and a
  group's H / G heads of P channels fill whole lane tiles (H P / G a
  multiple of 128, P a divisor or a multiple of 128; the published 8 heads
  of 64): a grid step is one chunk of one group, its heads' decay masks,
  its one C B^T tile and the state (N x r P float32, carried across a
  sequential grid axis) never leave VMEM, and the carry is the recurrence
  H_c = exp(l_end) H_{c-1} + S_c itself. The forward stores the state every
  chunk starts from (268 MB a layer at 2 x 8,192 tokens) for a backward
  kernel that walks the chunks in reverse; y, those states and the last one
  go through `graph/remat.py:keep`, so under `remat` the forward kernel
  runs once a step. The CPU backend runs the kernels in interpret mode
  (the tests). The module is imported in the branch of `apply_stateful`
  that calls it: a process that traces no such layer never imports pallas
  (tests/test_pallas_deltanet.py holds that).
- XLA's form below for every other shape (the tests' toy heads), which is
  also the oracle between the kernels and the token-by-token recurrence: a
  row of the batch at a time (`lax.map` over the rows, each row's scan
  checkpointed: the backward holds one row's decay masks, heads x chunks x
  chunk x chunk, not the batch's).

Which one a layer took is in the ring of obs/trace.py: one `ssm.path`
record a trace of the layer, `path` = `kernel` or `chunked` with heads,
head size, state, groups, chunk and the `reason`.
Everything the layer traces lies under one of five scopes inside its own,
none inside another, so that a device trace adds up by them: ssm_proj_in
(W_in and the split), ssm_conv, ssm_scan (delta, A, the chunked scan, the D
skip), ssm_gate_norm, ssm_proj_out. With `stats` a second top (weight 0,
kept as layer state so that the solver records it as `ssm.stats` where it
waits for a loss): the mean over rows, chunks and heads of exp(l_end), the
share of a state that survives one chunk — whether the carry matters at
these weights.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..proto import Message
from ..graph.registry import Layer, register
from ..obs.trace import default_tracer, kernel_import
from .convolution import _param_mults
from .deltanet import causal_depthwise_conv

_HI = lax.Precision.HIGHEST


def _decay(diff, mask):
    """exp(diff) where `mask`, else 0; no exponent of a masked place is
    taken (it may be large and positive)."""
    return jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)


def _ssd_row(x, dt, a, b, c, chunk):
    """The chunked scan of ONE sequence of whole chunks from a zero state:
    x (S, H, P) and b, c (S, G, N) in the compute type, dt (S, H) float32
    (delta), a (H,) float32 (A < 0). -> (y (S, H, P) float32 without the D
    skip, the state after the last token (H, P, N) float32, the mean of
    exp(l_end) over chunks and heads)."""
    s, h, p = x.shape
    g, n = b.shape[1:]
    r, q, nc = h // g, chunk, s // chunk
    f32 = jnp.float32
    xc = x.reshape(nc, q, g, r, p)
    bc, cc = b.reshape(nc, q, g, n), c.reshape(nc, q, g, n)
    dtc = dt.reshape(nc, q, g, r)
    l = jnp.cumsum(dtc * a.reshape(g, r), axis=1)           # (nc, q, g, r)
    dx = dtc[..., None] * xc.astype(f32)                    # delta x

    # inside a chunk: ((C B^T) * L) dx, one C B^T tile a group
    cb = jnp.einsum("cqgn,csgn->cgqs", cc, bc, preferred_element_type=f32)
    lt = jnp.moveaxis(l, 1, -1)                             # (nc, g, r, q)
    lower = jnp.tril(jnp.ones((q, q), bool))
    m = cb[:, :, None] * _decay(lt[..., :, None] - lt[..., None, :], lower)
    y = jnp.einsum("cgrqs,csgrp->cqgrp", m.astype(x.dtype),
                   dx.astype(x.dtype), preferred_element_type=f32)

    # each chunk's own state, and the state every chunk starts from
    l_end = l[:, -1]                                        # (nc, g, r)
    xw = (dx * jnp.exp(l_end[:, None] - l)[..., None]).astype(x.dtype)
    own = jnp.einsum("cqgrp,cqgn->cgrpn", xw, bc, preferred_element_type=f32)
    upto = jnp.cumsum(jnp.moveaxis(l_end, 0, -1), axis=-1)  # (g, r, nc)
    before = upto - jnp.moveaxis(l_end, 0, -1)              # the sum before c
    # between[c, d] = the decay from the end of chunk d to the start of c
    between = _decay(before[..., :, None] - upto[..., None, :],
                     jnp.tril(jnp.ones((nc, nc), bool), -1))
    start = jnp.einsum("grcd,dgrpn->cgrpn", between, own, precision=_HI,
                       preferred_element_type=f32)
    y = y + jnp.exp(l)[..., None] * jnp.einsum(
        "cqgn,cgrpn->cqgrp", cc.astype(f32), start, precision=_HI,
        preferred_element_type=f32)
    last = jnp.exp(l_end[-1])[..., None, None] * start[-1] + own[-1]
    return (y.reshape(s, h, p), last.reshape(h, p, n),
            jnp.mean(jnp.exp(l_end)))


def ssd_chunked(x, dt, a, b, c, chunk=128):
    """The Mamba-2 recurrence H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T,
    y_t = H_t C_t in chunks of `chunk` tokens, from a zero state: x (B, S,
    H, P), dt (B, S, H) (delta, cast to float32), a (H,) (A < 0), b, c (B,
    S, G, N), head i reading group i // (H / G). -> (y (B, S, H, P)
    float32, the last state (B, H, P, N) float32, the mean share of a state
    that survives a chunk). A row of the batch at a time, each row's scan
    checkpointed; S is padded to whole chunks with tokens of delta 0."""
    s = x.shape[1]
    pad = -s % chunk
    dt = dt.astype(jnp.float32)
    if pad:
        x, dt, b, c = [jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c)]
    a = a.astype(jnp.float32)
    row = jax.checkpoint(lambda v: _ssd_row(v[0], v[1], a, v[2], v[3], chunk))
    y, last, survive = lax.map(row, (x, dt, b, c))
    return y[:, :s], last, jnp.mean(survive)


def gated_group_norm(y, z, w, groups, eps):
    """w * g / rms(g) with g = y * silu(z), the mean square over each of
    `groups` runs of the last axis: the gate first, then the norm. y, z
    (..., C), w (C,) -> (..., C) float32. A group's squares are summed,
    and its factor spread back over its channels, by products with the 0/1
    matrix of which channel lies in which group, at the highest precision
    (float32 sums, as a reduction's). A reshape to (..., groups, C /
    groups) would put the groups where a tile's rows of tokens are, and
    XLA then moves the whole array before it reduces (0.82 ms a pass over
    a mixer's 268 MB, nine times a step, and the norm alone 9.7 ms where
    this form takes 4.4: chip runs, PR 43)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    c = g.shape[-1]
    member = (jnp.arange(c)[:, None] // (c // groups)
              == jnp.arange(groups)).astype(jnp.float32)       # (C, groups)
    mean_sq = jnp.einsum("...c,cg->...g", g * g, member,
                         precision=_HI) / (c // groups)
    return g * jnp.einsum("...g,cg->...c", lax.rsqrt(mean_sq + eps), member,
                          precision=_HI) * w.astype(jnp.float32)


def inverse_softplus(y):
    """x with softplus(x) = y, for y > 0."""
    return y + math.log(-math.expm1(-y))


@register
class Mamba2(Layer):
    type_name = "Mamba2"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.mamba2_param
        self.p = p
        self.embed = int(bottom_shapes[0][-1])
        self.heads, self.head_dim = int(p.num_heads), int(p.head_dim)
        self.state, self.groups = int(p.state_size), int(p.n_groups)
        self.kernel, self.chunk = int(p.conv_kernel), int(p.chunk)
        self.eps = float(p.norm_eps)
        self.inner = self.heads * self.head_dim
        self.conv_dim = self.inner + 2 * self.groups * self.state
        if self.groups < 1 or self.heads % self.groups:
            raise ValueError(f"{lp.name}: num_heads {self.heads} is not a "
                             f"multiple of n_groups {self.groups}")
        if min(self.head_dim, self.state, self.kernel, self.chunk) < 1:
            raise ValueError(f"{lp.name}: head_dim, state_size, conv_kernel "
                             "and chunk must be at least 1")
        if not 0.0 < float(p.dt_min) <= float(p.dt_max):
            raise ValueError(f"{lp.name}: dt_min {float(p.dt_min)} and "
                             f"dt_max {float(p.dt_max)}: want 0 < min <= max")
        # the statistic lives in the layer's state, as the MoE's: the
        # solver reads it where it already waits for a loss
        self.has_state = bool(int(p.stats))
        if self.has_state:
            self.monitor = ("ssm.stats", ("chunk_survival",))
            if len(lp.top) != 2:
                raise ValueError(f"{lp.name}: mamba2_param.stats wants a "
                                 "second top")

    def _why_xla(self):
        """Why this layer's shapes keep XLA's chunked form, or None when
        the kernels of ops/pallas_ssd.py take them."""
        r, p = self.heads // self.groups, self.head_dim
        if self.state != 128 or self.chunk != 128:
            return (f"state {self.state} and chunk {self.chunk} are not "
                    "both 128, the kernels' one tile")
        if (r * p) % 128 or (p % 128 and 128 % p):
            return (f"a group's {r} heads of {p} do not fill whole lane "
                    "tiles of 128")
        if r > 64:
            return (f"{r} heads a group: their per-token rows do not fit "
                    "one transpose of a chunk")
        return None

    def param_shapes(self):
        mults = _param_mults(self.lp, 8)
        p = self.p
        wf = p.weight_filler if p.has("weight_filler") \
            else Message("FillerParameter", type="gaussian", std=0.02)
        of = p.out_filler if p.has("out_filler") else wf
        lim = 1.0 / math.sqrt(self.kernel)
        taps = Message("FillerParameter", type="uniform", min=-lim, max=lim)
        one = Message("FillerParameter", type="constant", value=1.0)
        a_log = Message("FillerParameter", type="uniform", min=0.0,
                        max=math.log(16.0))
        dt_bias = Message("FillerParameter", type="uniform",
                          min=inverse_softplus(float(p.dt_min)),
                          max=inverse_softplus(float(p.dt_max)))
        h = self.heads
        return [((self.inner + self.conv_dim + h, self.embed), wf,
                 *mults[0]),                                    # W_in
                ((self.conv_dim, self.kernel), taps, *mults[1]),  # conv
                ((self.conv_dim,), taps, *mults[2]),            # conv bias
                ((h,), a_log, *mults[3]),                       # A_log
                ((h,), one, *mults[4]),                         # D
                ((h,), dt_bias, *mults[5]),                     # dt_bias
                ((self.inner,), one, *mults[6]),                # norm
                ((self.embed, self.inner), of, *mults[7])]      # W_out

    def out_shapes(self):
        return [tuple(self.bottom_shapes[0])] + [(1,)] * self.has_state

    def state_shapes(self):
        return [((1,), 0.0)] if self.has_state else []

    def apply(self, params, bottoms, train, rng):
        return self.apply_stateful(params, [], bottoms, train, rng)[0]

    def apply_stateful(self, params, state, bottoms, train, rng):
        x = bottoms[0]
        w_in, conv, conv_b, a_log, d_skip, dt_bias, norm, w_out = params
        bsz, s, _ = x.shape
        h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        f32 = jnp.float32
        why_xla = self._why_xla()
        tracer = default_tracer()
        now = tracer.now_ns()
        tracer.record("ssm.path", now, now, layer=self.lp.name,
                      path="chunked" if why_xla else "kernel", heads=h,
                      head_dim=p, state=n, groups=g, chunk=self.chunk,
                      reason=why_xla or "state, chunk and a group's heads "
                                        "fit the kernels' tiles")
        with jax.named_scope("ssm_proj_in"):
            zxbcdt = x @ w_in.astype(x.dtype).T
            z = zxbcdt[..., :self.inner]
            xbc = zxbcdt[..., self.inner:self.inner + self.conv_dim]
            dt = zxbcdt[..., self.inner + self.conv_dim:].astype(f32)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(
                causal_depthwise_conv(xbc, conv.astype(x.dtype))
                + conv_b.astype(x.dtype))
        with jax.named_scope("ssm_scan"):
            xs = xbc[..., :self.inner].reshape(bsz, s, h, p)
            b = xbc[..., self.inner:self.inner + g * n].reshape(bsz, s, g, n)
            c = xbc[..., self.inner + g * n:].reshape(bsz, s, g, n)
            delta = jax.nn.softplus(dt + dt_bias.astype(f32))
            a = -jnp.exp(a_log.astype(f32))
            if why_xla:
                y, _, survive = ssd_chunked(xs, delta, a, b, c, self.chunk)
            else:
                # here and not at the top: a process without such a layer
                # never imports pallas (1.4 s of every cell's set-up, PR 29)
                with kernel_import("sparknet_tpu.ops.pallas_ssd"):
                    from .pallas_ssd import chunk_scan
                y, _, survive = chunk_scan(xs, delta, a, b, c, self.chunk,
                                           layer=self.lp.name)
            y = y + d_skip.astype(f32)[:, None] * xs.astype(f32)
        with jax.named_scope("ssm_gate_norm"):
            y = gated_group_norm(y.reshape(bsz, s, self.inner), z, norm, g,
                                 self.eps).astype(x.dtype)
        with jax.named_scope("ssm_proj_out"):
            tops = [y @ w_out.astype(x.dtype).T]
        if self.has_state:
            stats = lax.stop_gradient(survive.reshape(1))
            return tops + [stats], [stats]
        return tops, state
