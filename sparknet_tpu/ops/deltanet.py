"""Gated DeltaNet — the linear-attention mixer with a recurrent state.

sparknet_tpu extension (no CNN-era twin): the layer that three of four
blocks of a hybrid language model use in place of softmax attention.
Bottom (B, S, E), top (B, S, E); every projection without bias.

  [q, k, v, z] = W_qkvz x    (Hk*Dk | Hk*Dk | Hv*Dv | Hv*Dv)
  [b, a]       = W_ba x      (Hv | Hv)
  [q, k, v]   <- SiLU(causal depthwise conv over the sequence, kernel K)
  beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (float32)
  q, k repeated to the Hv value heads, L2-normalised, q scaled by Dk^-0.5
  per head, with a state S in R^(Dk x Dv) that starts at zero:
      S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T
      o_t = S^T q_t
  o <- RMSNorm(o) * w * SiLU(z) per head;  y = W_out o

Blobs: W_qkvz (2 Hk Dk + 2 Hv Dv, E) | W_ba (2 Hv, E) | conv (2 Hk Dk +
Hv Dv, K) | A_log (Hv,) | dt_bias (Hv,) | norm (Dv,) | W_out (E, Hv Dv).

The recurrence is computed in chunks of `chunk` tokens (the published
chunked form). With G the running sum of g inside a chunk and S0 the state
at its start, the chunk's u solve the unit lower-triangular system
    (I + A) U = beta * (V - exp(G) * (K S0)),
    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)  for j < i,
so with T = (I + A)^-1:  U = T (beta V) - T (beta exp(G) K) S0,
    O = (exp(G) Q) S0 + (M * (Q K^T)) U,   M_ij = exp(G_i - G_j), j <= i,
    S_end = exp(G_end) S0 + (exp(G_end - G) K)^T U.
Two forms compute this, chosen where the layer is traced from what it can
see there (no switch, no argument):

- the pallas kernels of ops/pallas_deltanet.py (`gdn_chunk_fwd`,
  `gdn_chunk_bwd`) when Dk and Dv are multiples of the lane width 128 and
  `chunk` is 64: a chunk's triangular system, its products and the state
  stay in VMEM, the state carried across a sequential grid axis. The
  backward STORES each chunk's T and the state at the start of every 8th
  chunk (134 + 67 MB a layer at 2 x 8,192 tokens) and runs 8 chunks
  forward again, in VMEM, before it walks them back; q and k go in as the
  conv leaves them and are normalised inside. The CPU backend runs the
  kernels in interpret mode (the tests).
  The module is imported in the branch of `apply` that calls it: a
  process that traces no such layer never imports pallas
  (tests/test_pallas_deltanet.py holds that).
- the XLA form below for every other shape (the tests' toy heads), which
  is also the oracle between the kernels and the token-by-token
  reference: T, T (beta V), T (beta exp(G) K) and M * (Q K^T) are made for
  a group of 16 chunks at once; a `lax.scan` over the group's chunks
  carries S, and a `lax.scan` over the groups carries it on. The group's
  body is checkpointed, so the backward pass stores one state per GROUP,
  recomputes a group at a time and never holds a state per token, nor the
  chunk matrices of more than one group (at 16,384 tokens all groups at
  once took 11 GB).

Which one a layer took is in the ring of obs/trace.py: one `gdn.path`
record a trace of the layer, `path` = `kernel` or `xla` with the `reason`.
Everything the layer traces lies under one of five scopes inside its own,
so that a device trace adds up by them: gdn_proj_in (W_qkvz, W_ba and the
split), gdn_conv, gdn_scan (the heads' reshapes, beta, g and the rule),
gdn_gate_norm, gdn_proj_out.
A is strictly lower triangular, so its powers vanish at `chunk` and the
inverse is the exact product (I - A)(I + A^2)(I + A^4)...: log2(chunk)
squarings, all matrix products (the kernels use it on the diagonal blocks
of 16 and join the blocks exactly). State, decays and the triangular
system are float32 at the highest matmul precision in both forms.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..proto import Message
from ..graph.registry import Layer, register
from ..obs.trace import default_tracer, kernel_import
from .convolution import _param_mults
from .normalization import rms_norm

_HI = lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular a (..., C, C): the Neumann
    series sum_k (-a)^k, which ends at C, as the product
    (I - a)(I + a^2)(I + a^4)... ."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv, power, span = eye - a, a, 2
    while span < c:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = _mm("...ij,...jk->...ik", inv, eye + power)
        span *= 2
    return inv


def _group_rule(s, q, k, v, beta, g, chunk):
    """The chunked rule over one group of whole chunks, from the state `s`
    (B, H, Dk, Dv): q, k (B, L, H, Dk) normalised and scaled, v (B, L, H,
    Dv), beta and g (B, L, H), float32. -> (state after, o (B, L, H, Dv)).
    What depends on no state is made for all the group's chunks at once."""
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    n = length // chunk

    def chunks(a):          # (B, L, H, ...) -> (N, B, H, C, ...)
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)
    q, k, v, beta, g = [chunks(a) for a in (q, k, v, beta, g)]
    gc = jnp.cumsum(g, axis=-1)                         # G (N, B, H, C)
    # exp(G_i - G_j) for j <= i: every exponent is <= 0
    diff = gc[..., :, None] - gc[..., None, :]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = _mm("...id,...jd->...ij", k, k)
    a = jnp.tril(beta[..., :, None] * decay * kk, -1)
    tinv = unit_lower_inverse(a)
    u0 = _mm("...ij,...jd->...id", tinv, beta[..., None] * v)
    w = _mm("...ij,...jd->...id", tinv,
            (beta * jnp.exp(gc))[..., None] * k)
    att = decay * _mm("...id,...jd->...ij", q, k)
    qg = q * jnp.exp(gc)[..., None]
    g_end = gc[..., -1]                                 # (N, B, H)
    kd = k * jnp.exp(g_end[..., None] - gc)[..., None]

    def step(s, inp):
        u0_c, w_c, att_c, qg_c, kd_c, ge_c = inp
        u = u0_c - _mm("...cd,...dv->...cv", w_c, s)
        o = _mm("...cd,...dv->...cv", qg_c, s) \
            + _mm("...ij,...jv->...iv", att_c, u)
        s = s * jnp.exp(ge_c)[..., None, None] \
            + _mm("...cd,...cv->...dv", kd_c, u)
        return s, o

    s, o = lax.scan(step, s, (u0, w, att, qg, kd, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)       # (B, N, C, H, Dv)
    return s, o.reshape(b, length, h, dv)


def gated_delta_rule(q, k, v, beta, g, chunk=64, group=16, prepare=None):
    """The gated delta rule in chunks of `chunk` tokens. q, k (B, T, H,
    Dk), v (B, T, H, Dv), beta and g (B, T, H) -> o (B, T, H, Dv) float32.
    q and k come normalised and scaled, or `prepare(q, k)` makes them so
    (and repeats them to v's heads) a group at a time. A `lax.scan` over
    groups of `group` chunks carries the state; its body is checkpointed,
    so the backward pass stores one state per GROUP, recomputes a group
    and holds no more than one group's chunk matrices at a time. T is
    padded to whole groups with tokens that leave the state alone."""
    b, t = q.shape[:2]
    h, dv = v.shape[2:]
    n = -(-t // chunk)
    group = min(group, n)
    length = group * chunk
    pad = -t % length
    if pad:
        q, k, v, beta, g = [
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, beta, g)]
    groups = (t + pad) // length

    def split(a):           # (B, T, ...) -> (groups, B, L, ...)
        return jnp.moveaxis(
            a.reshape((b, groups, length) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_group(s, inp):
        q, k, v, beta, g = inp
        if prepare is not None:
            q, k = prepare(q, k)
        q, k, v, beta, g = [a.astype(jnp.float32)
                            for a in (q, k, v, beta, g)]
        return _group_rule(s, q, k, v, beta, g, chunk)

    dk = q.shape[-1]
    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = lax.scan(one_group, s0, tuple(split(a)
                                         for a in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1).reshape(b, groups * length, h, dv)[:, :t]


L2_EPS = 1e-6


def l2_normalize(u):
    """u / |u| over the last axis, in float32."""
    u = u.astype(jnp.float32)
    return u * lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + L2_EPS)


def causal_depthwise_conv(x, w):
    """y_t = sum_j w[c, j] x_{t-K+1+j}: x (B, T, C), w (C, K)."""
    k = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(k))


@register
class GatedDeltaNet(Layer):
    type_name = "GatedDeltaNet"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.gated_delta_net_param
        self.p = p
        self.embed = int(bottom_shapes[0][-1])
        self.hk, self.hv = int(p.num_k_heads), int(p.num_v_heads)
        self.dk, self.dv = int(p.head_k_dim), int(p.head_v_dim)
        self.kernel, self.chunk = int(p.conv_kernel), int(p.chunk)
        self.eps = float(p.norm_eps)
        if self.hv % self.hk:
            raise ValueError(f"{lp.name}: num_v_heads {self.hv} is not a "
                             f"multiple of num_k_heads {self.hk}")
        if self.chunk & (self.chunk - 1):
            raise ValueError(f"{lp.name}: chunk {self.chunk} is not a "
                             "power of two")

    def _why_xla(self):
        """Why this layer's shapes keep the XLA form, or None when the
        kernels take them."""
        if self.dk % 128 or self.dv % 128:
            return (f"head sizes {self.dk} and {self.dv} are not multiples "
                    "of the lane width 128")
        if self.chunk != 64:
            return f"chunk {self.chunk} is not 64"
        return None

    def param_shapes(self):
        mults = _param_mults(self.lp, 7)
        wf = self.p.weight_filler if self.p.has("weight_filler") \
            else Message("FillerParameter", type="gaussian", std=0.02)
        one = Message("FillerParameter", type="constant", value=1.0)
        # the state-space convention: A in [1, 16), so A_log in [0, log 16)
        a_log = Message("FillerParameter", type="uniform", min=0.0,
                        max=2.772588722239781)
        kd, vd = self.hk * self.dk, self.hv * self.dv
        return [((2 * kd + 2 * vd, self.embed), wf, *mults[0]),   # W_qkvz
                ((2 * self.hv, self.embed), wf, *mults[1]),       # W_ba
                ((2 * kd + vd, self.kernel), wf, *mults[2]),      # conv
                ((self.hv,), a_log, *mults[3]),                   # A_log
                ((self.hv,), one, *mults[4]),                     # dt_bias
                ((self.dv,), one, *mults[5]),                     # norm
                ((self.embed, vd), wf, *mults[6])]                # W_out

    def out_shapes(self):
        return [tuple(self.bottom_shapes[0])]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        w_qkvz, w_ba, conv, a_log, dt_bias, norm, w_out = params
        b, t, _ = x.shape
        hk, hv, dk, dv = self.hk, self.hv, self.dk, self.dv
        kd, vd = hk * dk, hv * dv
        with jax.named_scope("gdn_proj_in"):
            qkvz = x @ w_qkvz.astype(x.dtype).T
            qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
            ba = (x @ w_ba.astype(x.dtype).T).astype(jnp.float32)
        with jax.named_scope("gdn_conv"):
            qkv = jax.nn.silu(causal_depthwise_conv(
                qkv, conv.astype(x.dtype)))
        with jax.named_scope("gdn_scan"):
            q = qkv[..., :kd].reshape(b, t, hk, dk)
            k = qkv[..., kd:2 * kd].reshape(b, t, hk, dk)
            v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32))

            why_xla = self._why_xla()
            tracer = default_tracer()
            now = tracer.now_ns()
            tracer.record("gdn.path", now, now, layer=self.lp.name,
                          path="xla" if why_xla else "kernel",
                          reason=why_xla or "head sizes and chunk fit")
            if why_xla:
                def prepare(q, k):  # float32, a group of chunks at a time
                    return (jnp.repeat(l2_normalize(q), hv // hk, axis=2)
                            * dk ** -0.5,
                            jnp.repeat(l2_normalize(k), hv // hk, axis=2))
                o = gated_delta_rule(q, k, v, beta, g, self.chunk,
                                     prepare=prepare)
            else:
                # here and not at the top: a process without such a layer
                # never imports pallas (1.4 s of every cell's set-up, PR 29)
                with kernel_import("sparknet_tpu.ops.pallas_deltanet"):
                    from .pallas_deltanet import chunk_rule
                o, _ = chunk_rule(q, k, v, beta, g, chunk=self.chunk,
                                  layer=self.lp.name)
        with jax.named_scope("gdn_gate_norm"):
            o = rms_norm(o, norm, self.eps, zero_centered=False)
            o = o * jax.nn.silu(z.reshape(b, t, hv, dv).astype(jnp.float32))
            o = o.reshape(b, t, vd).astype(x.dtype)
        with jax.named_scope("gdn_proj_out"):
            return [o @ w_out.astype(x.dtype).T]
