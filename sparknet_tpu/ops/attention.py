"""Multi-head self-attention — the long-context extension layer.

Not in the CNN-era reference (SURVEY.md section 5: no attention anywhere);
this is the sparknet_tpu-native layer that the sequence-parallel machinery
(parallel.ring) plugs into. Bottom blob: (B, S, E). Fused QKV projection
keeps one large MXU matmul; when the net is traced inside a sequence-sharded
shard_map (parallel.context provides a "seq" axis) and attention_param.ring
is set, the core switches to ring attention over the mesh — the layer code
is identical on 1 chip and on a 64-way ring.

The grouped-query form (attention_param.num_kv_heads set) is the attention
of today's language models: separate bias-free projections
  q (H*D, E) — or [q | gate] per head, (H*2D, E), with output_gate —,
  k (Hkv*D, E), v (Hkv*D, E), out (E, H*D), then with qk_norm the two
  RMSNorm weights (D,) of the query and key heads, applied to every head
  before the rotary: zero-centred, y = x / rms(x) * (1 + w) with w filled
  with 0, or with qk_norm_zero_centered false the plain y = x / rms(x) * w
  with w filled with 1;
each key-value head serves H / Hkv query heads; rotary embedding
(rotate-half convention, positions 0..S-1) on the first rotary_dim
dimensions of every head, the rest untouched; softmax(q k^T / sqrt(D)) v;
with output_gate o * sigmoid(gate) before the out projection
(`out_filler` fills that one where a model starts it smaller). The flash
kernel reads the shared key-value heads in place (no repeat in memory);
the dense path repeats them.

The output gate has a second form, PER HEAD (attention_param.head_gate;
`gate="head"` in models/dsl.py; a layer takes one of the two): one more
blob W_g (H, E) after the head norms, g = sigmoid(W_g x) in float32, one
scalar a head and a token, and o_h * g_h before the out projection. Its
product and sigmoid lie under the scope attn_gate inside attn_proj_in, its
multiply under attn_gate inside attn_proj_out.

The rotary's angles are position x inv_freq over the rotary_dim / 2 pairs,
and inv_freq is a TABLE (`rope_table`): rope_type "plain", the default,
inv_i = rope_theta ** (-2i / rotary_dim); rope_type "yarn" the blend the
`transformers` library computes for YaRN, with d = rotary_dim, f =
rope_factor, L = rope_original_positions:
  extra_i = rope_theta ** (-2i / d), inter_i = extra_i / f,
  low  = floor(d ln(L / (rope_beta_fast 2 pi)) / (2 ln rope_theta)),
  high = ceil(d ln(L / (rope_beta_slow 2 pi)) / (2 ln rope_theta)),
         both inside [0, d - 1] (`yarn_range`),
  ramp_i = clip((i - low) / (high - low), 0, 1),
  inv_i = inter_i ramp_i + extra_i (1 - ramp_i);
rope_scale multiplies cos and sin (absent with yarn: 0.1 ln f + 1), so the
turned dimensions of q and of k are scaled and the passing ones are not. A
net chooses table and theta layer by layer: window layers with the plain
table on the whole head beside full layers with YaRN on half of it.

The rotary is ONE pass over its operand (`rotary`), x cos + rotate_half(x)
sin in float32 with float32 tables: rotate-half is the product of x with a
constant D x D matrix of 0 and +-1 (exact: every output is one input times
+-1, accumulated in float32), and cos and sin are as wide as the head, 1
and 0 on the dimensions that pass, so nothing slices or joins the lane
axis. The written-out form (`concatenate([-x2, x1])`, and a second join
for a partial rotary) XLA does not fuse on a TPU: it made three passes of
it, forward and backward each — a float32 copy of x, two half-width
float32 arrays padded to whole lane rows, then the arithmetic —, 9.20 GB
moved for the VJP at a bf16 q of (2, 8192, 64, 128) where reading and
writing q twice is 1.07 GB, 101 ms of a 620 ms step in the cell that
showed it most. The product is one fusion a pass with the move to (B, H,
S, D) inside it: 2.22 GB for the same VJP, the same bits. Its backward is
its own (`jax.custom_vjp`): the same pass over the cotangent with sin
negated, the tables rebuilt from the static arguments.

attention_param.window (with causal) is a sliding window: query i sees keys
i - window < j <= i. The flash kernel then runs over the band of key blocks
alone (`flash_swa_*` in a device trace), the dense path masks the same way.
Which core a layer took is in the ring of obs/trace.py, one `attn.path`
record a trace of the layer: `path` = `kernel`, `dense` or `ring`, the
`reason`, the `window`, the `head_dim`, and `live_blocks` / `causal_blocks`,
the key blocks the kernel visits over those of the causal half (equal
without a window), with `masked_blocks`, those of the live blocks that an
edge of the visible region crosses: the kernel masks these alone (a window
of one block masks EVERY live block, and its tiles hold twice the band's
pairs); `heads` and `kv_heads` as the core sees them (a net may change the
first from layer to layer), `gate` = `none`, `elementwise` or `head`,
`rope` = `none`, `plain` or `yarn` with `rope_factor` and `rope_scale`, and
`rope_form`, how the rotary ran in words (`ROPE_FORM`, or `none`). A
head narrower than the 128 lanes of a vector register (64) goes through the
same kernels as a block of its own width: every q, k, v, o tile fills half
of each lane row, two heads are NOT paired into one row, and the `reason`
says so.

attention_param.index_heads / index_head_dim / index_topk (with causal and
num_kv_heads): a learned index picks every query's keys, ops/dsa.py has
the mathematics. Five more blobs after the head norms: W_qI (HI*DI, E),
W_kI (DI, E), W_w (HI, E), the weight and bias (DI,) of a LayerNorm on the
index key (the layer's norm_eps); rotate-half rotary at the layer's theta
on the first half of every index query and of the key; the index
heads' weights w = W_w h / sqrt(HI * DI) in float32. The indexer reads
stop_gradient of the layer's input. A SECOND top, `<name>_kl`, carries the
index's own loss L_I (a scalar; its loss_weight is the layer's second), and
with index_stats a third of weight 0 (ops/dsa.py:selection_stats: the
share of the selected keys that a window of index_topk would have caught
and the mean keys a query; kept in the layer's state, which the solver
records as `dsa.window` where it waits for a loss). The core
is ops/pallas_dsa.py's four kernels where `flash` is set and 128 divides S,
else the plain form; one `dsa.select` record a trace of the layer besides
`attn.path`: `topk`, `tiles_causal` and `tiles_visited` (the key blocks of
the causal half and those the core visits: all of them, a set is data and
skips no tile), `mean_keys` a query, and the `select` and `core` forms in
words. Its scopes, none inside another: dsa_index_proj (the three index
products, the LayerNorm, the index rotary), dsa_select (the index scores by
tiles and every query's threshold), attn_core (the masked flash passes; in
the backward also the index's gradients, which need the heads'
probabilities), dsa_kl (L_I's value).

The latent form (attention_param.kv_lora_rank set, with causal; all five
of q_lora_rank Rq, kv_lora_rank Rkv, qk_nope_head_dim Dn, qk_rope_head_dim
Dr, v_head_dim Dv) is multi-head latent attention as a TRAINING step runs
it, keys and values expanded from their latent every pass. Seven bias-free
blobs, in order:
  W_qa (Rq, E), the query latent's RMSNorm weight (Rq,), W_qb (H*(Dn+Dr),
  Rq), W_kva (Rkv+Dr, E), the key-value latent's RMSNorm weight (Rkv,),
  W_kvb (H*(Dn+Dv), Rkv), out (E, H*Dv);
c_q = RMSNorm(W_qa h), a query head [q_nope (Dn) | q_pe (Dr)] of W_qb c_q;
[c_kv (Rkv) | k_pe (Dr)] = W_kva h, c_kv = RMSNorm(c_kv) (both norms plain,
y = x / rms(x) * w with w filled with 1, the layer's norm_eps, float32);
[k_nope (Dn) | v (Dv)] a head of W_kvb c_kv; rotate-half rotary at
rope_theta on the LAST Dr dimensions of every query head and on k_pe, which
has no head axis: ONE rotary key a token, shared by all H heads; a key head
is [k_nope | k_pe]; softmax(q k^T / sqrt(Dn + Dr)) v; the out projection of
the H value heads. The absorbed form (scores against c_kv itself) is
decode's and is not here. q, k and v reach `_core` as H heads each, so the
kernels and the `attn.path` record are the other forms'; the record says
besides `form` = `latent`, `q_rank`, `kv_rank`, `nope_dim`, `rope_dim`,
`v_dim` and `shared_key_bytes`, the bytes one pass of the layer writes to
give k_pe a head axis (0 once a kernel reads it in place). The flash kernel
needs a value head as wide as the key's (Dv = Dn + Dr); else the dense
path, and the `reason` says so. Inside attn_proj_in the form opens three
scopes of its own: mla_q_latent (both query products and the norm between
them), mla_kv_latent (W_kva, the split, the norm, W_kvb), mla_k_assemble
(k_pe broadcast over the heads and joined to k_nope). It has no meaning
with window, ring, index_heads, output_gate, qk_norm, num_kv_heads or
rotary_dim, and says so by name.

Everything the layer traces lies under one scope inside its own, in every
form, so that a device trace adds up by them: attn_proj_in (the q, k, v
products with their weights' casts, the reshapes, the head norms, the
output gate's split or the per-head gate's product, the move to (B, H, S,
D)), rope, attn_core (`_core`), attn_proj_out (the move back, the gate,
the out projection).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..proto import Message
from ..graph.registry import Layer, register
from ..obs.trace import default_tracer, kernel_import
from ..parallel import context
from ..parallel.ring import ring_attention, dense_attention
from .convolution import _param_mults
from .normalization import rms_norm
from . import dsa


def yarn_range(rotary_dim, theta, original_positions, beta_fast, beta_slow):
    """(low, high): the pair indices between which YaRN's ramp runs, as the
    `transformers` library finds them — the dimension that turns `beta`
    times inside `original_positions` is rotary_dim ln(original_positions /
    (beta 2 pi)) / (2 ln theta); floor of the fast one's, ceil of the slow
    one's, inside [0, rotary_dim - 1]."""
    def turns(beta):
        return rotary_dim * math.log(original_positions
                                     / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(turns(beta_fast)), 0),
            min(math.ceil(turns(beta_slow)), rotary_dim - 1))


def rope_table(rotary_dim, theta, yarn=None):
    """The rotary's frequencies (rotary_dim / 2,) in float32: the plain
    table theta ** (-2i / rotary_dim), or with `yarn` = (factor,
    original_positions, beta_fast, beta_slow) YaRN's blend of it
    (extrapolation) and of it divided by `factor` (interpolation):
    inv_i = inter_i ramp_i + extra_i (1 - ramp_i), ramp_i = clip((i - low)
    / (high - low), 0, 1) over `yarn_range`'s pair."""
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                    / rotary_dim)
    if yarn is None:
        return inv
    factor, original_positions, beta_fast, beta_slow = yarn
    low, high = yarn_range(rotary_dim, theta, original_positions, beta_fast,
                           beta_slow)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


#: what `attn.path` says of how a layer's rotary ran (`rope_form`)
ROPE_FORM = "one pass: rotate-half as a product"


def _rotate_half_matrix(d, rotary_dim, offset, dtype):
    """R (d, d) of 0 and +-1 with x @ R = rotate-half of x on the dimensions
    [offset, offset + rotary_dim): [-x2 | x1] of their halves [x1 | x2], and
    0 on every other dimension. A trace-time constant, a numpy array: the
    trace puts nothing on the device for it."""
    half = rotary_dim // 2
    r = np.zeros((d, d), dtype)
    j = np.arange(half) + offset
    r[j + half, j] = -1
    r[j, j + half] = 1
    return r


def _rope_cos_sin(s, d, rotary_dim, theta, yarn, scale, offset):
    """cos and sin (1, s, 1, d) in float32 at positions 0..s-1, the angles of
    `rope_table` on both halves of [offset, offset + rotary_dim), `scale`
    on both; cos = 1 and sin = 0 on the dimensions that pass."""
    inv = rope_table(rotary_dim, theta, yarn)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    pad = ((0, 0), (offset, d - offset - rotary_dim))
    return (jnp.pad(cos, pad, constant_values=1.0)[None, :, None, :],
            jnp.pad(sin, pad)[None, :, None, :])


def _turn(x, table, sign):
    """x cos + rotate_half(x) (sign sin) in float32, in x's dtype, `table`
    being `rotary`'s static arguments. An x that is not bfloat16 goes
    through the MXU at HIGHEST precision, so that a float32 net on the chip
    is not rounded to bfloat16 on the way."""
    rotary_dim, theta, yarn, scale, offset = table
    d = x.shape[-1]
    cos, sin = _rope_cos_sin(x.shape[1], d, rotary_dim, theta, yarn, scale,
                             offset)
    rot = jax.lax.dot_general(
        x, _rotate_half_matrix(d, rotary_dim, offset, x.dtype),
        (((3,), (0,)), ((), ())),
        precision=None if x.dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + rot * (sign * sin)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotary(x, table):
    return _turn(x, table, 1.0)


def _rotary_fwd(x, table):
    return _turn(x, table, 1.0), None


def _rotary_bwd(table, _, g):
    # the transpose of a turn is the turn by the negative angle; the table
    # is rebuilt from the static arguments, nothing is kept for it
    return (_turn(g, table, -1.0),)


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def rotary(x, rotary_dim, theta, yarn=None, scale=1.0, offset=0):
    """Rotate-half rotary embedding of x (B, S, H, D) on the `rotary_dim`
    dimensions from `offset` on, at positions 0..S-1, in float32, at the
    frequencies of `rope_table(rotary_dim, theta, yarn)`; `scale`
    multiplies cos and sin, so the turned dimensions alone.

    Nothing slices or joins x's last axis. Written with slices
    (`concatenate([-x2, x1])`, and a second join where part of the head
    passes) the chip's compiler made THREE passes of it, forward and
    backward each: a float32 copy of x, two half-width float32 arrays
    padded to whole lane rows, then the arithmetic; for the VJP at a bf16
    q of (2, 8192, 64, 128) it counted 9.20 GB moved and 1.61 GB of
    temporaries where reading and writing q twice is 1.07 GB. Here
    rotate-half is a product with a constant D x D matrix of 0 and +-1 and
    the tables are as wide as the head (cos 1 and sin 0 where a dimension
    passes): one fusion a pass, 2.22 GB, no temporaries, the same bits.
    Under its own VJP — the same pass over the cotangent with sin negated —
    because autodiff of the product would send the float32 `g sin` through
    the MXU at default precision, a rounding the backward never had."""
    if not rotary_dim:
        return x
    return _rotary(x, (int(rotary_dim), float(theta), yarn, float(scale),
                       int(offset)))


#: the latent form's sizes, in the order of AttentionParameter's fields
LATENT_SIZES = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")


@register
class Attention(Layer):
    type_name = "Attention"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.attention_param
        self.p = p
        b, s, e = bottom_shapes[0]
        self.embed = int(e)
        self.num_heads = int(p.num_heads)
        self.head_dim = int(p.head_dim) if p.has("head_dim") \
            else self.embed // self.num_heads
        self.causal = bool(p.causal)
        self.ring = bool(p.ring)
        self.flash = bool(p.flash)
        self.inner = self.num_heads * self.head_dim
        # grouped-query form
        self.gqa = p.has("num_kv_heads")
        self.kv_heads = int(p.num_kv_heads) if self.gqa else self.num_heads
        self.qk_norm = bool(p.qk_norm)
        self.rotary_dim = int(p.rotary_dim)
        self.rope_theta = float(p.rope_theta)
        self.output_gate = bool(p.output_gate)
        self.norm_eps = float(p.norm_eps)
        self.qk_zero_centered = bool(p.qk_norm_zero_centered)
        self.window = int(p.window)
        # one scalar a head gates the output (a blob of its own)
        self.head_gate = bool(p.head_gate)
        # the rotary's table and the factor on its cos and sin
        self.rope_type = str(p.rope_type)
        self.rope_factor = float(p.rope_factor)
        self.yarn = None
        if self.rope_type == "yarn":
            self.yarn = (self.rope_factor,
                         int(p.rope_original_positions or 0),
                         float(p.rope_beta_fast), float(p.rope_beta_slow))
        self.rope_scale = float(p.rope_scale) if p.has("rope_scale") \
            else 0.1 * math.log(self.rope_factor) + 1.0 if self.yarn else 1.0
        # a learned index picks the keys (ops/dsa.py)
        self.index = p.has("index_heads") or p.has("index_topk") \
            or p.has("index_head_dim")
        # multi-head latent attention: checked first, so that it refuses
        # another form's field by the field's name
        self.latent = any(p.has(n) for n in LATENT_SIZES)
        if self.latent:
            self._init_latent(p)
            return
        if self.window and (not self.causal or self.ring):
            raise ValueError(f"{lp.name}: a window needs causal attention "
                             "and has no ring mode")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{lp.name}: num_heads {self.num_heads} is not "
                             f"a multiple of num_kv_heads {self.kv_heads}")
        if not self.gqa and (self.qk_norm or self.rotary_dim
                             or self.output_gate or self.head_gate):
            raise ValueError(f"{lp.name}: qk_norm, rotary_dim, output_gate "
                             "and head_gate need num_kv_heads (the "
                             "grouped-query form)")
        if self.head_gate and self.output_gate:
            raise ValueError(f"{lp.name}: head_gate and output_gate are two "
                             "forms of one gate; a layer takes one")
        self._check_rope(p)
        if self.gqa and self.ring:
            raise ValueError(f"{lp.name}: the grouped-query form has no "
                             "ring mode")
        if self.index:
            if not (p.has("index_heads") and p.has("index_head_dim")
                    and p.has("index_topk")):
                raise ValueError(f"{lp.name}: an index needs index_heads, "
                                 "index_head_dim and index_topk")
            self.index_heads = int(p.index_heads)
            self.index_dim = int(p.index_head_dim)
            self.index_topk = int(p.index_topk)
            self.index_stats = bool(p.index_stats)
            # the statistics live in the layer's state, as the MoE's: the
            # solver reads them where it already waits for a loss
            self.has_state = self.index_stats
            if self.index_stats:
                self.monitor = ("dsa.window", ("window_share", "mean_keys"))
            for bad, why in ((self.window, "a window"), (self.ring, "ring"),
                             (not self.causal, "no causal mask"),
                             (not self.gqa, "no num_kv_heads (the "
                              "grouped-query form)"),
                             (self.output_gate or self.head_gate,
                              "an output gate")):
                if bad:
                    raise ValueError(f"{lp.name}: an index picks the keys "
                                     f"of causal grouped-query attention; "
                                     f"it has no meaning with {why}")
            if self.index_topk < 1 or self.index_heads < 1 \
                    or self.index_dim < 1:
                raise ValueError(
                    f"{lp.name}: index_topk {self.index_topk}, index_heads "
                    f"{self.index_heads} and index_head_dim "
                    f"{self.index_dim} must be at least 1")
            if self.index_dim % 4:
                raise ValueError(
                    f"{lp.name}: index_head_dim {self.index_dim} is no "
                    "multiple of 4 (the rotary turns its first half)")

    def _check_rope(self, p):
        """The rotary table's fields, refused by name where they say
        nothing: a table needs the rotary it is the table of."""
        name = self.lp.name
        if self.rope_type not in ("plain", "yarn"):
            raise ValueError(f"{name}: rope_type {self.rope_type!r}: plain "
                             "or yarn")
        given = [f for f in ("rope_factor", "rope_original_positions",
                             "rope_beta_fast", "rope_beta_slow")
                 if p.has(f)]
        if self.yarn is None:
            if given:
                raise ValueError(f"{name}: {', '.join(given)} belong to "
                                 "rope_type yarn")
        elif not self.rotary_dim:
            raise ValueError(f"{name}: rope_type yarn needs rotary_dim (it "
                             "is the rotary's table)")
        elif self.yarn[1] < 1 or self.rope_factor < 1.0:
            raise ValueError(
                f"{name}: rope_type yarn needs rope_original_positions "
                f"(at least 1, not {self.yarn[1]}) and a rope_factor of at "
                f"least 1 (not {self.rope_factor})")
        if p.has("rope_scale") and not self.rotary_dim:
            raise ValueError(f"{name}: rope_scale multiplies the rotary's "
                             "cos and sin and needs rotary_dim")

    def _init_latent(self, p):
        lp = self.lp
        lacks = [n for n in LATENT_SIZES
                 if not p.has(n) or int(getattr(p, n)) < 1]
        if lacks:
            raise ValueError(f"{lp.name}: the latent form needs "
                             f"{', '.join(lacks)} (all five sizes, each at "
                             "least 1)")
        self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim, \
            self.v_dim = (int(getattr(p, n)) for n in LATENT_SIZES)
        for bad, why in (
                (self.window, "window"), (self.ring, "ring"),
                (self.index, "index_heads, index_head_dim or index_topk"),
                (self.output_gate, "output_gate"),
                (self.head_gate, "head_gate"), (self.qk_norm, "qk_norm"),
                (self.yarn or p.has("rope_scale"), "rope_type yarn or "
                 "rope_scale (the latent form's rotary is the plain table)"),
                (self.gqa, "num_kv_heads (every head has a key of its own "
                 "from the latent)"),
                (self.rotary_dim, "rotary_dim (qk_rope_head_dim is the "
                 "rotary part)"),
                (not self.causal, "causal false")):
            if bad:
                raise ValueError(f"{lp.name}: the latent form has no "
                                 f"meaning with {why}")
        if self.rope_dim % 2:
            raise ValueError(f"{lp.name}: qk_rope_head_dim {self.rope_dim} "
                             "is odd (the rotary turns halves)")
        if p.has("head_dim") and \
                self.head_dim != self.nope_dim + self.rope_dim:
            raise ValueError(
                f"{lp.name}: head_dim {self.head_dim} is not "
                "qk_nope_head_dim + qk_rope_head_dim = "
                f"{self.nope_dim + self.rope_dim}")
        self.head_dim = self.nope_dim + self.rope_dim
        self.inner = self.num_heads * self.v_dim

    #: its loss top is a scalar that a scan over blocks may stack
    #: (graph/compiler.py:_scan_runs)
    scan_loss_tops = True

    def param_shapes(self):
        # unlike stock Caffe layers (default constant-0), an attention with
        # zero projections is a degenerate identity-killer — default xavier
        wf = self.p.weight_filler if self.p.has("weight_filler") \
            else Message("FillerParameter", type="xavier")
        if self.latent:
            mults = _param_mults(self.lp, 7)
            one = Message("FillerParameter", type="constant", value=1.0)
            of = self.p.out_filler if self.p.has("out_filler") else wf
            h, e = self.num_heads, self.embed
            return [((self.q_rank, e), wf, *mults[0]),
                    ((self.q_rank,), one, *mults[1]),
                    ((h * self.head_dim, self.q_rank), wf, *mults[2]),
                    ((self.kv_rank + self.rope_dim, e), wf, *mults[3]),
                    ((self.kv_rank,), one, *mults[4]),
                    ((h * (self.nope_dim + self.v_dim), self.kv_rank), wf,
                     *mults[5]),
                    ((e, self.inner), of, *mults[6])]
        if self.gqa:
            mults = _param_mults(self.lp, 11)
            kv = self.kv_heads * self.head_dim
            q_out = self.inner * (2 if self.output_gate else 1)
            of = self.p.out_filler if self.p.has("out_filler") else wf
            shapes = [((q_out, self.embed), wf, *mults[0]),
                      ((kv, self.embed), wf, *mults[1]),
                      ((kv, self.embed), wf, *mults[2]),
                      ((self.embed, self.inner), of, *mults[3])]
            if self.qk_norm:
                fill = None if self.qk_zero_centered else Message(
                    "FillerParameter", type="constant", value=1.0)
                shapes += [((self.head_dim,), fill, *mults[4]),
                           ((self.head_dim,), fill, *mults[5])]
            if self.head_gate:
                shapes += [((self.num_heads, self.embed), wf,
                            *mults[len(shapes)])]
            if self.index:
                at = len(shapes)
                one = Message("FillerParameter", type="constant", value=1.0)
                hi, di = self.index_heads, self.index_dim
                shapes += [((hi * di, self.embed), wf, *mults[at]),
                           ((di, self.embed), wf, *mults[at + 1]),
                           ((hi, self.embed), wf, *mults[at + 2]),
                           ((di,), one, *mults[at + 3]),
                           ((di,), None, *mults[at + 4])]
            return shapes
        mults = _param_mults(self.lp, 4)
        return [
            ((3 * self.inner, self.embed), wf, *mults[0]),   # fused qkv
            ((3 * self.inner,), None, *mults[1]),
            ((self.embed, self.inner), wf, *mults[2]),       # out proj
            ((self.embed,), None, *mults[3]),
        ]

    def out_shapes(self):
        out = [tuple(self.bottom_shapes[0])]
        if self.index:
            out += [()] + ([(2,)] if self.index_stats else [])
        return out

    def state_shapes(self):
        return [((2,), 0.0)] if self.index and self.index_stats else []

    def apply_stateful(self, params, state, bottoms, train, rng):
        tops = self._apply_gqa(params, bottoms[0])
        return tops, [tops[2]]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]                                   # (B, S, E)
        if self.latent:
            return self._apply_latent(params, x)
        if self.gqa:
            return self._apply_gqa(params, x)
        # every operation under one of three scopes (`rope` is the fourth,
        # in the grouped-query form): a device trace adds up by them
        with jax.named_scope("attn_proj_in"):
            wqkv, bqkv = [p.astype(x.dtype) for p in params[:2]]
        with jax.named_scope("attn_proj_out"):
            wo, bo = [p.astype(x.dtype) for p in params[2:]]
        b, s, _ = x.shape
        with jax.named_scope("attn_proj_in"):
            qkv = x @ wqkv.T + bqkv                      # (B, S, 3*H*D)
            qkv = qkv.reshape(b, s, 3, self.num_heads, self.head_dim)
            q, k, v = [jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3)]
        with jax.named_scope("attn_core"):
            o = self._core(q, k, v)
        with jax.named_scope("attn_proj_out"):
            o = jnp.moveaxis(o, 2, 1).reshape(b, s, self.inner)
            return [o @ wo.T + bo]

    def _core(self, q, k, v, **said):
        """softmax(q k^T / sqrt(D) + mask) v of q (B, H, S, D) against k, v
        (B, Hkv, S, D): over the ring, through the flash kernel or dense,
        chosen from what the layer sees, and recorded as `attn.path` with
        what the caller `said` of its form besides."""
        s, hk = q.shape[2], k.shape[1]
        grp = q.shape[1] // hk
        seq_axis = context.axis("seq")
        live = half = masked = 0
        if self.ring and seq_axis is not None:
            path, reason = "ring", f"ring over the mesh axis {seq_axis}"
            o = ring_attention(q, k, v, seq_axis, causal=self.causal)
        elif self.flash and s % 128 == 0 and v.shape[-1] == q.shape[-1]:
            # here and not at the top: a process without such a layer never
            # imports pallas (1.4 s of every cell's set-up, PR 29)
            with kernel_import("sparknet_tpu.ops.pallas_attention"):
                from .pallas_attention import (band_blocks, edge_blocks,
                                               flash_attention)
            path, reason = "kernel", "flash is set and 128 divides S"
            if q.shape[-1] % 128:
                reason += (f"; a head of {q.shape[-1]} is a block of its "
                           "own width, part of a lane row of 128: heads "
                           "are not paired into a row")
            if self.causal:
                live, half = band_blocks(s, self.window)
                masked = edge_blocks(s, self.window)
            o = flash_attention(q, k, v, self.causal, None, 512, 512,
                                self.window, self.lp.name)
        else:
            path = "dense"
            if not self.flash:
                reason = "flash is not set"
            elif s % 128:
                reason = f"128 does not divide the sequence length {s}"
            else:
                reason = (f"a value head of {v.shape[-1]} beside a key head "
                          f"of {q.shape[-1]}: the kernel takes one width")
            if grp > 1:
                k, v = (jnp.repeat(a, grp, axis=1) for a in (k, v))
            o = dense_attention(q, k, v, causal=self.causal,
                                window=self.window)
        tracer = default_tracer()
        now = tracer.now_ns()
        tracer.record("attn.path", now, now, layer=self.lp.name, path=path,
                      reason=reason, window=self.window, live_blocks=live,
                      causal_blocks=half, masked_blocks=masked,
                      head_dim=int(q.shape[-1]), heads=int(q.shape[1]),
                      kv_heads=int(hk), **self._gate_and_rope(), **said)
        return o

    def _gate_and_rope(self):
        """What `attn.path` says of the output gate's form and of the
        rotary's table."""
        gate = "head" if self.head_gate else \
            "elementwise" if self.output_gate else "none"
        turns = self.rotary_dim or self.latent
        return dict(gate=gate, rope=self.rope_type if turns else "none",
                    rope_form=ROPE_FORM if turns else "none",
                    rope_factor=self.rope_factor, rope_scale=self.rope_scale)

    def _apply_latent(self, params, x):
        """The latent form (the module's docstring has the equations)."""
        with jax.named_scope("attn_proj_in"):
            wqa, wqb, wkva, wkvb = [params[i].astype(x.dtype)
                                    for i in (0, 2, 3, 5)]
        with jax.named_scope("attn_proj_out"):
            wo = params[6].astype(x.dtype)
        b, s, _ = x.shape
        h, dn, dr, dv = (self.num_heads, self.nope_dim, self.rope_dim,
                         self.v_dim)
        with jax.named_scope("attn_proj_in"):
            with jax.named_scope("mla_q_latent"):
                cq = rms_norm(x @ wqa.T, params[1], self.norm_eps, False)
                q = (cq @ wqb.T).reshape(b, s, h, dn + dr)
            with jax.named_scope("mla_kv_latent"):
                ckv = x @ wkva.T                        # [c_kv | k_pe]
                k_pe = ckv[..., self.kv_rank:][:, :, None]  # (B, S, 1, Dr)
                ckv = rms_norm(ckv[..., :self.kv_rank], params[4],
                               self.norm_eps, False)
                kv = (ckv @ wkvb.T).reshape(b, s, h, dn + dv)
        with jax.named_scope("rope"):
            q = rotary(q, dr, self.rope_theta, offset=dn)
            k_pe = rotary(k_pe, dr, self.rope_theta)
        with jax.named_scope("attn_proj_in"):
            with jax.named_scope("mla_k_assemble"):
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))],
                    -1)
            q, k, v = [jnp.moveaxis(a, 1, 2)                # (B, H, S, D)
                       for a in (q, k, kv[..., dn:])]
        with jax.named_scope("attn_core"):
            o = self._core(
                q, k, v, form="latent", q_rank=self.q_rank,
                kv_rank=self.kv_rank, nope_dim=dn, rope_dim=dr, v_dim=dv,
                shared_key_bytes=b * s * h * dr * x.dtype.itemsize)
        with jax.named_scope("attn_proj_out"):
            o = jnp.moveaxis(o, 2, 1).reshape(b, s, h * dv)
            return [o @ wo.T]

    def _apply_gqa(self, params, x):
        # the weights are cast first, as before the scopes: the traced
        # operations keep their order, so the lowered step keeps its text
        with jax.named_scope("attn_proj_in"):
            wq, wk, wv = [p.astype(x.dtype) for p in params[:3]]
        with jax.named_scope("attn_proj_out"):
            wo = params[3].astype(x.dtype)
        b, s, _ = x.shape
        h, hk, d = self.num_heads, self.kv_heads, self.head_dim
        with jax.named_scope("attn_proj_in"):
            q = x @ wq.T
            gate = head_gate = None
            if self.output_gate:
                q = q.reshape(b, s, h, 2 * d)
                q, gate = q[..., :d], q[..., d:].reshape(b, s, h * d)
            q = q.reshape(b, s, h, d)
            k = (x @ wk.T).reshape(b, s, hk, d)
            v = (x @ wv.T).reshape(b, s, hk, d)
            if self.qk_norm:
                q = rms_norm(q, params[4], self.norm_eps,
                             self.qk_zero_centered)
                k = rms_norm(k, params[5], self.norm_eps,
                             self.qk_zero_centered)
            if self.head_gate:
                # one scalar a head and a token, in float32: (B, S, H)
                with jax.named_scope("attn_gate"):
                    wg = params[6 if self.qk_norm else 4].astype(x.dtype)
                    head_gate = jax.nn.sigmoid(jnp.einsum(
                        "bse,he->bsh", x, wg,
                        preferred_element_type=jnp.float32))
        with jax.named_scope("rope"):
            q = rotary(q, self.rotary_dim, self.rope_theta, self.yarn,
                       self.rope_scale)
            k = rotary(k, self.rotary_dim, self.rope_theta, self.yarn,
                       self.rope_scale)
        with jax.named_scope("attn_proj_in"):
            q, k, v = [jnp.moveaxis(a, 1, 2) for a in (q, k, v)]  # (B,H,S,D)
        extra = []
        if self.index:
            o, extra = self._core_index(q, k, v, x, params[-5:])
        else:
            with jax.named_scope("attn_core"):
                o = self._core(q, k, v)
        with jax.named_scope("attn_proj_out"):
            o = jnp.moveaxis(o, 2, 1)
            if head_gate is not None:
                with jax.named_scope("attn_gate"):
                    o = o * head_gate.astype(o.dtype)[..., None]
            o = o.reshape(b, s, h * d)
            if gate is not None:
                o = o * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(o.dtype)
            return [o @ wo.T] + extra

    def _core_index(self, q, k, v, x, blobs):
        """-> (o (B, H, S, D), [L_I] or [L_I, stats]): the index's three
        products from stop_gradient of the layer's input, then the core
        over the keys it picks."""
        b, s, _ = x.shape
        hi, di, topk = self.index_heads, self.index_dim, self.index_topk
        with jax.named_scope("dsa_index_proj"):
            xi = jax.lax.stop_gradient(x)
            wqi, wki, ww = [p.astype(x.dtype) for p in blobs[:3]]
            qi = (xi @ wqi.T).reshape(b, s, hi, di)
            kf = (xi @ wki.T).astype(jnp.float32)
            kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
            kf = kf * jax.lax.rsqrt(jnp.mean(kf * kf, axis=-1, keepdims=True)
                                    + self.norm_eps)
            ki = (kf * blobs[3].astype(jnp.float32)
                  + blobs[4].astype(jnp.float32)).astype(x.dtype)
            w = jnp.einsum("bse,je->bjs", xi, ww,
                           preferred_element_type=jnp.float32) \
                * (hi * di) ** -0.5
            qi = rotary(qi, di // 2, self.rope_theta)
            ki = rotary(ki[:, :, None, :], di // 2,
                        self.rope_theta)[:, :, 0, :]
            qi = jnp.moveaxis(qi, 1, 2)                   # (B, HI, S, DI)
        tracer = default_tracer()
        if self.flash and s % 128 == 0:
            # here and not at the top: see `_core`
            with kernel_import("sparknet_tpu.ops.pallas_dsa"):
                from .pallas_dsa import (BITS_A_PASS, SELECT_PASSES,
                                         causal_tiles, sparse_attention)
            path, reason = "kernel", "flash is set and 128 divides S"
            tiles, passes, bits = causal_tiles(s), SELECT_PASSES, BITS_A_PASS
            o, kl = sparse_attention(q, k, v, qi, ki, w, topk, self.lp.name)
        else:
            path, tiles, passes, bits = "dense", 0, 0, 0
            reason = "flash is not set" if not self.flash \
                else f"128 does not divide the sequence length {s}"
            with jax.named_scope("attn_core"):
                o, kl = dsa.sparse_attention_plain(q, k, v, qi, ki, w, topk)
        extra = [kl.astype(jnp.float32)]
        if self.index_stats:
            with jax.named_scope("dsa_stats"):
                extra.append(dsa.selection_stats(qi, ki, w, topk))
        now = tracer.now_ns()
        core = "masked tiles of the causal half, the index tile recomputed " \
            "in every kernel" if path == "kernel" else "whole score matrices"
        select = "a threshold a query, the topk-th largest by counting the " \
            "scores' 32 bit planes, 32 keys a word and a bit a walk" \
            if path == "kernel" else "jax.lax.top_k"
        backward = "one kernel: dq in VMEM, dk dv dkI through HBM a tile" \
            if path == "kernel" else "XLA's transpose of the whole matrices"
        tracer.record("attn.path", now, now, layer=self.lp.name, path=path,
                      reason=reason, window=0, live_blocks=tiles,
                      causal_blocks=tiles, masked_blocks=tiles,
                      head_dim=int(q.shape[-1]), heads=int(q.shape[1]),
                      kv_heads=int(k.shape[1]), **self._gate_and_rope(),
                      core=core, select=select, backward=backward,
                      backward_kernels=int(path == "kernel"))
        full = min(s, topk)     # queries up to here take every key
        tracer.record("dsa.select", now, now, layer=self.lp.name, topk=topk,
                      tiles_causal=tiles, tiles_visited=tiles,
                      mean_keys=(full * (full + 1) / 2 + (s - full) * full)
                      / s, select=select, core=core, select_passes=passes,
                      bits_a_pass=bits)
        return o, extra
