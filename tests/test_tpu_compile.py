"""The main path's pallas kernels, compiled for a DESCRIBED v5e at real
widths — no chip attached, nothing runs (on-chip-measurement guide §2).

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: a slice off the tiling, too much VMEM, a kernel that cannot be
partitioned. These few compiles (~1-2 s each) guard that at no chip time.

Rules this file keeps, because breaking them turns the whole suite into 0
passes under the driver's six xdist workers: the topology is described
inside a module-scoped, non-autouse fixture (never at import, in a skipif,
in parametrize or in conftest.py); everything built from it is built in a
fixture or a test; no child process compiles; all such tests live in this
ONE file, so one worker loads libtpu and keeps it.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparknet_tpu.ops import moe as moe_ops
from sparknet_tpu.ops import pallas_attention as pa
from sparknet_tpu.ops import pallas_deltanet as pd
from sparknet_tpu.ops import pallas_dsa
from sparknet_tpu.ops import pallas_epilogue as pe
from sparknet_tpu.ops import pallas_lrn as plrn
from sparknet_tpu.ops import pallas_moe as pm

LRN = dict(size=5, alpha=1e-4, beta=0.75, k=1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes_dtypes, kernels=()):
    """Compile for the described chip; the program holds a TPU kernel, and
    each of `kernels` as an instruction under the name its pallas_call
    gives (what a device trace then calls it)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_dtypes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert f"%{name}" in text and f"/{name}/pallas_call" in text, name
    return compiled


# flash attention at the d1024 LM's shape (8 heads -> D=128) and at the
# half-lane D=64 variant, S=4096, default 512x512 blocks
@pytest.mark.parametrize("head_dim", [128, 64])
def test_flash_forward_compiles(one_chip, head_dim):
    shp = ((4, 8, 4096, head_dim), jnp.bfloat16)
    scale = head_dim ** -0.5
    _compile(lambda q, k, v: pa._flash_forward(
        q, k, v, True, scale, 512, 512, False), one_chip, shp, shp, shp,
        kernels=["flash_fwd"])


@pytest.mark.parametrize("head_dim", [128, 64])
def test_flash_backward_compiles(one_chip, head_dim):
    shp = ((4, 8, 4096, head_dim), jnp.bfloat16)
    scale = head_dim ** -0.5
    # lse exactly as the forward emits it, whatever its layout
    lse = jax.eval_shape(
        lambda q: pa._flash_forward(q, q, q, True, scale, 512, 512, True),
        jax.ShapeDtypeStruct(*shp))[1]
    _compile(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, scale, 512, 512, False),
        one_chip, shp, shp, shp, shp, (lse.shape, lse.dtype), shp,
        kernels=["flash_dq", "flash_dkv"])


# the grouped-query pass of the hybrid LMs at S=8192, read in place; the
# dK/dV kernel sums a group: (batch, query heads, key-value heads, head)
GQA_SHAPES = {
    # 16 query heads over 2 key-value heads of 256
    "head256": (2, 16, 2, 256),
    # 32 over 8 of 64: every tile half a lane row wide
    "lfm2_head64": (3, 32, 8, 64),
    # 32 over 2 of 128: sixteen query heads a key-value head
    "nemotron_g16": (2, 32, 2, 128),
    # the latent attention's: 20 over 20 of 256, every head a key of its own
    "glm_mla": (1, 20, 20, 256)}


@pytest.mark.parametrize("shape", list(GQA_SHAPES))
def test_flash_gqa_compiles(one_chip, shape):
    b, h, hkv, d = GQA_SHAPES[shape]
    q = ((b, h, 8192, d), jnp.bfloat16)
    kv = ((b, hkv, 8192, d), jnp.bfloat16)
    scale = d ** -0.5
    _compile(lambda q, k, v: pa._flash_forward(
        q, k, v, True, scale, 512, 512, False), one_chip, q, kv, kv,
        kernels=["flash_fwd"])
    lse = jax.eval_shape(
        lambda q, k: pa._flash_forward(q, k, k, True, scale, 512, 512, True),
        jax.ShapeDtypeStruct(*q), jax.ShapeDtypeStruct(*kv))[1]
    _compile(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, scale, 512, 512, False),
        one_chip, q, kv, kv, q, (lse.shape, lse.dtype), q,
        kernels=["flash_dq", "flash_dkv"])


# the gated delta rule's kernel pair at the hybrid LM's shape: 2 x 8,192
# tokens, 32 value heads over 16 key heads of 128, chunks of 64, q, k and
# v bfloat16 as the conv leaves them
GDN_B, GDN_T, GDN_HK, GDN_HV, GDN_D, GDN_CHUNK = 2, 8192, 16, 32, 128, 64


@pytest.mark.parametrize("which", ["forward", "forward_for_backward",
                                   "backward"])
def test_gdn_chunk_kernels_compile(one_chip, which):
    n, r = GDN_T // GDN_CHUNK, GDN_HV // GDN_HK
    qk = ((GDN_B, GDN_T, GDN_HK * GDN_D), jnp.bfloat16)
    v = ((GDN_B, GDN_T, GDN_HV * GDN_D), jnp.bfloat16)
    rows = ((GDN_B, GDN_HK, n, r * GDN_CHUNK), jnp.float32)
    state = ((GDN_B, GDN_HV, GDN_D, GDN_D), jnp.float32)
    if which != "backward":     # differentiated (with residuals), or not
        _compile(lambda *a: pd._forward(
            *a, GDN_CHUNK, pd.GROUP, False,
            which == "forward_for_backward"), one_chip,
            qk, qk, v, rows, rows, state, kernels=["gdn_chunk_fwd"])
        return
    residuals = jax.eval_shape(
        lambda *a: pd._forward(*a, GDN_CHUNK, pd.GROUP, False)[2:],
        *[jax.ShapeDtypeStruct(*a) for a in (qk, qk, v, rows, rows, state)])
    do = (v[0], jnp.float32)
    _compile(lambda *a: pd._backward(*a, GDN_CHUNK, pd.GROUP, False),
             one_chip, qk, qk, v, rows, rows,
             *[(x.shape, x.dtype) for x in residuals], do, state,
             kernels=["gdn_chunk_bwd"])


# the held experts' grouped products at the LM cells' shapes, bfloat16 in,
# float32 out, the row tile `fit_tile` gives the layer: (tokens, top_k,
# held, experts, embed, hidden, activation, the tile, the window's rows, the
# most temporaries)
MOE_SHAPES = {
    # 2 x 8,192 tokens, top-10 of 512 with 16 held, 2048 x 512, SiLU: 320
    # rows an expert, tiles of 128
    "qwen3_next": (16384, 10, 16, 512, 2048, 512, "silu", 128, 6400,
                   1 << 30),
    # 2 x 16,384 tokens, top-6 of 64 with 8 held, 2560 x 768, ReLU: an even
    # routing's 24,576 pairs and a quarter more in one window, 3,072 rows
    # an expert in tiles of 512 (PR 47: blocks of (512, 1280, 768))
    "smallthinker": (32768, 6, 8, 64, 2560, 768, "relu", 512, 30720,
                     3 << 30),
    # 3 x 8,192 tokens, top-4 of 32 with 8 held, 2048 x 1792, SiLU: the
    # same 24,576 pairs, the same window and tile (blocks of (512, 1024,
    # 896), a float32 output block of 1.8 MB)
    "lfm2_moe": (24576, 4, 8, 32, 2048, 1792, "silu", 512, 30720, 3 << 30),
    # 1 x 32,768 tokens, top-8 of 128 with 16 held, 2048 x 768, SiLU: 32,768
    # even pairs and a quarter more in 160 tiles of 256 (2,048 rows an
    # expert: 512 tied in the cell's step, and the tie keeps 256)
    "keye_vl2": (32768, 8, 16, 128, 2048, 768, "silu", 256, 40960, 3 << 30),
    # 2 x 8,192 tokens, top-6 of 128 with 8 held, 2688 x 1856 PADDED TO
    # 1,920 as the layer pads its cast copies, TWO matrices, relu^2: an
    # even routing's 6,144 pairs and a quarter more in one window, 768 rows
    # an expert in tiles of 256; at these widths a tile of 512 would NOT
    # fit VMEM (a float32 output block of (512, 2688) twice and its
    # accumulator: PERF.md section 7), and 768 rows an expert do not ask
    # for it
    "nemotron_h": (16384, 6, 8, 128, 2688, 1920, "relu2", 256, 7680,
                   1 << 30),
    # 1 x 8,192 tokens, top-4 of 64 with 8 held, 2048 x 1536, SiLU: 512 rows
    # an expert in tiles of 256, the smallest window any cell has
    "glm4_moe_lite": (8192, 4, 8, 64, 2048, 1536, "silu", 256, 5120,
                      1 << 30)}


# the same at shapes whose routing fits a larger tile than the kernels'
# blocks do at the layer's widths and compute type (`moe_ops.kernel_tile`):
# what the default path of a zoo net traces beyond its cell's batch, or in
# float32; and a shape at which the parent's segment add did not compile. As
# above, with the compute type and the routing's tile before the layer's.
MOE_CAPPED = {
    # Nemotron's net at batch 8: 3,072 rows an expert take 512 by the
    # routing, whose float32 output block (512, 2688) misses VMEM: 256
    "nemotron_h_batch_8": (65536, 6, 8, 128, 2688, 1920, "relu2",
                           jnp.bfloat16, 512, 256, 30720, 4 << 30),
    # Qwen3-Next's at batch 8: 1,280 rows an expert in tiles of 256, a
    # window of 50 x 512 rows under a segment of 10, which the segment add
    # takes in blocks of 256 (512 and their shifted copies miss VMEM: the
    # parent did not compile here at any tile)
    "qwen3_next_batch_8": (65536, 10, 16, 512, 2048, 512, "silu",
                           jnp.bfloat16, 256, 256, 25600, 3 << 30),
    # float32 operands: the LFM2 cell's 512 comes down to 256, Nemotron's
    # own cell's 256 to the 128 it had before a tile was fitted
    "lfm2_moe_float32": (24576, 4, 8, 32, 2048, 1792, "silu", jnp.float32,
                         512, 256, 30720, 4 << 30),
    "nemotron_h_float32": (16384, 6, 8, 128, 2688, 1920, "relu2",
                           jnp.float32, 256, 128, 7680, 2 << 30)}


def _compile_held_experts(one_chip, which, case, dtype):
    n, k, held, experts_, e, f, act, tile, rows, most = case
    fitted = moe_ops.fit_tile(n, k, held, experts_)
    assert moe_ops.kernel_tile(fitted, e, f, jnp.dtype(dtype).itemsize) \
        == tile
    window = moe_ops.window_rows(n, k, held, experts_, tile)
    assert window == rows
    x = ((n, e), dtype)
    pairs = ((n * k,), jnp.float32)
    experts = ((n * k,), jnp.int32)
    up = ((held, f, e), dtype)
    down = ((held, e, f), dtype)

    def run(x, pw, pair_expert, wg, wu, wd):
        plan = moe_ops.plan_windows(pair_expert, held, window)
        # an expert of two matrices has no gate matrix
        return moe_ops.held_experts(x, pw, plan,
                                    None if act == "relu2" else wg, wu, wd,
                                    tile, k, window, True, act)
    if which == "forward":
        compiled = _compile(run, one_chip, x, pairs, experts, up, up, down,
                            kernels=["moe_gmm_fwd", "moe_segment_add"])
    else:
        def grads(x, pw, pair_expert, wg, wu, wd, dy):
            return jax.vjp(lambda x, pw, wg, wu, wd: run(
                x, pw, pair_expert, wg, wu, wd), x, pw, wg, wu, wd)[1](dy)
        compiled = _compile(
            grads, one_chip, x, pairs, experts, up, up, down,
            ((n, e), jnp.float32),
            kernels=["moe_gmm_fwd", "moe_gmm_bwd", "moe_gmm_dw",
                     "moe_segment_add"])
    # a window's buffers, not tokens x top_k rows of anything
    assert compiled.memory_analysis().temp_size_in_bytes < most
    return compiled, fitted


@pytest.mark.parametrize("which", ["forward", "backward"])
@pytest.mark.parametrize("shape", list(MOE_SHAPES))
def test_moe_grouped_products_compile(one_chip, monkeypatch, shape, which):
    # the kernels interpret themselves wherever the backend is the CPU,
    # as it is here: steer that in the test, the program has no option
    monkeypatch.setattr(pm, "_should_interpret", lambda: False)
    case = MOE_SHAPES[shape]
    compiled, fitted = _compile_held_experts(one_chip, which, case,
                                             jnp.bfloat16)
    # at its cell's shape no family's widths hold the routing's tile down
    assert fitted == case[7]
    # the combine gathers (PR 39): no scatter of float32 rows of the
    # embedding width is left, forward or backward (what is left scatters
    # scalars: d pair_weight, the kernels' group metadata)
    wide = re.findall(rf"f32\[\d+,{case[4]}\]\S* scatter\(",
                      compiled.as_text())
    assert not wide, wide


@pytest.mark.parametrize("which", ["forward", "backward"])
@pytest.mark.parametrize("shape", list(MOE_CAPPED))
def test_moe_grouped_products_compile_at_the_tile_their_blocks_fit(
        one_chip, monkeypatch, shape, which):
    monkeypatch.setattr(pm, "_should_interpret", lambda: False)
    *case, dtype, by_routing, tile, rows, most = MOE_CAPPED[shape]
    _, fitted = _compile_held_experts(
        one_chip, which, (*case, tile, rows, most), dtype)
    assert fitted == by_routing


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("shape", list(MOE_SHAPES))
def test_moe_segment_add_compiles(one_chip, monkeypatch, shape, weighted):
    """The combine's one dense pass alone at the cells' windows: a block
    and a halo that fit the tiling at a segment of 6, 4 and 10 rows (the
    last reaches 9 rows into the next block: a halo of 16), at widths of
    2,048, 2,560 and 2,688 (21 lane rows: blocks of 896 lanes)."""
    monkeypatch.setattr(pm, "_should_interpret", lambda: False)
    n, k, held, _, e, _, _, _, window, _ = MOE_SHAPES[shape]
    segment = min(k, held)
    block = pm.segment_block(window, segment)
    assert block == {6400: 256, 30720: 512, 7680: 512, 40960: 512,
                     5120: 512}[window]
    rows = ((window, e), jnp.float32)
    column = ((window,), jnp.float32)
    tok = ((window,), jnp.int32)
    if weighted:
        compiled = _compile(
            lambda z, wt, tok: pm.segment_add(z, wt, tok, n, segment, block),
            one_chip, rows, column, tok, kernels=["moe_segment_add"])
    else:
        compiled = _compile(
            lambda z, tok: pm.segment_add(z, None, tok, n, segment, block),
            one_chip, rows, tok, kernels=["moe_segment_add"])
    # one read and one write of the window: nothing is copied round the call
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_gated_delta_net_backward_holds_less_than_the_scans_did(
        one_chip, monkeypatch):
    """One layer's forward and backward at the cell's shape through the
    kernel pair: the scans of checkpointed groups needed 3.2 GB of
    temporaries here (PR 28, this compile), and the cell's memory stands
    at 15.96 of 16 GB: the kernels' stored states must not need more."""
    from sparknet_tpu.graph.registry import get as get_layer
    from sparknet_tpu.models import dsl
    # the described chip is not the backend: the kernels, not interpret
    monkeypatch.setattr(pd, "_should_interpret", lambda: False)
    embed = 2048
    lp = dsl.GatedDeltaNetLayer("mixer", ["x"], GDN_HK, GDN_HV, GDN_D,
                                GDN_D, conv_kernel=4)
    x = ((GDN_B, GDN_T, embed), jnp.bfloat16)
    impl = get_layer(lp.type)(lp, [x[0]], 0)
    blobs = [(s[0], jnp.float32) for s in impl.param_shapes()]

    def grads(x, cot, *blobs):
        def loss(x, blobs):
            y = impl.apply(list(blobs), [x], True, None)[0]
            return jnp.sum(y.astype(jnp.float32) * cot)
        return jax.grad(loss, (0, 1))(x, blobs)

    compiled = _compile(grads, one_chip, x, (x[0], jnp.float32), *blobs,
                        kernels=["gdn_chunk_fwd", "gdn_chunk_bwd"])
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2e9


# the Mamba-2 scan's kernel pair at the Nemotron cell's shape: 2 x 8,192
# tokens, 64 heads of 64 over 8 groups, state and chunks of 128, x, B and
# C bfloat16 as the conv leaves them
SSD_B, SSD_S, SSD_H, SSD_P, SSD_G, SSD_N, SSD_Q = 2, 8192, 64, 64, 8, 128, 128


@pytest.mark.parametrize("which", ["forward", "forward_for_backward",
                                   "backward"])
def test_ssd_chunk_kernels_compile(one_chip, which):
    from sparknet_tpu.ops import pallas_ssd as ps
    r, nc = SSD_H // SSD_G, SSD_S // SSD_Q
    x = ((SSD_B, SSD_S, SSD_H * SSD_P), jnp.bfloat16)
    bc = ((SSD_B, SSD_S, SSD_G * SSD_N), jnp.bfloat16)
    rows = ((SSD_B, SSD_G, nc, ps._rows(r), SSD_Q), jnp.float32)
    if which != "backward":     # differentiated (with the states), or not
        _compile(lambda *a: ps._forward(
            *a, r, SSD_P, False, which == "forward_for_backward"), one_chip,
            x, bc, bc, rows, rows, kernels=["ssd_chunk_fwd"])
        return
    last = ((SSD_B, SSD_G, SSD_N, r * SSD_P), jnp.float32)
    starts = ((SSD_B, SSD_G, nc, SSD_N, r * SSD_P), jnp.float32)
    _compile(lambda *a: ps._backward(*a, r, SSD_P, False), one_chip,
             x, bc, bc, rows, rows, starts, last, (x[0], jnp.float32), last,
             kernels=["ssd_chunk_bwd"])


def test_mamba2_layer_compiles_to_the_kernel_pair(one_chip, monkeypatch):
    """One Mamba-2 mixer's forward and backward at the Nemotron cell's
    shape (bfloat16 in) through the kernel pair, both under `ssm_scan`.
    XLA's chunked form held one row's decay masks (64 heads x 64 chunks x
    128 x 128, 268 MB in float32) and chunk states beside the projections'
    and the gate's float32 arrays: 3.1 GB of temporaries here (PR 42, this
    compile, bound 3.6e9). The kernels' masks never leave VMEM; what lies
    in HBM for them is y and every chunk's first state (268 MB each), and
    with the group norm's sums as products (no moved copy of y) the
    temporaries read 2.30 GB when this test was written."""
    from sparknet_tpu.graph.registry import get as get_layer
    from sparknet_tpu.models import dsl
    from sparknet_tpu.ops import pallas_ssd as ps
    # the described chip is not the backend: the kernels, not interpret
    monkeypatch.setattr(ps, "_should_interpret", lambda: False)
    embed = 2688
    lp = dsl.Mamba2Layer("mixer", ["x"], SSD_H, SSD_P, SSD_N, SSD_G,
                         conv_kernel=4, chunk=SSD_Q, norm_eps=1e-5)
    x = ((SSD_B, SSD_S, embed), jnp.bfloat16)
    impl = get_layer(lp.type)(lp, [x[0]], 0)
    blobs = [(s[0], jnp.float32) for s in impl.param_shapes()]

    def grads(x, cot, *blobs):
        def loss(x, blobs):
            y = impl.apply(list(blobs), [x], True, None)[0]
            return jnp.sum(y.astype(jnp.float32) * cot)
        return jax.grad(loss, (0, 1))(x, blobs)

    compiled = _compile(grads, one_chip, x, (x[0], jnp.float32), *blobs,
                        kernels=["ssd_chunk_fwd", "ssd_chunk_bwd"])
    text = compiled.as_text()
    for kernel in ("ssd_chunk_fwd", "ssd_chunk_bwd"):
        assert re.search(rf"ssm_scan[^\"]*/{kernel}/pallas_call", text), kernel
    assert not re.search(r"ssm_scan[^\"]*/while", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


def test_latent_attention_layer_compiles_to_the_flash_kernels(one_chip,
                                                              monkeypatch):
    """One latent attention's forward and backward at the GLM cell's shape
    (8,192 tokens of 2,048 in bfloat16, 20 heads of 192 + 64 | 256, ranks
    768 and 512): the three flash kernels under `attn_core`, the key's
    assembly under `mla_k_assemble` inside `attn_proj_in`, and temporaries
    of 0.70 GB when this test was written (q, k, v and their gradients at
    20 heads of 256 are 84 MB each)."""
    from sparknet_tpu.graph.registry import get as get_layer
    from sparknet_tpu.models import dsl
    monkeypatch.setattr(pa, "_should_interpret", lambda: False)
    lp = dsl.AttentionLayer("attn", ["x"], 20, causal=True, flash=True,
                            q_lora_rank=768, kv_lora_rank=512,
                            qk_nope_head_dim=192, qk_rope_head_dim=64,
                            v_head_dim=256, rope_theta=1e6, norm_eps=1e-5)
    x = ((1, 8192, 2048), jnp.bfloat16)
    impl = get_layer(lp.type)(lp, [x[0]], 0)
    assert sum(functools.reduce(lambda a, b: a * b, s[0])
               for s in impl.param_shapes()) == 21_759_232
    blobs = [(s[0], jnp.float32) for s in impl.param_shapes()]

    def grads(x, cot, *blobs):
        def loss(x, blobs):
            y = impl.apply(list(blobs), [x], True, None)[0]
            return jnp.sum(y.astype(jnp.float32) * cot)
        return jax.grad(loss, (0, 1))(x, blobs)

    compiled = _compile(grads, one_chip, x, (x[0], jnp.float32), *blobs,
                        kernels=["flash_fwd", "flash_dq", "flash_dkv"])
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(rf"attn_core[^\"]*/{kernel}", text), kernel
    assert re.search(r"attn_proj_in\)+/mla_k_assemble/", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.95e9


# the two kinds of attention layer of the window/full hybrid LM with two
# head counts, at its cell's shape (2 x 8,192 tokens of 2,048 in bfloat16, 8
# key-value heads of 128): (query heads, window, rotary dims, the rotary
# table's fields, the kernels, the layer's temporaries: a tenth above what
# the sandbox's compile reads, 0.917 and 1.188 GB — 1.819 and 2.304 GB
# while the rotary was three passes with float32 copies between them)
GATED_GQA = {
    "full_48_yarn": (48, 0, 64, dict(
        rope_type="yarn", rope_factor=64, rope_original_positions=4096,
        rope_beta_fast=64, rope_beta_slow=1, rope_scale=1.4158883083359672),
        ("flash_fwd", "flash_dq", "flash_dkv"), 1.01e9),
    "window512_64": (64, 512, 128, {},
                     ("flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv"),
                     1.31e9)}


@pytest.mark.parametrize("kind", list(GATED_GQA))
def test_head_gated_attention_layer_compiles_to_the_flash_kernels(
        one_chip, monkeypatch, kind):
    """One layer's forward and backward: a window EQUAL to the kernels'
    block of 512 compiles at eight query heads a key-value head and the
    causal form at six, the per-head gate's product and multiply lie under
    `attn_gate` inside `attn_proj_in` and `attn_proj_out`, and the rotary
    under `rope` in ONE pass over q: no half-width float32 copy of the
    heads (`rotary`'s slices made two a call) and half the temporaries."""
    from sparknet_tpu.graph.registry import get as get_layer
    from sparknet_tpu.models import dsl
    monkeypatch.setattr(pa, "_should_interpret", lambda: False)
    heads, window, rotary_dim, rope, kernels, temp_limit = GATED_GQA[kind]
    lp = dsl.AttentionLayer(
        "attn", ["x"], heads, head_dim=128, causal=True, flash=True,
        num_kv_heads=8, rotary_dim=rotary_dim,
        rope_theta=1e4 if window else 5e5, rope=rope, gate="head",
        window=window)
    x = ((2, 8192, 2048), jnp.bfloat16)
    impl = get_layer(lp.type)(lp, [x[0]], 0)
    assert [s[0] for s in impl.param_shapes()] == [
        (heads * 128, 2048), (1024, 2048), (1024, 2048),
        (2048, heads * 128), (heads, 2048)]
    blobs = [(s[0], jnp.float32) for s in impl.param_shapes()]

    def grads(x, cot, *blobs):
        def loss(x, blobs):
            y = impl.apply(list(blobs), [x], True, None)[0]
            return jnp.sum(y.astype(jnp.float32) * cot)
        return jax.grad(loss, (0, 1))(x, blobs)

    compiled = _compile(grads, one_chip, x, (x[0], jnp.float32), *blobs,
                        kernels=kernels)
    text = compiled.as_text()
    for kernel in kernels:
        assert re.search(rf"attn_core[^\"]*/{kernel}", text), kernel
    assert re.search(r"attn_proj_in\)*/attn_gate/", text)
    assert re.search(r"attn_proj_out\)*/attn_gate/", text)
    assert re.search(r"op_name=\"[^\"]*[(/]rope\)*/", text)
    assert not re.search(rf"f32\[2,8192,{heads},64\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


def test_rotary_and_its_vjp_are_one_pass_each(one_chip):
    """`rotary` alone at the Laguna window layers' q with the move to (B,
    H, S, D) that follows it in the layer, forward and VJP: no float32
    array of q's size and no half-width one among the temporaries (1.61 GB
    of them while rotate-half was a slice and a join; 4 MB of tables now),
    and rotate-half is the product."""
    from sparknet_tpu.ops.attention import rotary

    def both(x, cot):
        def turned(x):
            with jax.named_scope("rope"):
                return jnp.moveaxis(rotary(x, 128, 1e4), 1, 2)
        y, vjp = jax.vjp(turned, x)
        return y, vjp(cot)[0]

    compiled = jax.jit(both).lower(*[
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in ((2, 8192, 64, 128), (2, 64, 8192, 128))]).compile()
    text = compiled.as_text()
    assert len(re.findall(r"rope\)*/dot_general", text)) >= 2
    entry = text[text.index("ENTRY"):]
    assert not re.search(r"f32\[2,8192,64,(128|64)\]", entry)
    assert compiled.memory_analysis().temp_size_in_bytes < 50e6


# LRN where CaffeNet runs it (after each pool) and at GoogLeNet's conv2
# site, the one 3-op conv+relu+lrn site SPARKNET_EPILOGUE=auto fuses
CAFFENET_NORM1 = (256, 96, 27, 27)
CAFFENET_NORM2 = (256, 256, 13, 13)
GOOGLENET_CONV2 = (256, 192, 56, 56)


@pytest.mark.parametrize("shape", [CAFFENET_NORM1, CAFFENET_NORM2,
                                   GOOGLENET_CONV2])
def test_lrn_forward_compiles(one_chip, shape):
    _compile(lambda x: plrn._call_fwd(x, LRN["size"], LRN["alpha"],
                                      LRN["beta"], LRN["k"], False),
             one_chip, (shape, jnp.bfloat16), kernels=["lrn_fwd"])


@pytest.mark.parametrize("shape", [CAFFENET_NORM1, CAFFENET_NORM2,
                                   GOOGLENET_CONV2])
def test_lrn_backward_compiles(one_chip, shape):
    _compile(lambda x, g: plrn._call_bwd(x, g, LRN["size"], LRN["alpha"],
                                         LRN["beta"], LRN["k"], False),
             one_chip, (shape, jnp.bfloat16), (shape, jnp.bfloat16),
             kernels=["lrn_bwd"])


@pytest.mark.parametrize("shape", [CAFFENET_NORM1, CAFFENET_NORM2,
                                   GOOGLENET_CONV2])
def test_bias_relu_lrn_compiles(one_chip, shape):
    kernel = functools.partial(pe._bias_relu_lrn_kernel, LRN["size"],
                               LRN["alpha"], LRN["beta"], LRN["k"])
    _compile(lambda x, b: pe._call_epilogue(kernel, "bias_relu_lrn", x, b,
                                            False),
             one_chip, (shape, jnp.bfloat16), ((shape[1],), jnp.float32),
             kernels=["bias_relu_lrn"])


def test_bias_relu_compiles(one_chip):
    _compile(lambda x, b: pe._call_epilogue(pe._bias_relu_kernel,
                                            "bias_relu", x, b, False),
             one_chip, (GOOGLENET_CONV2, jnp.bfloat16),
             ((GOOGLENET_CONV2[1],), jnp.float32), kernels=["bias_relu"])


# libtpu 0.0.34 refuses CaffeNet's forward at batch 1-7 (the serve tier's
# small buckets) unless ops/lrn.py keeps XLA's space-to-batch pass out of
# the LRN window sum: found by chip_smoke.py's serve phase, PR 22
@pytest.mark.parametrize("batch", [1, 4])
def test_caffenet_forward_small_batch_compiles(one_chip, batch):
    from sparknet_tpu.graph.compiler import CompiledNet, TEST
    from sparknet_tpu.models import zoo
    from sparknet_tpu.serve.engine import deploy_net_param
    shape = (batch, 3, 227, 227)
    net = CompiledNet(deploy_net_param(
        zoo.caffenet(batch_size=batch, num_classes=1000)), TEST,
        feed_shapes={"data": shape})
    params, state = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def forward(p, s, x):
        return net.apply(p, s, {"data": x}, train=False)[0]["fc8"]

    jax.jit(forward).lower(
        on_chip(params), on_chip(state),
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)).compile()


def test_kernel_bytes_ignore_the_call_stack_and_op_names_keep_the_scope(
        one_chip):
    """What utils/compile_cache.py sets before the first compile on a chip:
    a kernel's serialized MLIR (part of the step's cache key) is the same
    from any entry point, and the compiled op_name still carries the
    named_scope path."""
    import re
    from sparknet_tpu.utils.compile_cache import LOCATION_FRAMES
    args = [jax.ShapeDtypeStruct((8, 192, 56, 56), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((192,), jnp.float32, sharding=one_chip)]

    def loss(x, b):
        with jax.named_scope("conv2"):
            y = plrn._call_fwd(x + b[None, :, None, None].astype(x.dtype),
                               LRN["size"], LRN["alpha"], LRN["beta"],
                               LRN["k"], False)
        return y.astype(jnp.float32).sum()

    def shallow():
        return jax.jit(loss).lower(*args)

    def deep():
        return (lambda: (lambda: jax.jit(loss).lower(*args))())()

    old = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", LOCATION_FRAMES)
    try:
        a, b = shallow(), deep()
        kernels = [re.findall(r'backend_config = "([^"]+)"', low.as_text())
                   for low in (a, b)]
        assert kernels[0] and kernels[0] == kernels[1]
        assert 'op_name="jit(loss)/conv2/lrn_fwd/pallas_call"' in \
            a.compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", old)


# the host-fed cell's input transform (benchmark/feeds/hostfed_u8.py:
# device_fn() + the feed's bf16 cast) at its real shape, and the no-crop
# CIFAR shape: crop and mirror are selector matmuls, so the program holds
# no loop over the batch, no lane reverse and no float32 copy of the record
@pytest.mark.parametrize("record,crop", [((3, 256, 256), 227),
                                         ((3, 32, 32), 0)])
def test_input_transform_is_two_matmuls_and_no_loop(one_chip, record, crop):
    from sparknet_tpu.data.device_transform import build_device_transformer
    from sparknet_tpu.proto import Message
    n = 1536
    tp = Message("TransformationParameter", mirror=True)
    if crop:
        tp.crop_size = crop
    tp.mean_value.extend([104.0, 117.0, 123.0])
    devt = build_device_transformer(tp, phase=0)
    inner = devt.device_fn()

    def transform(b):
        b = inner(b)
        b["data"] = b["data"].astype(jnp.bfloat16)
        return b

    dtypes = {k: a.dtype for k, a in devt.aux(0, record).items()}
    dtypes["data"] = jnp.uint8
    batch = {k: jax.ShapeDtypeStruct(shape, dtypes[k], sharding=one_chip)
             for k, shape in devt.raw_overrides(n, record).items()}
    compiled = jax.jit(transform).lower(batch).compile()
    text = compiled.as_text()
    assert " while(" not in text and " reverse(" not in text
    assert " gather(" not in text and " dynamic-slice(" not in text
    assert " convolution(" in text              # the selectors, on the MXU
    if crop:        # no float32 copy of the record (uncropped, the
        # transform's float32 OUTPUT has the record's shape)
        assert "f32[%d,%d,%d,%d]" % ((n,) + record) not in text
    assert compiled.cost_analysis()["bytes accessed"] < 4e9


# the window layers of the window/global hybrid LM: 28 query heads over 4
# key-value heads of 128 at S=16,384, a window of 4,096: the band's grids
# (9 key blocks a query block of the 32, 9 query blocks a key block)
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_flash_window_kernels_compile(one_chip, which):
    q = ((2, 28, 16384, 128), jnp.bfloat16)
    kv = ((2, 4, 16384, 128), jnp.bfloat16)
    scale, window = 128 ** -0.5, 4096
    if which == "forward":
        compiled = _compile(lambda q, k, v: pa._flash_forward(
            q, k, v, True, scale, 512, 512, False, window),
            one_chip, q, kv, kv, kernels=["flash_swa_fwd"])
        assert "flash_fwd" not in compiled.as_text()
        return
    lse = jax.eval_shape(
        lambda q, k: pa._flash_forward(q, k, k, True, scale, 512, 512, True,
                                       window),
        jax.ShapeDtypeStruct(*q), jax.ShapeDtypeStruct(*kv))[1]
    _compile(lambda q, k, v, o, lse, g: pa._flash_backward(
        q, k, v, o, lse, g, True, scale, 512, 512, False, window),
        one_chip, q, kv, kv, q, (lse.shape, lse.dtype), q,
        kernels=["flash_swa_dq", "flash_swa_dkv"])


# attention over an index-picked key set at the Keye cell's shape: 32 query
# heads on 4 key-value heads of 128, 16 index heads of 64 and one index key,
# S = 32,768, topk 2,048; all the heads of a query block in one grid step
# (30 MB of VMEM under the kernels' own limit), the selection's scratch of
# 32,768 x 128 sortable integers (16 MB) and as much again for their bit
# planes
@pytest.mark.parametrize("which", ["select", "forward", "kl", "backward"])
def test_index_picked_attention_kernels_compile(one_chip, which):
    b, h, hk, s, d, hi, di, topk = 1, 32, 4, 32768, 128, 16, 64, 2048
    bf, f32 = jnp.bfloat16, jnp.float32
    q, kv = ((b, h, s, d), bf), ((b, hk, s, d), bf)
    qi, ki, w = ((b, hi, s, di), bf), ((b, s, di), bf), ((b, hi, 1, s), f32)
    row, stat = ((b, 1, s), f32), ((b, h, 1, s), f32)
    bq, bk, sq, sk = pallas_dsa.blocks(s)
    assert (bq, bk, sq, sk) == (512, 512, 128, 1024)
    scale = d ** -0.5
    if which == "select":
        _compile(lambda qi, ki, w: pallas_dsa._select(
            qi, ki, w, topk, sq, sk, False), one_chip, qi, ki, w,
            kernels=["dsa_index_select"])
    elif which == "forward":
        _compile(lambda q, k, v, qi, ki, w, thr: pallas_dsa._forward(
            q, k, v, qi, ki, w, thr, scale, bq, bk, False),
            one_chip, q, kv, kv, qi, ki, w, row,
            kernels=["flash_sparse_fwd"])
    elif which == "kl":
        _compile(lambda q, k, qi, ki, w, thr, lse, lse_i: pallas_dsa._kl_rows(
            q, k, qi, ki, w, thr, lse, lse_i, scale, bq, bk, False),
            one_chip, q, kv, qi, ki, w, row, stat, row, kernels=["dsa_kl"])
    else:
        # one kernel: dq's grid with the key side's gradients beside it
        compiled = _compile(
            lambda q, k, v, qi, ki, w, thr, lse_i, o, lse, g:
            pallas_dsa._backward(q, k, v, qi, ki, w, thr, lse_i, o, lse, g,
                                 scale, bq, bk, False),
            one_chip, q, kv, kv, qi, ki, w, row, row, q, stat, q,
            kernels=["flash_sparse_dq"])
        text = compiled.as_text()
        assert "flash_sparse_dkv" not in text
        assert text.count('custom_call_target="tpu_custom_call"') == 1
