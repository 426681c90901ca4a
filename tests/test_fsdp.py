"""FSDP/ZeRO + mixed-precision numerics contracts (parallel/fsdp.py).

The lever set's whole claim is "same numbers, less memory", so the tests
are equality tests, not smoke tests: fsdp=on at fp32 must be BIT-FOR-BIT
fsdp=off over real optimization steps (psum_scatter/n is the same
per-element additions as the pmean, the sharded update is the same
arithmetic on each device's own rows), sharded snapshots must be
consumable by every existing reader (restore, a replicated solver,
serve) unchanged, and the memory win must be visible to XLA's own
memory_analysis of the compiled step — not just to our bookkeeping."""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from sparknet_tpu.models import zoo
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver
from sparknet_tpu.solver.updates import accum_init, accum_add
from sparknet_tpu.parallel import (
    DataParallelSolver, FSDPSolver, GSPMDSolver, fsdp_enabled,
    plan_param_specs, transformer_tp_rule)
from sparknet_tpu.parallel.mesh import make_tp_mesh

VOCAB, SEQ, BATCH, D = 64, 16, 8, 64


def lm_net(batch=BATCH, seq=SEQ, d=D, nl=2, vocab=VOCAB):
    return zoo.transformer_lm(vocab_size=vocab, seq_len=seq,
                              batch_size=batch, d_model=d, num_layers=nl,
                              num_heads=4, flash=False)


def lm_batches(n, batch=BATCH, seq=SEQ, vocab=VOCAB, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        toks = rs.randint(0, vocab, (batch, seq)).astype(np.int32)
        out.append({"data": toks, "label": (toks + 1) % vocab})
    return out


def small_sp(**kw):
    fields = dict(base_lr=0.05, lr_policy="fixed", momentum=0.9,
                  weight_decay=0.0005, display=0, random_seed=7)
    fields.update(kw)
    return Message("SolverParameter", **fields)


def tree_equal(a, b):
    for lname in a:
        for i, x in enumerate(a[lname]):
            np.testing.assert_array_equal(np.asarray(x),
                                          np.asarray(b[lname][i]),
                                          err_msg=f"{lname}[{i}]")


def hist_equal(a, b):
    for lname in a:
        for i, slot in enumerate(a[lname]):
            for j, x in enumerate(slot):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(b[lname][i][j]),
                    err_msg=f"history {lname}[{i}][{j}]")


# ------------------------------------------------------------ shard plan ----

class TestPlan:
    def test_dim0_divisible_shards(self):
        tree = {"a": [np.zeros((16, 4)), np.zeros((9, 4))]}
        specs = plan_param_specs(tree, 8, min_size=1)
        assert specs["a"][0] == P("data")
        assert specs["a"][1] == P()          # 9 % 8 != 0

    def test_min_size_keeps_small_blobs_replicated(self):
        tree = {"a": [np.zeros((8,)), np.zeros((8, 512))]}
        specs = plan_param_specs(tree, 8, min_size=2048)
        assert specs["a"][0] == P()          # 8 elements < 2048
        assert specs["a"][1] == P("data")

    def test_world_of_one_replicates_everything(self):
        tree = {"a": [np.zeros((16, 4))]}
        specs = plan_param_specs(tree, 1, min_size=1)
        assert specs["a"][0] == P()

    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("SPARKNET_FSDP", raising=False)
        assert not fsdp_enabled()
        monkeypatch.setenv("SPARKNET_FSDP", "on")
        assert fsdp_enabled()
        monkeypatch.setenv("SPARKNET_FSDP", "off")
        assert not fsdp_enabled()


# ------------------------------------------------- the bitwise contract ----

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_without_fma(method):
    """Run ``TestFSDPBitwise.<method>`` in a child whose XLA:CPU has no
    fused multiply-add. Under jaxlib 0.9.0 the CPU backend's fusion
    emitters contract a*b + c into one FMA in some loops and not in
    others, and the replicated update (64 elements of ``block0/ln1``'s
    momentum) and the per-shard one (8 elements a device) come out
    differently: 4 of 64 elements 1 ulp apart (1.16e-10), params and
    losses still equal. With the ISA capped below FMA (AVX), or with
    --xla_cpu_use_fusion_emitters=false, both programs agree to the bit
    (PR 31). That is the compiler's rounding, not the algorithm's: the
    contract stays bit-for-bit, compared where both sides round alike.
    XLA_FLAGS is read once a process, hence the child."""
    code = ("import sys; sys.path.insert(0, 'tests'); import conftest; "
            f"import test_fsdp; test_fsdp.TestFSDPBitwise().{method}()")
    env = dict(os.environ,
               XLA_FLAGS=os.environ["XLA_FLAGS"] + " --xla_cpu_max_isa=AVX",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]


class TestFSDPBitwise:
    def _run(self, cls, batches, **kw):
        s = cls(small_sp(**kw.pop("sp", {})), net_param=lm_net(), **kw)
        losses = [np.asarray(s.train_step(dict(b))) for b in batches]
        return s, losses

    def test_sgd_momentum_bitwise(self):
        _child_without_fma("sgd_momentum_bitwise")

    def test_adam_bitwise(self):
        _child_without_fma("adam_bitwise")

    def sgd_momentum_bitwise(self):
        """fsdp=on at fp32 == fsdp=off, bit for bit: params, optimizer
        history AND per-step losses over real steps."""
        batches = lm_batches(4)
        dp, dp_losses = self._run(DataParallelSolver, batches)
        fs, fs_losses = self._run(FSDPSolver, batches, min_shard_size=1)
        np.testing.assert_array_equal(dp_losses, fs_losses)
        tree_equal(dp.params, fs.params)
        hist_equal(dp.history, fs.history)

    def adam_bitwise(self):
        """Adam's two history slots shard like their params and update
        to the same bits (per-shard elementwise == replicated rows)."""
        batches = lm_batches(3)
        sp = {"type": "adam", "momentum2": 0.999, "delta": 1e-8}
        dp, dp_losses = self._run(DataParallelSolver, batches, sp=dict(sp))
        fs, fs_losses = self._run(FSDPSolver, batches, sp=dict(sp),
                                  min_shard_size=1)
        np.testing.assert_array_equal(dp_losses, fs_losses)
        tree_equal(dp.params, fs.params)
        hist_equal(dp.history, fs.history)

    def test_params_live_sharded(self):
        """The step's outputs really are 1/n per device — measured off
        the live arrays, not the plan."""
        batches = lm_batches(1)
        fs, _ = self._run(FSDPSolver, batches, min_shard_size=1)
        w = fs.params["block0/ffn1"][0]          # (d_ff, d), dim0 % 8 == 0
        assert "data" in w.sharding.spec
        assert w.addressable_shards[0].data.nbytes == w.nbytes // 8
        m = fs.history["block0/ffn1"][0][0]      # momentum shards along
        assert m.addressable_shards[0].data.nbytes == m.nbytes // 8

    def test_grad_clip_matches_dp(self):
        """clip_gradients under FSDP uses the sharded-sum norm — same
        value to float tolerance (different reduction order), and the
        clipped trajectories stay close."""
        batches = lm_batches(3)
        sp = {"clip_gradients": 0.5}
        dp, dp_losses = self._run(DataParallelSolver, batches, sp=dict(sp))
        fs, fs_losses = self._run(FSDPSolver, batches, sp=dict(sp),
                                  min_shard_size=1)
        np.testing.assert_allclose(dp_losses, fs_losses, rtol=1e-5)
        for lname in dp.params:
            for i, x in enumerate(dp.params[lname]):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(fs.params[lname][i]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{lname}[{i}]")

    def test_compiled_memory_shrinks(self):
        """XLA's own memory_analysis of the compiled step: the sharded
        step's resident arguments (params + history + batch) are a
        fraction of the replicated step's."""
        b = lm_batches(1)[0]
        dp = DataParallelSolver(small_sp(), net_param=lm_net())
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        dp.train_step(dict(b))
        fs.train_step(dict(b))
        dpm = dp.compiled_memory_stats(b)
        fsm = fs.compiled_memory_stats(b)
        if dpm is None or fsm is None:
            pytest.skip("backend exposes no memory analysis")
        assert fsm["argument_bytes"] < dpm["argument_bytes"] / 4
        assert fsm["peak_bytes"] < dpm["peak_bytes"]


# --------------------------------------------------------------- refusals ----

class TestRefusals:
    def test_refuses_elastic(self):
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        with pytest.raises(ValueError, match="loses its shard"):
            fs.arm_elastic(object())

    def test_refuses_staleness(self):
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        with pytest.raises(ValueError, match="fsdp=off"):
            fs.arm_staleness(object())

    def test_refuses_staleness_kwarg(self):
        with pytest.raises(ValueError, match="staleness"):
            FSDPSolver(small_sp(), net_param=lm_net(), staleness=object())


# ----------------------------------------------- snapshots cross-consume ----

class TestShardedSnapshots:
    def test_kill_resume_matches_replicated_bitwise(self, tmp_path):
        """FSDP train N -> snapshot -> fresh FSDP solver -> restore ->
        M more steps equals BOTH the uninterrupted FSDP run and the
        plain-DP run, bit for bit (fp32)."""
        N, M = 3, 2
        batches = lm_batches(N + M)
        full = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        for b in batches:
            full.train_step(dict(b))

        part = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        for b in batches[:N]:
            part.train_step(dict(b))
        _, state_path = part.snapshot(str(tmp_path / "fs"))

        res = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        res.restore(state_path)
        assert res.iter == N
        # restored params land back in their shard layout
        w = res.params["block0/ffn1"][0]
        assert w.addressable_shards[0].data.nbytes == w.nbytes // 8
        for b in batches[N:]:
            res.train_step(dict(b))
        tree_equal(full.params, res.params)

        dp = DataParallelSolver(small_sp(), net_param=lm_net())
        for b in batches:
            dp.train_step(dict(b))
        tree_equal(dp.params, res.params)

    def test_replicated_solver_consumes_sharded_snapshot(self, tmp_path):
        """The snapshot an FSDP run writes is a NORMAL snapshot: a
        replicated DP solver restores it unchanged and continues on the
        same trajectory."""
        N = 3
        batches = lm_batches(N + 1)
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        for b in batches[:N]:
            fs.train_step(dict(b))
        _, state_path = fs.snapshot(str(tmp_path / "x"))

        dp = DataParallelSolver(small_sp(), net_param=lm_net())
        dp.restore(state_path)
        assert dp.iter == N
        tree_equal(fs.params, dp.params)
        dp.train_step(dict(batches[N]))
        fs.train_step(dict(batches[N]))
        tree_equal(fs.params, dp.params)

    def test_serve_loads_sharded_run_checkpoint(self, tmp_path):
        """`sparknet serve` consumes the checkpoint a sharded run wrote
        — weights-only load from the same manifest, no conversion."""
        from sparknet_tpu.serve import ServeEngine
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        for b in lm_batches(2):
            fs.train_step(dict(b))
        prefix = str(tmp_path / "srv")
        fs.snapshot(prefix)
        eng = ServeEngine(prefix, log_fn=None)
        entry = eng.load()
        assert entry["iter"] == 2
        got = eng._params["block0/ffn1"][0]
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(fs.params["block0/ffn1"][0]))


# --------------------------------------------------------- mixed precision ----

class TestPrecision:
    def test_env_resolution(self, monkeypatch):
        from sparknet_tpu.graph.compiler import _env_precision
        monkeypatch.delenv("SPARKNET_PRECISION", raising=False)
        assert _env_precision() is None
        monkeypatch.setenv("SPARKNET_PRECISION", "fp32")
        assert _env_precision() is None
        monkeypatch.setenv("SPARKNET_PRECISION", "bf16")
        assert _env_precision() is jnp.bfloat16
        monkeypatch.setenv("SPARKNET_PRECISION", "fp64")
        with pytest.raises(ValueError, match="SPARKNET_PRECISION"):
            _env_precision()

    def test_fp32_env_is_bitwise_off_path(self, monkeypatch):
        """precision=fp32 through the env var is the untouched path:
        bitwise-identical params to no env var at all."""
        batches = lm_batches(2)
        monkeypatch.delenv("SPARKNET_PRECISION", raising=False)
        ref = Solver(small_sp(), net_param=lm_net())
        for b in batches:
            ref.train_step(dict(b))
        monkeypatch.setenv("SPARKNET_PRECISION", "fp32")
        s = Solver(small_sp(), net_param=lm_net())
        for b in batches:
            s.train_step(dict(b))
        tree_equal(ref.params, s.params)

    def test_bf16_master_weights_stay_fp32(self, monkeypatch):
        monkeypatch.setenv("SPARKNET_PRECISION", "bf16")
        s = Solver(small_sp(), net_param=lm_net())
        assert s.net.compute_dtype == jnp.bfloat16
        s.train_step(dict(lm_batches(1)[0]))
        for lname, blobs in s.params.items():
            for b in blobs:
                assert b.dtype == jnp.float32, lname

    def test_bf16_tracks_fp32_on_surrogate(self, monkeypatch):
        """bf16 compute with fp32 masters lands within tolerance of the
        fp32 run on the shape-texture surrogate (convergence-grade
        synthetic data, data/synthetic.py)."""
        from sparknet_tpu.data.synthetic import shape_texture_images
        imgs, labels = shape_texture_images(4 * 16, seed=3)
        imgs = (imgs.astype(np.float32) - 128.0) / 64.0
        batches = [{"data": imgs[i * 16:(i + 1) * 16],
                    "label": labels[i * 16:(i + 1) * 16]}
                   for i in range(4)]
        runs = {}
        for prec in ("fp32", "bf16"):
            monkeypatch.setenv("SPARKNET_PRECISION", prec)
            s = Solver(small_sp(), net_param=zoo.cifar10_full(batch_size=16))
            runs[prec] = [float(s.train_step(dict(b))) for b in batches]
        np.testing.assert_allclose(runs["bf16"], runs["fp32"],
                                   rtol=0.05, atol=0.05)

    def test_fsdp_composes_with_bf16(self, monkeypatch):
        """fsdp=on + precision=bf16 — the headline combination — trains
        with finite loss and fp32 sharded masters."""
        monkeypatch.setenv("SPARKNET_PRECISION", "bf16")
        fs = FSDPSolver(small_sp(), net_param=lm_net(), min_shard_size=1)
        losses = [float(fs.train_step(dict(b))) for b in lm_batches(3)]
        assert all(np.isfinite(losses))
        w = fs.params["block0/ffn1"][0]
        assert w.dtype == jnp.float32
        assert w.addressable_shards[0].data.nbytes == w.nbytes // 8

    def test_accum_init_fp32_for_low_precision(self):
        """iter_size grad accumulation runs in fp32 even for sub-32-bit
        params, and stays the bitwise zeros_like path for fp32."""
        tree = {"a": [jnp.zeros((4,), jnp.bfloat16),
                      jnp.zeros((4,), jnp.float32)]}
        acc = accum_init(tree)
        assert acc["a"][0].dtype == jnp.float32
        assert acc["a"][1].dtype == jnp.float32
        g = {"a": [jnp.full((4,), 0.5, jnp.bfloat16),
                   jnp.full((4,), 0.25, jnp.float32)]}
        acc = accum_add(acc, g)
        assert acc["a"][0].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(acc["a"][0]),
                                      np.full((4,), 0.5, np.float32))


# --------------------------------------------------------- tensor parallel ----

class TestTensorParallel:
    def test_tp_rule_specs(self):
        rule = transformer_tp_rule(2)
        assert rule("block0/attn", 0, (192, 64)) == P("model")   # wqkv
        assert rule("block0/attn", 1, (192,)) == P("model")      # bqkv
        assert rule("block0/attn", 2, (64, 64)) == P(None, "model")  # wo
        assert rule("block0/attn", 3, (64,)) == P()              # bo
        assert rule("block0/ffn1", 0, (256, 64)) == P("model")
        assert rule("block0/ffn2", 0, (64, 256)) == P(None, "model")
        assert rule("block0/ffn2", 1, (64,)) == P()
        assert rule("lm_head", 0, (64, 64)) == P("model")
        assert rule("tok_embed", 0, (64, 64)) == P("model")
        assert rule("block0/ln1", 0, (64,)) == P()
        # non-divisible dims degrade to replicated, blob by blob
        assert rule("block0/ffn1", 0, (7, 64)) == P()

    def test_tp_mesh_shapes(self):
        m = make_tp_mesh(2)
        assert m.shape["model"] == 2 and m.shape["data"] == 4
        with pytest.raises(ValueError):
            make_tp_mesh(0)

    def test_tp_matches_single_device(self):
        """GSPMD over the (data, model) mesh with the transformer rule
        == single-device training, to float tolerance (XLA places the
        Megatron psums; the arithmetic is the same)."""
        batches = lm_batches(3)
        ref = Solver(small_sp(), net_param=lm_net())
        tp = GSPMDSolver(small_sp(), mesh=make_tp_mesh(2),
                         param_rule=transformer_tp_rule(2),
                         net_param=lm_net())
        for b in batches:
            lr = ref.train_step(dict(b))
            lt = tp.train_step(dict(b))
            np.testing.assert_allclose(float(lr), float(lt),
                                       rtol=1e-5, atol=1e-6)
        for lname in ref.params:
            for i, x in enumerate(ref.params[lname]):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(tp.params[lname][i]),
                    rtol=1e-4, atol=1e-5, err_msg=f"{lname}[{i}]")

    def test_tp_shards_the_named_blobs(self):
        tp = GSPMDSolver(small_sp(), mesh=make_tp_mesh(2),
                         param_rule=transformer_tp_rule(2),
                         net_param=lm_net())
        tp.train_step(dict(lm_batches(1)[0]))
        wqkv = tp.params["block0/attn"][0]
        assert wqkv.sharding.spec == P("model")
        ffn2 = tp.params["block0/ffn2"][0]
        assert ffn2.sharding.spec == P(None, "model")


# --------------------------------------------------- the one-big-model proof ----

@pytest.mark.slow
class TestOneBigModel:
    def test_d2048_fits_sharded_not_replicated(self, monkeypatch):
        """The tentpole's reason to exist, by XLA's own accounting: a
        d_model=2048 x 32-layer LM whose compiled replicated step needs
        more than one 16 GiB chip's HBM, while the FSDP step's resident
        footprint (params + optimizer state + outputs) shrinks by the
        shard factor.  Peak temp bytes are NOT asserted against the HBM
        line: on CPU XLA the scan body all-gathers the full weight stack
        into temps, which a TPU schedule would discard per-layer.
        Lower+compile only (memory_analysis needs no execution);
        scan-over-layers keeps the 1-core CPU compile sane."""
        monkeypatch.setenv("SPARKNET_SCAN", "on")
        net_kw = dict(vocab_size=32768, seq_len=256, batch_size=8,
                      d_model=2048, num_layers=32, num_heads=16,
                      flash=False)
        sp_kw = {"type": "adam", "momentum2": 0.999, "delta": 1e-8}
        rs = np.random.RandomState(0)
        toks = rs.randint(0, 32768, (8, 256)).astype(np.int32)
        b = {"data": toks, "label": (toks + 1) % 32768}
        HBM = 16 * 2 ** 30

        dp = DataParallelSolver(small_sp(**sp_kw),
                                net_param=zoo.transformer_lm(**net_kw))
        dpm = dp.compiled_memory_stats(b)
        del dp
        if dpm is None:
            pytest.skip("backend exposes no memory analysis")
        assert dpm["peak_bytes"] > HBM          # does NOT fit replicated

        fs = FSDPSolver(small_sp(**sp_kw),
                        net_param=zoo.transformer_lm(**net_kw))
        fsm = fs.compiled_memory_stats(b)
        # resident state (the ZeRO claim): args shrink ~8x minus the
        # replicated smalls — demand better than 6x
        assert fsm["argument_bytes"] < dpm["argument_bytes"] / 6
        assert fsm["output_bytes"] < dpm["output_bytes"] / 6
        # end-to-end the compiled step must still be meaningfully
        # smaller than the replicated one even with CPU's conservative
        # gather-everything temp schedule (measured on this container:
        # 22.1 GB sharded vs 40.4 GB replicated — 1.8x)
        assert fsm["peak_bytes"] < dpm["peak_bytes"] * 3 / 4
