"""PipelineLMSolver — transformer_lm trained with its trunk as a GPipe
pipeline over a "pipe" mesh axis.

Completes VERDICT round-2 item 4: pipeline parallelism was a tested but
orphaned primitive (parallel/pipeline.py); this makes it a usable solver
strategy reachable from the zoo/CLI (`sparknet lm --pipeline-stages S`).

Structure (zoo.transformer_lm_pieces):
  prefix  (embed)      — replicated, computed identically on every stage
  blocks  (x L)        — ONE CompiledNet traced once; its params stacked on
                         a leading (L, ...) dim, sharded P("pipe") so each
                         stage owns L/S consecutive blocks; the forward is
                         parallel.pipeline.pipeline_apply (GPipe schedule:
                         M microbatches, ppermute between stages)
  suffix  (head+loss)  — replicated

The optimizer is the stock caffe-semantics Updater applied to the flat
{prefix..., blocks..., suffix...} param dict — stacked leaves update
elementwise, so SGD/momentum/Adam math is identical to the unpipelined
net's. Gradient equivalence against a single-device zoo.transformer_lm
step (same param values, same batch) is asserted by
tests/test_pipeline_solver.py.

No reference twin (SURVEY.md section 2c: PP absent from the CNN-era
reference); the design target is the framework's own axis map (README).
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.compiler import CompiledNet, TRAIN
from ..solver.lr_policy import make_lr_fn
from ..solver.updates import Updater
from .pipeline import pipeline_apply, stack_params
from .data_parallel import check_global_feed, place_tree
from .mesh import make_mesh


def _flat(prefix_name, layer_params):
    return {f"{prefix_name}/{ln}": list(blobs)
            for ln, blobs in layer_params.items()}


def _unflat(flat, prefix_name):
    plen = len(prefix_name) + 1
    return {k[plen:]: v for k, v in flat.items()
            if k.startswith(prefix_name + "/")}


class PipelineLMSolver:
    """Minimal Solver-shaped driver (train_step / step / params / iter)
    for the pipelined LM. Deliberately NOT a Solver subclass: the graph is
    three CompiledNets composed functionally, not one net, so the base
    class's net-centric checkpoint/test machinery doesn't apply."""

    def __init__(self, solver_param, mesh=None, num_layers=4,
                 num_microbatches=None, axis="pipe", dtype=jnp.float32,
                 log_fn=print, metrics=None, compute_dtype=None,
                 **lm_kwargs):
        from ..models import zoo
        self.param = solver_param
        self.log = log_fn or (lambda *a: None)
        if jax.process_count() > 1 and int(solver_param.random_seed) < 0:
            # the pipe axis spans hosts: every host must hold the SAME
            # stacked params and batch (global-feed discipline, like
            # Seq/ExpertParallelSolver)
            raise ValueError(
                "multi-process PipelineLMSolver requires an explicit "
                "SolverParameter.random_seed: hosts must agree on param "
                "init and rng streams")
        self._own_metrics = isinstance(metrics, str)
        if isinstance(metrics, str):
            from ..utils.metrics import MetricsLogger
            metrics = MetricsLogger(metrics)
        self.metrics = metrics
        from ..obs import Tracer
        self.tracer = Tracer(self.metrics)
        self.stepstats = self.comms = self.memstats = None
        self._comms_registered = False
        if self.metrics is not None:
            from ..obs import StepAccounting, CommsMeter, MemoryMonitor
            self.stepstats = StepAccounting(self.metrics, tracer=self.tracer)
            self.comms = CommsMeter(self.metrics)
            self.memstats = MemoryMonitor(self.metrics, tracer=self.tracer)
        self.mesh = mesh if mesh is not None else make_mesh({axis: -1})
        self.axis = axis
        S = self.mesh.shape[axis]
        if num_layers % S:
            raise ValueError(f"num_layers {num_layers} not divisible by "
                             f"pipeline stages {S}")
        self.num_layers = num_layers
        self.num_microbatches = num_microbatches or max(2 * S, 1)
        prefix_np, block_np, suffix_np = zoo.transformer_lm_pieces(
            **lm_kwargs)
        self.prefix = CompiledNet(prefix_np, TRAIN, dtype=dtype,
                                  compute_dtype=compute_dtype)
        self.suffix = CompiledNet(suffix_np, TRAIN, dtype=dtype,
                                  compute_dtype=compute_dtype)
        self.batch_size, self.seq_len = self.prefix.feed_shapes()["data"]
        if self.batch_size % self.num_microbatches:
            raise ValueError(
                f"batch {self.batch_size} not divisible by "
                f"microbatches {self.num_microbatches}")
        # the block runs on MICROBATCHES inside the gpipe schedule — its
        # static shapes must be (B/M, S, E)
        mb = self.batch_size // self.num_microbatches
        d_model = self.suffix.feed_shapes()["x"][2]
        self.block = CompiledNet(
            block_np, TRAIN, dtype=dtype, compute_dtype=compute_dtype,
            feed_shapes={"x": (mb, self.seq_len, d_model)})

        seed = int(solver_param.random_seed)
        self.rng = jax.random.PRNGKey(seed if seed >= 0 else 0)
        self.rng, kp, ks = jax.random.split(self.rng, 3)
        prefix_p, _ = self.prefix.init(kp)
        suffix_p, _ = self.suffix.init(ks)
        block_ps = []
        for i in range(num_layers):
            self.rng, kb = jax.random.split(self.rng)
            bp, _ = self.block.init(kb)
            block_ps.append(bp)
        self.params = {**_flat("prefix", prefix_p),
                       **_flat("blocks", stack_params(block_ps)),
                       **_flat("suffix", suffix_p)}
        mults = {ln: [(1.0, 1.0)] * len(v) for ln, v in self.params.items()}
        self.updater = Updater(solver_param, mults)
        self.history = self.updater.init(self.params)
        # place params/history on the mesh up front (stage-sharded blocks,
        # replicated ends); required for multi-process, where jit cannot
        # shard host-local arrays across hosts itself
        pspec = {ln: [P(self.axis) if ln.startswith("blocks/") else P()
                      for _ in blobs]
                 for ln, blobs in self.params.items()}
        hspec = {ln: [[pspec[ln][i]] * len(slot)
                      for i, slot in enumerate(self.history[ln])]
                 for ln in self.history}
        self.params = place_tree(self.params, pspec, self.mesh)
        self.history = place_tree(self.history, hspec, self.mesh)
        self.lr_fn = make_lr_fn(solver_param)
        self.iter = 0
        self._it_dev = None
        self._jit_train = None
        self._last_loss = None
        self.snapshot_prefix = None   # set to enable periodic snapshots

    # -- forward/loss ------------------------------------------------------
    def _loss_fn(self):
        prefix, block, suffix = self.prefix, self.block, self.suffix
        mesh, M, axis = self.mesh, self.num_microbatches, self.axis

        def block_fn(bp, h):
            blobs, _ = block.apply(bp, {}, {"x": h}, train=True)
            return blobs["res2"]

        def loss_fn(params, batch, rng):
            pp = _unflat(params, "prefix")
            bp = _unflat(params, "blocks")
            sp_ = _unflat(params, "suffix")
            blobs, _ = prefix.apply(pp, {}, batch, train=True)
            h = pipeline_apply(block_fn, bp, blobs["embed"], mesh, M,
                               axis=axis)
            loss, (sblobs, _) = suffix.loss_fn(
                sp_, {}, {"x": h, "label": batch["label"]}, rng)
            return loss

        return loss_fn

    def _build_train_step(self):
        loss_fn = self._loss_fn()
        updater, lr_fn = self.updater, self.lr_fn

        def step(params, history, batch, it, rng):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, rng))(params)
            params, history = updater(params, grads, history, lr_fn(it), it)
            return params, history, loss, it + 1

        rep = NamedSharding(self.mesh, P())
        piped = NamedSharding(self.mesh, P(self.axis))
        pshard = {ln: [piped if ln.startswith("blocks/") else rep
                       for _ in blobs]
                  for ln, blobs in self.params.items()}
        hshard = {ln: [[pshard[ln][i]] * len(slot)
                       for i, slot in enumerate(self.history[ln])]
                  for ln in self.history}
        return jax.jit(step,
                       in_shardings=(pshard, hshard, rep, rep, rep),
                       out_shardings=(pshard, hshard, rep, rep),
                       donate_argnums=(0, 1))

    # -- public API --------------------------------------------------------
    def smoothed_loss(self):
        """Latest step loss (one fetch), or None before any step — same
        accessor Solver exposes, so drivers stay solver-agnostic."""
        if self._last_loss is None:
            return None
        return float(self._last_loss)

    def _register_comms(self, cm):
        """GPipe stage traffic: every microbatch activation crosses each
        stage boundary once forward (ppermute) and its gradient once
        backward — per chip that is M microbatch activations out per
        direction per step."""
        from ..obs.comms import tree_bytes
        S = self.mesh.shape[self.axis]
        mb = self.batch_size // self.num_microbatches
        d_model = self.suffix.feed_shapes()["x"][2]
        act = mb * self.seq_len * d_model * 4       # f32 carrier
        cm.set_topology(strategy=type(self).__name__,
                        n_devices=self.mesh.size,
                        axes=dict(self.mesh.shape),
                        param_bytes=tree_bytes(self.params))
        if S > 1:
            cm.register("pipeline_ppermute",
                        2 * self.num_microbatches * act, axis=self.axis,
                        note="microbatch activations fwd + grads bwd, "
                             "per chip per step")

    def _obs_step(self, host_s, result, batch):
        if self.stepstats is None:
            return
        if not self._comms_registered:
            self._comms_registered = True
            try:
                self._register_comms(self.comms)
            except Exception as e:
                self.log(f"comms registration failed: {e!r}")
        from ..obs.comms import tree_bytes
        it = self.iter - 1
        self.comms.add_h2d(tree_bytes(batch))
        self.comms.tick(it)
        sampled = self.stepstats.observe(it, host_s, result=result)
        if sampled and self.memstats is not None:
            try:
                self.memstats.sample(it)
            except Exception as e:
                self.log(f"memstats sampling failed: {e!r}")

    def close(self):
        """Flush observability summaries; close an owned metrics stream.
        Mirrors Solver.close() so drivers stay solver-agnostic."""
        self.memstats = None
        if self.stepstats is not None:
            try:
                self.stepstats.flush(self.iter)
            finally:
                self.stepstats = None
        if self.comms is not None:
            try:
                self.comms.flush(self.iter - 1)
            finally:
                self.comms = None
        if self._own_metrics and self.metrics is not None:
            self.metrics.close()
            self.metrics = None

    def train_step(self, batch):
        # the spans of every solver's step (Solver._step_span): the tracer
        # hears what the enqueue builds, and why
        with self.tracer.step("solver.step", self.iter,
                              "solver.prep") as span:
            if self._jit_train is None:
                self._jit_train = self._build_train_step()
            if jax.process_count() > 1 and not getattr(
                    self, "_feed_checked", False):
                self._feed_checked = True
                check_global_feed(batch)
            self.rng, key = jax.random.split(self.rng)
            if self._it_dev is None:
                self._it_dev = jnp.asarray(self.iter, jnp.int32)
            # the enqueue counts laying the batch over the mesh
            span.phase("solver.enqueue")
            batch = place_tree({k: np.asarray(v) for k, v in batch.items()},
                               {k: P() for k in batch}, self.mesh)
            args = (self.params, self.history, batch, self._it_dev, key)
            span.watch(self._jit_train, args,
                       ("params", "history", "batch", "iter", "key"))
            self.params, self.history, loss, self._it_dev = \
                self._jit_train(*args)
            self.iter += 1
        self._last_loss = loss
        self._obs_step(span.host_s, loss, batch)
        return loss

    def step(self, num_iters, data_iter):
        import time
        sp = self.param
        t_last, it_last = time.time(), self.iter
        for _ in range(num_iters):
            loss = self.train_step(next(data_iter))
            if sp.display and (self.iter - 1) % sp.display == 0:
                v = float(loss)
                lr = float(self.lr_fn(self.iter - 1))
                self.log(f"Iteration {self.iter - 1}, loss = {v:.6g}, "
                         f"lr = {lr:.6g}")
                if self.metrics:
                    dt = time.time() - t_last
                    steps = self.iter - it_last
                    toks = steps * self.batch_size * self.seq_len
                    self.metrics.log(
                        "train", iter=self.iter - 1, loss=v, lr=lr,
                        tokens_per_sec=round(toks / dt, 1) if dt > 0
                        else None)
                    t_last, it_last = time.time(), self.iter
            if sp.snapshot and self.snapshot_prefix \
                    and self.iter % int(sp.snapshot) == 0:
                self.snapshot(self.snapshot_prefix)

    # -- checkpointing (npz — the pipelined param layout is not a net) -----
    def snapshot(self, prefix):
        flat = {}
        for ln, blobs in self.params.items():
            for i, b in enumerate(blobs):
                flat[f"p/{ln}@{i}"] = np.asarray(b)
        for ln, blobs in self.history.items():
            for i, slots in enumerate(blobs):
                for s, h in enumerate(slots):
                    flat[f"h/{ln}@{i}@{s}"] = np.asarray(h)
        path = f"{prefix}_iter_{self.iter}.lm.npz"
        # crash-safe: a relaunch must never see a torn .lm.npz (SPK301)
        from ..resilience.checkpoint import atomic_write_bytes
        atomic_write_bytes(
            path, lambda f: np.savez(f, __iter__=self.iter, **flat))
        self.log(f"Snapshotting to {path}")
        return path

    def restore(self, path):
        z = np.load(path)
        self.iter = int(z["__iter__"])
        self._it_dev = None
        new_p = {ln: list(blobs) for ln, blobs in self.params.items()}
        new_h = {ln: [list(slots) for slots in blobs]
                 for ln, blobs in self.history.items()}
        for k in z.files:
            if k == "__iter__":
                continue
            kind, rest = k.split("/", 1)
            if kind == "p":
                ln, i = rest.rsplit("@", 1)
                ref = new_p[ln][int(i)]
                new_p[ln][int(i)] = jnp.asarray(z[k], ref.dtype)
            else:
                ln, i, s = rest.rsplit("@", 2)
                ref = new_h[ln][int(i)][int(s)]
                new_h[ln][int(i)][int(s)] = jnp.asarray(z[k], ref.dtype)
        self.params, self.history = new_p, new_h
