"""dp x sp solver: data parallelism composed with sequence parallelism.

The long-context training runner: batch dim sharded over a "data" mesh
axis, sequence dim sharded over a "seq" axis. Inside the shard_map the
net's sequence-aware layers pick the "seq" axis up from parallel.context
— Attention(ring=True) runs ring attention (parallel/ring.py: K/V blocks
rotate via ppermute, O(S/sp) memory per chip), PositionalEmbed offsets
its table lookup by the shard's global position, and SoftmaxWithLoss's
per-token mean distributes exactly over equal shards, so

    pmean_{data,seq}(local loss) == the single-device loss

and one grads-pmean over both axes makes the update identical to
single-device training on the global batch (test_seq_parallel.py asserts
the whole loss CURVE matches to tolerance).

The reference has no sequence dimension at all (CNN-era; SURVEY.md
section 5 lists long-context as a framework extension); the analog of
this file's job there is P2PSync's single data axis (parallel.cpp), which
here is just the "data" half of the mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..solver.solver import Solver
from ..obs.divergence import consensus_stats, _sq_sum, gather_worker_scalar
from .data_parallel import _rebatch, _batch_specs, shard_batch, \
    check_global_feed, check_seq_shardable_losses
from . import context
from .compat import shard_map, axis_size


class SeqParallelSolver(Solver):
    """Solver whose step runs under shard_map over ("data", "seq"):
    batch dim 0 sharded over data, dim 1 (sequence) sharded over seq;
    params/state/history replicated; grads pmean'd over both axes.

    Multi-process feeding discipline: EVERY host passes the full global
    batch (token blobs are bytes-per-element small, unlike image
    batches) and shard_batch's callback path hands each host's devices
    their (data, seq) blocks — per-host batch slicing can't express a
    sequence axis that spans hosts. check_batch therefore validates
    against GLOBAL shapes on every host."""

    def __init__(self, solver_param, mesh=None, data_axis="data",
                 seq_axis="seq", **kw):
        from .mesh import make_mesh
        if jax.process_count() > 1 and int(solver_param.random_seed) < 0:
            # every replicated input (params at init, the dropout key per
            # step) must be IDENTICAL across hosts; an unset seed falls
            # back to per-host clock entropy and training silently desyncs
            raise ValueError(
                "multi-process SeqParallelSolver requires an explicit "
                "SolverParameter.random_seed: hosts must agree on param "
                "init and rng streams")
        self.mesh = mesh if mesh is not None else \
            make_mesh({data_axis: 1, seq_axis: -1})
        self.data_axis, self.seq_axis = data_axis, seq_axis
        if int(solver_param.iter_size) > 1:
            raise ValueError("SeqParallelSolver does not support "
                             "iter_size > 1")
        super().__init__(solver_param, **kw)
        check_seq_shardable_losses(self.net, "SeqParallelSolver")
        dp = self.mesh.shape[data_axis]
        sp = self.mesh.shape[seq_axis]
        self.local_net = _rebatch(self.net, dp, seq=sp)
        self.local_test_net = _rebatch(self.test_net, dp, seq=sp) \
            if self.test_net is not None else None

    def _axes_context(self):
        return context.axis_context(data=self.data_axis, seq=self.seq_axis)

    def _batch_spec(self, batch):
        return _batch_specs(batch, self.data_axis,
                            seq_axis=self.seq_axis)

    def _sharded_step(self, batch_example):
        net, updater, lr_fn = self.local_net, self.updater, self.lr_fn
        da, sa = self.data_axis, self.seq_axis
        with_stats = self.stepstats is not None
        loss_fn = self._wrapped_loss(net)

        def step(params, state, history, batch, it, rng):
            # distinct rng stream per shard (dropout etc.)
            flat_idx = jax.lax.axis_index(da) * axis_size(sa) \
                + jax.lax.axis_index(sa)
            rng = jax.random.fold_in(rng, flat_idx)

            def lf(p):
                loss, (blobs, new_state) = loss_fn(p, state, batch, rng)
                return loss, new_state
            (loss, state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            # seq shards hold partial grads of the same data-worker's
            # batch slice: average over seq first, THEN measure the
            # between-data-worker divergence (the gradient noise) around
            # the data-axis pmean when stats are on
            g_seq = jax.lax.pmean(grads, sa)
            if with_stats:
                grads, aux = consensus_stats(g_seq, da)
                aux["ref_sq"] = _sq_sum(grads)
                aux["worker_loss"] = gather_worker_scalar(
                    jax.lax.pmean(loss, sa), da)
            else:
                grads = jax.lax.pmean(g_seq, da)
                aux = {}
            loss = jax.lax.pmean(jax.lax.pmean(loss, sa), da)
            state = jax.lax.pmean(jax.lax.pmean(state, sa), da)
            params, history = updater(params, grads, history, lr_fn(it), it)
            return params, state, history, loss, it + 1, aux

        bspec = self._batch_spec(batch_example)
        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(P(), P(), P(), bspec, P(), P()),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _build_train_step(self):
        return None              # built lazily on the first batch

    def _register_comms(self, cm):
        """Grads/state pmean over both axes (costed as one ring over the
        full mesh), plus ring attention's neighbor ppermute traffic —
        each attention layer rotates its local K/V shard around the seq
        ring once per step (forward; backward re-runs the ring, x2)."""
        from ..obs.comms import tree_bytes, ring_allreduce_bytes
        from .ring import ring_attention_comm_bytes
        super()._register_comms(cm)
        nd = self.mesh.size
        sp = self.mesh.shape[self.seq_axis]
        gb = tree_bytes(self.params) + tree_bytes(self.state)
        cm.set_topology(axes=dict(self.mesh.shape))
        cm.register("allreduce_grads", ring_allreduce_bytes(gb, nd),
                    axis=f"{self.data_axis}x{self.seq_axis}",
                    note="pmean(grads)+pmean(state) per step")
        if sp > 1:
            itemsize = np.dtype(self.net.compute_dtype
                                or self.net.dtype).itemsize
            ring_b = 0
            for lp, impl, bottoms, _ in self.local_net.layers:
                if getattr(impl, "ring", False):
                    b, s_local = self.local_net.blob_shapes[bottoms[0]][:2]
                    block = (b, s_local, getattr(impl, "inner", 0))
                    ring_b += ring_attention_comm_bytes(block, sp,
                                                        itemsize=itemsize)
            if ring_b:
                # backward replays the K/V rotation: ~2x forward traffic
                cm.register("ring_attention_ppermute", 2 * ring_b,
                            axis=self.seq_axis,
                            note="K/V block rotation, fwd+bwd, per chip "
                                 "(analytic, from local activation shapes)")

    def _shard(self, batch):
        return shard_batch(batch, self.mesh, self.data_axis,
                           seq_axis=self.seq_axis, global_feed=True)

    def train_step(self, batch):
        with self._step_span() as span:
            self.check_batch(batch, split_across_hosts=False)
            if not getattr(self, "_feed_checked", False):
                self._feed_checked = True
                check_global_feed(batch)
            self.rng, key = jax.random.split(self.rng)
            span.phase("solver.enqueue")
            with self._axes_context():
                if self._jit_train is None:
                    self._jit_train = self._sharded_step(batch)
                dev = self._shard(batch)
                if self._it_dev is None:     # device-resident, like Solver
                    self._it_dev = jnp.asarray(self.iter, jnp.int32)
                args = (self.params, self.state, self.history, dev,
                        self._it_dev, key)
                span.watch(self._jit_train, args)
                (self.params, self.state, self.history, loss,
                 self._it_dev, aux) = self._jit_train(*args)
            self.iter += 1
        self._obs_step(span.host_s, loss, batch,
                       aux=dict(aux, kind="grads") if aux else None)
        return loss

    def _build_eval_step(self):
        net = self.local_test_net
        da, sa = self.data_axis, self.seq_axis
        tf = self.test_input_transform
        compiled = {}

        def ev(params, state, batch):
            if tf is not None:
                batch = tf(batch)
            blobs, _ = net.apply(params, state, batch, train=False)
            return {b: jax.lax.pmean(jax.lax.pmean(
                jnp.asarray(blobs[b], jnp.float32), sa), da)
                    for b in net.output_blobs}

        def stepper(params, state, batch):
            # no np.asarray: test() feeds device arrays and a forced
            # fetch would serialize its pipelined eval loop
            key = tuple(sorted((k, tuple(np.shape(v)))
                               for k, v in batch.items()))
            with self._axes_context():
                if key not in compiled:
                    bspec = self._batch_spec(batch)
                    compiled[key] = jax.jit(shard_map(
                        ev, mesh=self.mesh, in_specs=(P(), P(), bspec),
                        out_specs=P(), check_vma=False))
                return compiled[key](params, state, self._shard(batch))

        return stepper
