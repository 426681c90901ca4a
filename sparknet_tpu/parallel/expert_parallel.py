"""dp x ep solver: data parallelism composed with expert parallelism.

The MoE training runner: batch dim sharded over the full ("data",
"expert") mesh — so tokens arrive SHARDED along the expert axis and
ops.moe's all_to_all path shards expert COMPUTE ep-fold, not just weight
memory (ops/moe.py:27-43) — while each MoE layer's expert-major weight
blobs (w1/b1/w2/b2, dim 0 = num_experts) live sharded P("expert"), each
device holding and updating only its own experts' slices (optimizer
history included, ZeRO-style for the expert weights). The router stays
replicated: every token computes all num_experts logits before dispatch.

Gradient semantics (the part that makes the update equal single-device
training on the global batch): the local loss is the mean over this
device's 1/(dp*ep) token slice, so

  * replicated params (router, attention, embeddings...): grads pmean'd
    over BOTH axes == the global-batch gradient (every token's
    contribution appears on exactly one device);
  * expert-sharded params: each expert's gradient contributions appear
    only on the ep-column that owns it (the backward all_to_all routes
    them home), summed over that column's ep peers already — so the
    correct reduction is pmean over "data" DIVIDED by ep (a psum over
    "data" scaled by the global 1/(dp*ep) loss normalization).
    tests/test_expert_parallel.py asserts the resulting loss curve
    equals the single-device run's exactly (no-overflow capacity).

The Switch aux loss is computed from LOCAL routing statistics and
pmean'd — mean-of-products, not the product of global means. That is the
standard data-parallel MoE formulation (each shard balances its own
routing); with aux weight 0 the step is bit-equivalent to single-device.

No reference twin: SURVEY.md section 2c lists EP/MoE as absent from the
CNN-era reference; this solver completes the dp/tp/sp/ep/pp set with the
same Solver API as the other axes. ``seq_axis`` composes a third axis —
dp x sp x ep, the long-context MoE shape: sequence dim sharded over
"seq" (ring attention + positional offsets via parallel.context, as in
SeqParallelSolver), expert dispatch still all_to_all over "expert"
within each (data, seq) row; expert-param grads then pmean over BOTH
data and seq before the 1/ep factor.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..solver.solver import Solver
from .data_parallel import _rebatch, _batch_specs, shard_batch, \
    check_global_feed, check_seq_shardable_losses, place_tree
from . import context
from .compat import shard_map, axis_size


class ExpertParallelSolver(Solver):
    """Solver whose step runs under shard_map over ("data", "expert"):
    batch dim 0 sharded over both axes, MoE expert weights sharded over
    "expert", everything else replicated; see module docstring for the
    gradient reductions."""

    # MoE param blob order: router, w1, b1, w2, b2 (ops/moe.py
    # param_shapes); slot 0 (router) is replicated, 1-4 expert-sharded
    _EXPERT_SLOTS = (1, 2, 3, 4)

    def __init__(self, solver_param, mesh=None, data_axis="data",
                 expert_axis="expert", seq_axis=None, **kw):
        from .mesh import make_mesh
        if jax.process_count() > 1 and int(solver_param.random_seed) < 0:
            raise ValueError(
                "multi-process ExpertParallelSolver requires an explicit "
                "SolverParameter.random_seed: hosts must agree on param "
                "init and rng streams")
        self.mesh = mesh if mesh is not None else \
            make_mesh({data_axis: 1, expert_axis: -1})
        self.data_axis, self.expert_axis = data_axis, expert_axis
        # optional third axis: dim 1 (sequence) sharded over "seq" — the
        # dp x sp x ep long-context MoE composition. Sequence-aware
        # layers (ring attention, positional-embed offsets, per-token
        # loss) pick the axis up from parallel.context exactly as under
        # SeqParallelSolver; the MoE all_to_all still runs over
        # "expert" only (each (data, seq) shard's tokens route among
        # that row's ep peers).
        self.seq_axis = seq_axis
        if int(solver_param.iter_size) > 1:
            raise ValueError("ExpertParallelSolver does not support "
                             "iter_size > 1")
        super().__init__(solver_param, **kw)
        if seq_axis:
            check_seq_shardable_losses(self.net, "ExpertParallelSolver")
        dp = self.mesh.shape[data_axis]
        self.ep = ep = self.mesh.shape[expert_axis]
        sp = self.mesh.shape[seq_axis] if seq_axis else 1
        self.local_net = _rebatch(self.net, dp * ep, seq=sp)
        self.local_test_net = _rebatch(self.test_net, dp * ep, seq=sp) \
            if self.test_net is not None else None
        # per-param sharding specs ({layer: [spec per owned blob]}) + the
        # matching bool tree used to pick the gradient reduction
        self._param_specs, self._expert_flags = self._build_specs()
        self._history_specs = {
            ln: [[spec] * len(self.history[ln][i])
                 for i, spec in enumerate(specs)]
            for ln, specs in self._param_specs.items()}
        # place params/history on the mesh once at init (expert blobs
        # sharded, the rest replicated); donation keeps them resident
        self.params = self._place(self.params, self._param_specs)
        self.history = self._place(self.history, self._history_specs)

    def _build_specs(self):
        ea = self.expert_axis
        specs, flags = {}, {}
        by_name = {lp.name: (lp, impl)
                   for lp, impl, _, _ in self.net.layers}
        for lname, blobs in self.params.items():
            lp, impl = by_name[lname]
            shard = lp.type == "MoE" and getattr(impl, "expert_parallel",
                                                 False)
            if shard and self.ep > 1 and \
                    impl.num_experts % self.ep:
                raise ValueError(
                    f"{lname}: num_experts {impl.num_experts} not "
                    f"divisible by expert axis size {self.ep}")
            specs[lname] = [
                P(ea) if shard and i in self._EXPERT_SLOTS else P()
                for i in range(len(blobs))]
            flags[lname] = [shard and i in self._EXPERT_SLOTS
                            for i in range(len(blobs))]
        return specs, flags

    def _place(self, tree, specs):
        return place_tree(tree, specs, self.mesh)

    def _axes_context(self):
        axes = dict(data=self.data_axis, expert=self.expert_axis)
        if self.seq_axis:
            axes["seq"] = self.seq_axis
        return context.axis_context(**axes)

    def _batch_spec(self, batch):
        return _batch_specs(batch, (self.data_axis, self.expert_axis),
                            seq_axis=self.seq_axis)

    def _sharded_step(self, batch_example):
        net, updater, lr_fn = self.local_net, self.updater, self.lr_fn
        da, ea, ep = self.data_axis, self.expert_axis, self.ep
        sa = self.seq_axis
        # every non-expert mesh axis a token shard lives on; expert-param
        # grads skip "expert" (each column owns distinct experts) but pay
        # the 1/ep loss-normalization factor (module docstring)
        other = [da] + ([sa] if sa else [])
        flags = self._expert_flags
        with_stats = self.stepstats is not None
        loss_fn = self._wrapped_loss(net)

        def pmean_over(x, axes):
            for a in axes:
                x = jax.lax.pmean(x, a)
            return x

        def reduce_grads(grads):
            def red(g, is_expert):
                if is_expert:
                    return pmean_over(g, other) / ep
                return pmean_over(g, [ea] + other)
            return jax.tree_util.tree_map(red, grads, flags)

        def step(params, state, history, batch, it, rng):
            flat_idx = jax.lax.axis_index(da)
            for a in ([sa] if sa else []) + [ea]:
                flat_idx = flat_idx * axis_size(a) \
                    + jax.lax.axis_index(a)
            rng = jax.random.fold_in(rng, flat_idx)

            def lf(p):
                loss, (blobs, new_state) = loss_fn(p, state, batch, rng)
                return loss, new_state
            (loss, state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            grads = reduce_grads(grads)
            if with_stats:
                # per-data-worker loss (averaged over its expert/seq
                # columns first): the loss-skew detector's input — a
                # token shard training differently from its peers
                from ..obs.divergence import gather_worker_scalar
                aux = {"worker_loss": gather_worker_scalar(
                    pmean_over(loss, [ea] + ([sa] if sa else [])), da)}
            else:
                aux = {}
            loss = pmean_over(loss, [ea] + other)
            state = pmean_over(state, [ea] + other)
            params, history = updater(params, grads, history, lr_fn(it), it)
            return params, state, history, loss, it + 1, aux

        bspec = self._batch_spec(batch_example)
        pspec, hspec = self._param_specs, self._history_specs
        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(pspec, P(), hspec, bspec, P(), P()),
            out_specs=(pspec, P(), hspec, P(), P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _build_train_step(self):
        return None              # built lazily on the first batch

    def _register_comms(self, cm):
        """Three traffic classes per step (module docstring): replicated
        params pmean over ALL axes; expert-sharded params pmean over the
        non-expert axes only; and the MoE dispatch/combine all_to_all
        pairs over the expert axis (fwd + bwd), costed from the local
        activation shapes."""
        from ..obs.comms import (tree_bytes, ring_allreduce_bytes,
                                 all_to_all_bytes)
        super()._register_comms(cm)
        ep = self.ep
        n_other = max(1, self.mesh.size // ep)
        eb = rb = 0
        for ln, blobs in self.params.items():
            flags = self._expert_flags.get(ln) or [False] * len(blobs)
            for b, is_expert in zip(blobs, flags):
                if is_expert:
                    eb += int(b.nbytes)
                else:
                    rb += int(b.nbytes)
        rb += tree_bytes(self.state)
        cm.set_topology(axes=dict(self.mesh.shape))
        cm.register("allreduce_dense", ring_allreduce_bytes(rb, self.mesh.size),
                    axis="all",
                    note="replicated-param grads + state pmean per step")
        if eb:
            cm.register("allreduce_expert", ring_allreduce_bytes(eb, n_other),
                        axis=self.data_axis,
                        note="expert-sharded grads pmean over non-expert "
                             "axes (global expert bytes)")
        a2a = 0
        itemsize = np.dtype(self.net.compute_dtype
                            or self.net.dtype).itemsize
        for lp, impl, bottoms, _ in self.local_net.layers:
            if lp.type == "MoE" and getattr(impl, "expert_parallel", False):
                act = 1
                for d in self.local_net.blob_shapes[bottoms[0]]:
                    act *= int(d)
                # dispatch + combine, forward and backward: 4 all_to_alls
                # of the (capacity-padded ~ input-sized) token buffer
                a2a += 4 * all_to_all_bytes(act * itemsize, ep)
        if a2a:
            cm.register("moe_all_to_all", a2a, axis=self.expert_axis,
                        note="token dispatch/combine fwd+bwd per step "
                             "(analytic, from local activation shapes)")

    def _shard(self, batch):
        return shard_batch(batch, self.mesh,
                           (self.data_axis, self.expert_axis),
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
                if self._it_dev is None:
                    self._it_dev = jnp.asarray(self.iter, jnp.int32)
                args = (self.params, self.state, self.history, dev,
                        self._it_dev, key)
                span.watch(self._jit_train, args)
                (self.params, self.state, self.history, loss,
                 self._it_dev, aux) = self._jit_train(*args)
            self.iter += 1
        self._obs_step(span.host_s, loss, batch, aux=aux or None)
        return loss

    def _build_eval_step(self):
        net = self.local_test_net
        da, ea = self.data_axis, self.expert_axis
        tf = self.test_input_transform
        compiled = {}

        sa = self.seq_axis
        axes = [ea, da] + ([sa] if sa else [])

        def ev(params, state, batch):
            if tf is not None:
                batch = tf(batch)
            blobs, _ = net.apply(params, state, batch, train=False)
            out = {}
            for b in net.output_blobs:
                v = jnp.asarray(blobs[b], jnp.float32)
                for a in axes:
                    v = jax.lax.pmean(v, a)
                out[b] = v
            return out

        def stepper(params, state, batch):
            key = tuple(sorted((k, tuple(np.shape(v)))
                               for k, v in batch.items()))
            with self._axes_context():
                if key not in compiled:
                    bspec = self._batch_spec(batch)
                    compiled[key] = jax.jit(shard_map(
                        ev, mesh=self.mesh,
                        in_specs=(self._param_specs, P(), bspec),
                        out_specs=P(), check_vma=False))
                return compiled[key](params, state, self._shard(batch))

        return stepper
