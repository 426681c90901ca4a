"""Distributed solvers: per-step allreduce DP and tau-step local SGD.

Two strategies, one mesh:

`DataParallelSolver` — synchronous data parallelism. The whole of the
reference's P2PSync machinery (parallel.cpp:271-437: tree topology from P2P
DMA pairs, weights pushed down-tree at on_start, gradients summed up-tree at
on_gradients_ready, one solver thread per GPU) is a single `lax.pmean` of
the gradients inside the compiled step; XLA lowers it to an ICI allreduce.

`LocalSGDSolver` — the SparkNet algorithm itself (CifarApp.scala:92-135):
broadcast weights, tau local SGD steps per worker on its own data shard,
collect and average. Here "broadcast" is replicated-in, "collect/average"
is one `lax.pmean` of the params per round, and the tau inner steps run as a
`lax.scan` — the entire round is ONE compiled XLA program with exactly one
collective, versus the reference's 2 full-model transfers through a JVM
driver per round (spark.driver.maxResultSize=30G, ImageNetApp.scala:42).
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..solver.solver import Solver
from ..solver.updates import accum_init, accum_add
from ..obs.divergence import (tree_sq_dist, _sq_sum,
                              gather_worker_scalar)
from ..resilience.elastic import (masked_consensus, masked_consensus_stats,
                                  masked_scalar_mean, tree_finite,
                                  staleness_discount, weighted_consensus,
                                  weighted_consensus_stats)
from .mesh import DATA_AXIS
from . import context
from .compat import shard_map


def shard_batch(batch, mesh, axis=DATA_AXIS, batch_dim=0, seq_axis=None,
                seq_dim=1, global_feed=False):
    """Place a batch dict onto the mesh, sharded along the batch dimension —
    the analog of an RDD partition landing on its executor. With
    ``seq_axis``, rank>=2 blobs are additionally sharded along ``seq_dim``
    (the dp x sp placement of SeqParallelSolver).

    Single-process: ``batch`` is the global batch; device_put scatters it.
    Multi-process (jax.process_count() > 1), two feeding disciplines:
      * global_feed=False — each host passes only ITS slice of the batch
        axis (see mesh.local_batch_slice — the per-worker RDD partition of
        CifarApp.scala:56-64); the global array is assembled from per-host
        shards without any host holding the full batch. Right for image
        batches.
      * global_feed=True — each host passes the FULL global batch and its
        devices pull their blocks via make_array_from_callback. Right when
        the batch is small but sharded along dims a per-host batch slice
        can't express (the sequence axis: a seq mesh axis spanning hosts
        needs per-host SEQUENCE blocks, which hosts can cheaply slice from
        the whole token array).
    Single-process, already-on-device jax arrays are resharded without a
    host round trip; the multihost assembly paths need host-resident data
    and will fetch a device-resident input first.

    ``axis`` may be a tuple of mesh axis names — the batch dim shards
    over their product (the (host, data) layout of the hierarchical
    runtime). A mesh made purely of THIS process's devices (a survivor
    that shrank away its dead peers — mesh.is_local_mesh) always takes
    the single-process device_put path: the global-assembly calls would
    wait on processes that no longer exist.
    """
    multihost = jax.process_count() > 1
    if multihost:
        from .mesh import is_local_mesh
        if is_local_mesh(mesh):
            multihost = False
    out = {}
    for k, v in batch.items():
        if not isinstance(v, jax.Array):
            v = np.asarray(v)
        s = _one_spec(np.ndim(v), axis, batch_dim, seq_axis, seq_dim)
        sharding = NamedSharding(mesh, s)
        if multihost and np.ndim(v):
            if global_feed:
                arr = np.asarray(v)
                out[k] = jax.make_array_from_callback(
                    arr.shape, sharding, lambda idx, a=arr: a[idx])
            else:
                out[k] = jax.make_array_from_process_local_data(
                    sharding, np.asarray(v))
        else:
            out[k] = jax.device_put(v, sharding)
    return out


def place_tree(tree, specs, mesh):
    """Place every leaf of ``tree`` on ``mesh`` per the matching
    PartitionSpec in ``specs`` (a pytree of specs with the same
    structure, or prefixes of it). Single-process: device_put.
    Multi-process: every host holds the full value (seed-identical
    init — the global-feed discipline), so the global array assembles
    via make_array_from_callback."""
    multihost = jax.process_count() > 1

    def put(spec, sub):
        sh = NamedSharding(mesh, spec)

        def one(x):
            if multihost:
                arr = np.asarray(x)
                return jax.make_array_from_callback(
                    arr.shape, sh, lambda idx, a=arr: a[idx])
            return jax.device_put(x, sh)
        # sub may be a SUBTREE (specs as a prefix tree: e.g. one spec per
        # param covering all its history slots)
        return jax.tree_util.tree_map(one, sub)

    return jax.tree_util.tree_map(put, specs, tree,
                                  is_leaf=lambda s: isinstance(s, P))


def check_seq_shardable_losses(net, solver_name):
    """Sequence-sharded exactness (pmean of per-shard means == global
    mean) requires every shard to normalize by the same token count; a
    loss with ignore_label normalizes by its LOCAL valid count, so shards
    with more padding would weigh their tokens more — silently biased
    gradients. Refuse rather than mis-train."""
    for lp, impl, _, _ in net.layers:
        if getattr(impl, "ignore_label", None) is not None and \
                net.loss_weights.get(lp.name) and \
                any(net.loss_weights[lp.name]):
            raise ValueError(
                f"layer {lp.name!r}: ignore_label losses normalize by "
                f"the per-shard valid-token count, which breaks "
                f"{solver_name}'s equal-shard loss/grad exactness "
                "(shards with more padding would be over-weighted). "
                "Drop ignore_label or mask labels on the host instead.")


def check_global_feed(batch):
    """First-step agreement check for the global-feed discipline (every
    host passes the SAME full batch; devices pull their own blocks): a
    per-host rng would desync silently — devices would pull blocks from
    their own host's divergent copy — so one cross-host checksum
    comparison surfaces it. Call once, on the first fed batch."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils
    sums = np.array([np.asarray(v, np.float64).sum()
                     for _, v in sorted(batch.items())])
    gathered = multihost_utils.process_allgather(sums)
    if not np.allclose(gathered, gathered[0]):
        raise ValueError(
            "global-feed batches differ across hosts (first-step "
            "checksum mismatch): every host must construct the identical "
            "global batch")


def _rebatch(net, n, seq=1):
    """Compile a per-shard twin of ``net``: identical params/layers and
    precision, feed blobs with leading (batch) dim divided by ``n`` (and,
    for ``seq > 1``, dim 1 divided by ``seq``)."""
    from ..graph.compiler import CompiledNet
    local = {}
    for name, s in net.feed_shapes().items():
        if not s:
            local[name] = s
            continue
        if s[0] % n:
            raise ValueError(
                f"feed blob {name!r} batch {s[0]} not divisible by mesh "
                f"axis size {n}")
        out = [s[0] // n] + list(s[1:])
        if seq > 1 and len(s) >= 2:
            # rank-1 (per-example) blobs need no sequence shard: _one_spec
            # already leaves them replicated along the seq axis
            if s[1] % seq:
                raise ValueError(
                    f"feed blob {name!r} seq dim {s[1]} not divisible "
                    f"by seq axis size {seq}")
            out[1] = s[1] // seq
        local[name] = tuple(out)
    twin = CompiledNet(net.net_param, net.phase, feed_shapes=local,
                       dtype=net.dtype, compute_dtype=net.compute_dtype)
    # the knobs the solver's constructor may have set (Solver(remat=...))
    twin.remat, twin.scan = net.remat, net.scan
    return twin


def _one_spec(ndim, axis, batch_dim=0, seq_axis=None, seq_dim=1):
    if not ndim:
        return P()
    spec = [None] * ndim
    if batch_dim < ndim:
        spec[batch_dim] = axis
    if seq_axis is not None and seq_dim < ndim:
        spec[seq_dim] = seq_axis
    return P(*spec)


def _batch_specs(batch, axis, batch_dim=0, seq_axis=None, seq_dim=1):
    return {k: _one_spec(np.ndim(v), axis, batch_dim, seq_axis, seq_dim)
            for k, v in batch.items()}


class DataParallelSolver(Solver):
    """Solver whose train step runs under shard_map over the "data" axis:
    batch sharded, params/state/history replicated, grads pmean'd.

    pmean (not psum) keeps the effective lr identical to single-device
    training on the same *global* batch, matching Caffe's semantics where
    the loss is already normalized by the full batch size."""

    def __init__(self, solver_param, mesh=None, axis=DATA_AXIS,
                 staleness=None, s_decay=0.5, **kw):
        from .mesh import make_mesh
        self.mesh = mesh if mesh is not None else make_mesh({axis: -1})
        self.axis = axis
        super().__init__(solver_param, **kw)
        # the per-shard nets: same params, feed blobs at batch/n — the graph
        # each device traces (the user-facing self.net keeps global shapes)
        n = self.mesh.shape[axis]
        if jax.process_count() == 1:
            # replicated over the mesh from the start (the step's
            # in_specs), so that whoever replaces a blob keeps that
            # placement: weights committed to one device cannot enter a
            # program that spans the mesh
            rep = NamedSharding(self.mesh, P())
            self.params, self.state, self.history = jax.device_put(
                (self.params, self.state, self.history), rep)
        self.local_net = _rebatch(self.net, n)
        self.local_test_net = _rebatch(self.test_net, n) \
            if self.test_net is not None else None
        if staleness is not None:
            # async bounded staleness at step granularity (the LocalSGD
            # round-granularity twin — see LocalSGDSolver)
            self.arm_staleness(staleness, decay=s_decay)

    # -- compiled steps ----------------------------------------------------
    def _sharded_step(self, batch_example):
        iter_size = int(self.param.iter_size)
        net, updater, lr_fn = self.local_net, self.updater, self.lr_fn
        axis = self.axis
        n_workers = self.mesh.shape[axis]
        # metrics on -> also measure per-worker gradient divergence around
        # the averaging consensus (obs/divergence.py): the between-shard
        # gradient noise, per layer, plus the per-worker loss vector —
        # all replicated scalars, fetched only at step-sample points
        with_stats = self.stepstats is not None
        # elastic membership armed -> every collective is validity-masked
        # (resilience/elastic.py): a worker the host evicted, or whose
        # grads/loss went non-finite this step, is excluded from the
        # consensus with its weight renormalized over the live count —
        # bit-for-bit the old pmean when every worker is valid
        elastic_on = self.elastic is not None
        # async bounded staleness -> the gradient consensus additionally
        # discounts each shard by its version lag (step-granularity
        # versions; lag is a traced input, zero recompiles)
        async_on = self.staleness is not None and elastic_on
        s_bound, s_decay = self.staleness, self.s_decay
        loss_fn = self._wrapped_loss(net)   # device-side input transform
        # (shape-polymorphic vmap, so the global-net transform applies
        # unchanged to each shard's slice)
        # bucketed grad consensus (parallel/overlap.py): reverse-order
        # per-dtype buckets let XLA start allreducing deep layers' grads
        # while shallow layers' backward still runs — bit-for-bit the
        # whole-tree consensus, so it defaults on. The stats variants
        # take the bucketed result as a precomputed consensus and keep
        # their per-layer divergence decomposition on the raw tree.
        from .overlap import bucketed_consensus, overlap_enabled
        overlap_on = overlap_enabled()

        def grad_consensus(consensus_fn, grads, weight):
            # the gradients' exchange and what it costs beside the
            # all-reduce itself (the buckets laid flat and cut up again,
            # the mean's division): one scope, so a device trace adds it up
            with jax.named_scope("grad_exchange"):
                if overlap_on:
                    return bucketed_consensus(consensus_fn, grads, weight,
                                              axis)
                return consensus_fn(grads, weight, axis)

        def one_grad(params, state, batch, rng):
            def lf(p):
                loss, (blobs, new_state) = loss_fn(p, state, batch, rng)
                return loss, new_state
            (loss, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            return loss, grads, new_state

        def step(params, state, history, batch, it, rng, alive, lag):
            # one step rng for the global batch: a layer that draws per
            # sample (Dropout) keeps its shard's rows of the global draw,
            # so the mesh step equals the one-device step on the same
            # batch; any other random layer folds its shard's index in
            # (parallel.context.batch_shard, read while `one_grad` traces)
            with jax.named_scope("grad_exchange"):
                w = jax.lax.axis_index(axis)
                my_alive = alive[w]
            if iter_size == 1:
                with context.batch_shard_context(axis, n_workers):
                    loss, grads, state = one_grad(params, state, batch, rng)
            else:
                def body(carry, micro):
                    acc, state, i = carry
                    with context.batch_shard_context(axis, n_workers):
                        loss, g, state = one_grad(
                            params, state, micro,
                            jax.random.fold_in(rng, i))
                    # fp32 accumulation regardless of param dtype (the
                    # mixed-precision contract; bitwise the old
                    # zeros_like path for fp32 params)
                    with jax.named_scope("grad_accum"):
                        acc = accum_add(acc, g)
                    return (acc, state, i + 1), loss
                with jax.named_scope("grad_accum"):
                    (grads, state, _), losses = jax.lax.scan(
                        body, (accum_init(params), state, 0), batch)
                    loss = jnp.mean(losses)
            # validity: the host-declared alive bit AND (with elasticity
            # armed) the on-device finite check — a NaN'd shard can't
            # poison the consensus even before the host evicts it
            with jax.named_scope("grad_exchange"):
                if elastic_on:
                    finite = jnp.logical_and(tree_finite(grads),
                                             jnp.isfinite(loss))
                    valid = my_alive * finite.astype(jnp.float32)
                else:
                    valid = my_alive
                if async_on:
                    sweight = valid * staleness_discount(lag[w], s_bound,
                                                         s_decay)
                    inc = (sweight > 0).astype(jnp.float32)
                else:
                    sweight = valid
                    inc = valid
            # THE collective: replaces P2PSync's up-tree gradient sum —
            # with stats on, masked_consensus_stats is the same masked
            # average plus each live shard's drift from it (the
            # gradient noise)
            if with_stats:
                if async_on:
                    pre = grad_consensus(weighted_consensus, grads,
                                         sweight) if overlap_on else None
                    grads, aux = weighted_consensus_stats(
                        grads, valid, sweight, axis, consensus=pre)
                else:
                    pre = grad_consensus(masked_consensus, grads,
                                         valid) if overlap_on else None
                    grads, aux = masked_consensus_stats(
                        grads, valid, axis, consensus=pre)
                aux["ref_sq"] = _sq_sum(grads)
                aux["worker_loss"] = gather_worker_scalar(loss, axis)
            elif elastic_on:
                if async_on:
                    grads, _ = grad_consensus(weighted_consensus, grads,
                                              sweight)
                    n_live = jax.lax.psum(inc, axis)
                else:
                    grads, n_live = grad_consensus(masked_consensus, grads,
                                                   valid)
                aux = {"valid": jax.lax.all_gather(valid, axis),
                       "n_live": n_live,
                       "worker_loss": gather_worker_scalar(loss, axis)}
                if async_on:
                    aux["weight"] = jax.lax.all_gather(sweight, axis)
            else:
                grads, _ = grad_consensus(masked_consensus, grads, valid)
                aux = {}
            with jax.named_scope("grad_exchange"):
                loss = masked_scalar_mean(loss, inc, axis)
                # BN running stats etc. must stay replicated
                if async_on:
                    state, _ = weighted_consensus(state, sweight, axis)
                else:
                    state, _ = masked_consensus(state, valid, axis)
            with jax.named_scope("update"):
                rate = lr_fn(it)
            params, history = updater(params, grads, history, rate, it)
            return params, state, history, loss, aux

        bspec = _batch_specs(batch_example, axis,
                             batch_dim=0 if iter_size == 1 else 1)
        with context.axis_context(data=axis), \
                context.world_context(axis=axis, size=n_workers,
                                      elastic=elastic_on):
            sharded = shard_map(
                step, mesh=self.mesh,
                in_specs=(P(), P(), P(), bspec, P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P(), P()),
                check_vma=False)
            return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _build_train_step(self):
        # built lazily on first batch (need shapes for specs)
        return None

    def _memory_step_fn(self, batch):
        if self._jit_train is None:
            self._jit_train = self._sharded_step(
                {k: np.asarray(v) for k, v in batch.items()})
        return self._jit_train

    def _memory_step_args(self, batch):
        dev_batch = shard_batch(
            batch, self.mesh, self.axis,
            batch_dim=0 if int(self.param.iter_size) == 1 else 1)
        return (self.params, self.state, self.history, dev_batch,
                jnp.asarray(self.iter, jnp.int32), self.rng,
                self._alive_mask(), self._staleness_lag())

    def _register_comms(self, cm):
        """Per-step DP sync: the grads+state pmean over the data axis —
        the P2PSync replacement, costed with obs/comms.py's ring model.
        With bucketed overlap on (the default, parallel/overlap.py) the
        gradient volume is registered per bucket in issue order; every
        bucket but the last-issued one (the stem/embedding grads backward
        finishes last) can hide under the backward tail, so the meter
        marks them overlappable and `sparknet report` decomposes
        overlapped vs exposed bytes."""
        from ..obs.comms import (tree_bytes, ring_allreduce_bytes,
                                 broadcast_collect_bytes)
        from .overlap import bucket_sizes, overlap_enabled, plan_buckets
        super()._register_comms(cm)
        n = self.mesh.shape[self.axis]
        gb = tree_bytes(self.params)
        sb = tree_bytes(self.state)
        cm.set_topology(axes=dict(self.mesh.shape))
        if overlap_enabled():
            sizes = bucket_sizes(plan_buckets(self.params))
            for bi, nb in enumerate(sizes):
                extra = {}
                if bi == len(sizes) - 1:
                    # the paper comparison rides the grad volume (its
                    # per-round weight movement), not the BN state —
                    # which may be empty and hence unregistered
                    extra["paper_broadcast_collect_bytes"] = \
                        broadcast_collect_bytes(gb, n)
                cm.register(
                    "allreduce_grads_bucket", ring_allreduce_bytes(nb, n),
                    axis=self.axis, bucket=bi,
                    overlappable=bi < len(sizes) - 1,
                    note="bucketed pmean(grads), issued as backward "
                         "drains; ring model per chip", **extra)
            cm.register(
                "allreduce_state", ring_allreduce_bytes(sb, n),
                axis=self.axis,
                note="pmean(state) per step, ring model per chip")
        else:
            cm.register(
                "allreduce_grads", ring_allreduce_bytes(gb + sb, n),
                axis=self.axis,
                note="pmean(grads)+pmean(state) per step, ring model "
                     "per chip",
                paper_broadcast_collect_bytes=broadcast_collect_bytes(gb, n))

    def train_step(self, batch):
        with self._step_span() as span:
            # a batch that already lives on the devices stays there
            # (shard_batch reshards it without a host round trip)
            batch = {k: v if isinstance(v, jax.Array) else np.asarray(v)
                     for k, v in batch.items()}
            iter_size = int(self.param.iter_size)
            self.check_batch(batch,
                             leading=(iter_size,) if iter_size > 1 else ())
            if self._jit_train is None:
                self._jit_train = self._sharded_step(batch)
            self.rng, key = jax.random.split(self.rng)
            # the enqueue counts laying the batch over the mesh
            span.phase("solver.enqueue")
            dev_batch = shard_batch(batch, self.mesh, self.axis,
                                    batch_dim=0 if iter_size == 1 else 1)
            args = (self.params, self.state, self.history, dev_batch,
                    jnp.asarray(self.iter, jnp.int32), key,
                    self._alive_mask(), self._staleness_lag())
            span.watch(self._jit_train, args)
            self.params, self.state, self.history, loss, aux = \
                self._jit_train(*args)
            self.iter += 1
        host_s = span.host_s
        if self.staleness is not None and self.elastic is not None:
            # step-granularity version clocks: the DP twin of the
            # LocalSGD round bookkeeping (park/unpark events flow from
            # the policy itself)
            it = self.iter - 1
            slow = self.chaos.slow_worker_spec(it) \
                if self.chaos is not None else None
            self.elastic.advance_versions(it, host_s, slow=slow)
            self.elastic.observe_staleness(it)
        self._obs_step(host_s, loss, batch,
                       aux=dict(aux, kind="grads") if aux else None)
        if aux and self.elastic is not None and self.stepstats is None:
            # metrics off: _obs_step never fetches the aux, but the
            # membership controller still needs the validity vector
            self._observe_sync_round(dict(aux, kind="grads"))
        return self._chaos_loss(loss)

    def _build_eval_step(self):
        net = self.local_test_net
        axis = self.axis
        tf = self.test_input_transform

        def ev(params, state, batch):
            if tf is not None:
                batch = tf(batch)
            blobs, _ = net.apply(params, state, batch, train=False)
            # test scores are batch means -> pmean across equal shards
            return {b: jax.lax.pmean(jnp.asarray(blobs[b], jnp.float32), axis)
                    for b in net.output_blobs}

        compiled = {}

        def stepper(params, state, batch):
            batch = {k: np.asarray(v) for k, v in batch.items()}
            key = tuple(sorted((k, v.shape) for k, v in batch.items()))
            if key not in compiled:
                bspec = {k: (P(axis) if v.ndim else P())
                         for k, v in batch.items()}
                compiled[key] = jax.jit(shard_map(
                    ev, mesh=self.mesh, in_specs=(P(), P(), bspec),
                    out_specs=P(), check_vma=False))
            dev = shard_batch(batch, self.mesh, self.axis)
            return compiled[key](params, state, dev)

        return stepper


class LocalSGDSolver(Solver):
    """tau-step local SGD with periodic weight averaging — the SparkNet
    outer loop compiled to one XLA program per round.

    round(params, ...) under shard_map:
      each "worker" (mesh slot on the data axis) runs tau sequential solver
      steps on its own tau batches via lax.scan, with its own lr schedule
      positions (global iter advances tau per round, matching the reference
      where each worker's native solver advances its own iter counter);
      then params (and optionally history) are pmean'd.

    average_history=True also averages optimizer state each round; the
    reference does NOT (each Caffe worker keeps its own momentum, only
    weights go through the driver — Net.scala:134-154), so default False.

    unroll: scan unroll factor for the tau inner steps. None (default)
    picks per platform: full unroll on CPU meshes — XLA:CPU pessimizes
    convolutions inside While loops ~10x (measured: 27.7s vs 2.8s for 10
    cifar10_full steps), which would poison the virtual-mesh experiments —
    and 1 on TPU, where the rolled loop compiles fast and runs at full
    speed.

    host_axis: arms the HIERARCHICAL two-tier mode over a 2-D
    (host_axis, axis) mesh (parallel.multihost.host_mesh): the local-SGD
    "worker" becomes a whole host — its devices run per-step gradient
    pmean over ``axis`` (synchronous DP inside the fault domain, over
    ICI), hosts diverge for tau steps, and the round's collect & average
    is the masked consensus over ``host_axis`` (over DCN) with a
    PER-HOST alive mask. Membership — eviction, readmission, quorum —
    operates at host granularity, matching the real production failure
    unit (preemption/OOM kill whole processes, not single chips). With
    one device per host the inner tier is skipped at trace time, so the
    round is bit-for-bit the single-tier SparkNet round it generalizes.

    staleness: arms the ASYNCHRONOUS bounded-staleness mode (`--staleness
    s` next to `--tau`): workers push versioned contributions and the
    round's collect & average becomes a staleness-weighted consensus
    (resilience/elastic.py) — a worker ``lag`` rounds behind the fastest
    live peer is discounted by ``s_decay ** lag``, parked (excluded,
    still a member) once ``lag > s``, and resynced from the replicated
    consensus after the cooldown. The round never blocks on a straggler:
    a chaos ``slow_worker``'s injected seconds land on its own virtual
    clock (its lag grows) instead of the host loop, so round latency
    tracks the median worker, not the max. s=0 is BIT-FOR-BIT the
    synchronous masked round (the same guarantee style as the all-valid
    masked pmean); the lag vector is a traced input, so staleness
    changes cost zero recompiles.
    """

    def __init__(self, solver_param, mesh=None, axis=DATA_AXIS, tau=10,
                 average_history=False, unroll=None, host_axis=None,
                 staleness=None, s_decay=0.5, **kw):
        from .mesh import make_mesh, make_host_device_mesh
        self.host_axis = host_axis
        if mesh is None:
            mesh = make_host_device_mesh(device_axis=axis) \
                if host_axis is not None else make_mesh({axis: -1})
        self.mesh = mesh
        self.axis = axis
        if host_axis is not None and host_axis not in self.mesh.shape:
            raise ValueError(f"host_axis {host_axis!r} not in mesh axes "
                             f"{tuple(self.mesh.shape)}")
        # membership granularity: per-host in hierarchical mode (the
        # alive mask indexes fault domains), per-device-worker otherwise
        self.elastic_axis = host_axis if host_axis is not None else axis
        self.elastic_unit = "host" if host_axis is not None else "worker"
        self.tau = int(tau)
        self.unroll = unroll
        self.average_history = bool(average_history)
        # cross-host transport for the tau-consensus: None = the
        # compiled masked collective; a heartbeat.FileConsensus when
        # arm_heartbeat decided the backend needs the relay
        self._relay = None
        super().__init__(solver_param, **kw)
        self._jit_round = None
        self._round_idx = 0
        if staleness is not None:
            self.arm_staleness(staleness, decay=s_decay)

    def _build_round(self, batch_example):
        net, updater, lr_fn = self.net, self.updater, self.lr_fn
        axis, tau = self.axis, self.tau
        # two-tier wiring: the tau-interval consensus (and the alive
        # mask) runs over sync_axis; intra > 1 arms the per-step
        # gradient pmean over ``axis`` inside each fault domain. Both
        # collapse at trace time in the degenerate configurations, so
        # hosts=1 or one-device-per-host is the single-tier program
        # bit-for-bit (the PR 4 masked-pmean guarantee style).
        host_axis = self.host_axis
        sync_axis = host_axis if host_axis is not None else axis
        n_workers = self.mesh.shape[sync_axis]
        intra = self.mesh.shape[axis] if host_axis is not None else 1
        unroll = self.unroll
        if unroll is None:
            # True = fully unroll regardless of tau (works on every jax
            # vintage; integer 0 is rejected by older lax.scan). unroll=tau
            # would seem equivalent but lowers tau==1 through the While
            # path (jax excludes unroll==1 from its full-unroll shortcut),
            # which XLA:CPU pessimizes ~10x like any conv-in-loop
            unroll = True if all(d.platform == "cpu"
                                 for d in self.mesh.devices.flat) else 1
        average_history = self.average_history
        # metrics on -> measure the paper's tau drift where it happens:
        # each worker's L2 distance from the post-average consensus,
        # computed on-device BEFORE the averaging collective (the average
        # itself comes from masked_consensus_stats, so the extra cost is
        # one elementwise pass + scalar collectives, never a host gather)
        with_stats = self.stepstats is not None
        # elastic membership armed -> the collect & average is quorum-
        # based (resilience/elastic.py): host-evicted or non-finite
        # workers are excluded and the weights renormalize over the live
        # count — bit-for-bit the old pmean when every worker is valid
        elastic_on = self.elastic is not None
        # async bounded staleness armed -> the average is additionally
        # weighted by each worker's version lag (a traced input like the
        # alive mask — zero recompiles); all-lag-zero weights are
        # exactly 1.0, so s=0 stays the synchronous round bit for bit
        async_on = self.staleness is not None and elastic_on
        s_bound, s_decay = self.staleness, self.s_decay
        loss_fn = self._wrapped_loss(net)

        def one_step(params, state, history, batch, it, rng):
            def lf(p):
                loss, (blobs, new_state) = loss_fn(p, state, batch, rng)
                return loss, new_state
            (loss, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            if intra > 1:
                # tier 1, per STEP: devices inside one fault domain are
                # a synchronous DP group (grads pmean'd over ICI), so
                # params/history stay replicated within the host and the
                # host is ONE logical local-SGD worker
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, axis), grads)
            params, history = updater(params, grads, history, lr_fn(it), it)
            return params, new_state, history, loss

        def intra_mean(x):
            """Fold a per-device value to its host's mean — a trace-time
            no-op outside hierarchical mode (bit-for-bit single-tier)."""
            if intra <= 1:
                return x
            return jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axis), x)

        def round_fn(params, state, history, batches, it0, rng, alive, lag):
            params_in = params          # the round's broadcast weights
            w = jax.lax.axis_index(sync_axis)
            my_alive = alive[w]
            rng = jax.random.fold_in(rng, w)
            if intra > 1:
                # distinct dropout/augmentation streams per device inside
                # the host (their grads average, like any DP group)
                rng = jax.random.fold_in(rng, jax.lax.axis_index(axis) + 1)

            def body(carry, inp):
                params, state, history = carry
                batch, i = inp
                params, state, history, loss = one_step(
                    params, state, history, batch, it0 + i,
                    jax.random.fold_in(rng, i))
                return (params, state, history), loss

            (params, state, history), losses = jax.lax.scan(
                body, (params, state, history),
                (batches, jnp.arange(tau, dtype=jnp.int32)),
                unroll=unroll)
            # validity: the host-declared alive bit AND (with elasticity
            # armed) the on-device finite check over this worker's
            # replica — a replica that went NaN mid-round can never
            # poison the consensus, even before the host evicts it. In
            # hierarchical mode the fault domain is valid only when
            # EVERY one of its devices is (pmin over the intra axis).
            if elastic_on:
                finite = jnp.logical_and(tree_finite(params),
                                         jnp.all(jnp.isfinite(losses)))
                finite = finite.astype(jnp.float32)
                if intra > 1:
                    finite = jax.lax.pmin(finite, axis)
                valid = my_alive * finite
            else:
                valid = my_alive
            if async_on:
                # bounded staleness: this worker's push is discounted by
                # its version lag; over the bound the discount is 0 and
                # the same where-mask that excludes dead workers applies
                # — stale and dead degrade identically. valid stays the
                # MEMBERSHIP bit (a parked-but-healthy worker must not
                # accrue "nonfinite" eviction streaks).
                sweight = valid * staleness_discount(lag[w], s_bound,
                                                     s_decay)
                inc = (sweight > 0).astype(jnp.float32)
            else:
                sweight = valid
                inc = valid
            # the per-worker (per-host, hierarchically) round loss: mean
            # over tau steps, folded over the host's devices
            local_loss = intra_mean(jnp.mean(losses))
            # tier 2, per ROUND — collect & average
            # (CifarApp.scala:131-133) == one masked weighted average
            # over sync_axis (== pmean when all workers are valid) —
            # with stats on, masked_consensus_stats IS that average plus
            # each live worker's drift from the result (the paper's tau
            # drift), and ref_sq is the consensus round update's sq norm
            if with_stats:
                if async_on:
                    params, aux = weighted_consensus_stats(
                        params, valid, sweight, sync_axis)
                else:
                    params, aux = masked_consensus_stats(params, valid,
                                                         sync_axis)
                aux["ref_sq"] = tree_sq_dist(params, params_in)[1]
                aux["worker_loss"] = gather_worker_scalar(local_loss,
                                                          sync_axis)
            elif elastic_on:
                if async_on:
                    params, _ = weighted_consensus(params, sweight,
                                                   sync_axis)
                    n_live = jax.lax.psum(inc, sync_axis)
                else:
                    params, n_live = masked_consensus(params, valid,
                                                      sync_axis)
                aux = {"valid": jax.lax.all_gather(valid, sync_axis),
                       "n_live": n_live,
                       "worker_loss": gather_worker_scalar(local_loss,
                                                           sync_axis)}
                if async_on:
                    aux["weight"] = jax.lax.all_gather(sweight, sync_axis)
            else:
                params, _ = masked_consensus(params, valid, sync_axis)
                aux = {}
            # BN running stats differ per device (each saw its own
            # shard): fold within the host first, then the masked
            # cross-host consensus (staleness-weighted in async mode,
            # like the params they ran under)
            if async_on:
                state, _ = weighted_consensus(intra_mean(state), sweight,
                                              sync_axis)
                if average_history:
                    history, _ = weighted_consensus(history, sweight,
                                                    sync_axis)
            else:
                state, _ = masked_consensus(intra_mean(state), valid,
                                            sync_axis)
                if average_history:
                    # history is already replicated within a host
                    # (identical pmean'd grads drive identical updates),
                    # so only the cross-host average is needed
                    history, _ = masked_consensus(history, valid,
                                                  sync_axis)
            # the round loss is the mean over the INCLUDED workers' tau
            # steps — without the collective the P() out_spec would hand
            # back whichever worker's mean sits on the fetching host's
            # first device (observably different across hosts/modes)
            return params, state, history, \
                masked_scalar_mean(local_loss, inc, sync_axis), aux

        shard_axes = (host_axis, axis) if host_axis is not None else axis
        bspec = _batch_specs(batch_example, shard_axes, batch_dim=1)
        world_kw = dict(axis=axis, size=self.mesh.shape[axis],
                        elastic=elastic_on)
        if host_axis is not None:
            world_kw.update(host_axis=host_axis, hosts=n_workers)
        with context.axis_context(data=axis), \
                context.world_context(**world_kw):
            sharded = shard_map(
                round_fn, mesh=self.mesh,
                in_specs=(P(), P(), P(), bspec, P(), P(), P(), P()),
                out_specs=(P(), P(), P(), P(), P()),
                check_vma=False)
            return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _register_comms(self, cm):
        """The SparkNet tradeoff itself: ONE param-sized averaging pmean
        per tau-step round (vs. DP's per-step grad allreduce). In
        hierarchical mode the round average crosses hosts (DCN) while a
        per-step gradient pmean stays inside each host (ICI) — both
        registered so the report shows the two tiers' volumes apart."""
        from ..obs.comms import (tree_bytes, ring_allreduce_bytes,
                                 broadcast_collect_bytes)
        super()._register_comms(cm)
        sync_axis = self.host_axis if self.host_axis is not None \
            else self.axis
        n = self.mesh.shape[sync_axis]
        pb = tree_bytes(self.params) + tree_bytes(self.state)
        if self.average_history:
            pb += tree_bytes(self.history)
        cm.set_topology(axes=dict(self.mesh.shape), tau=self.tau)
        cm.register(
            "param_average", ring_allreduce_bytes(pb, n), axis=sync_axis,
            steps_per_round=self.tau,
            note="one weight-averaging pmean per tau-step round "
                 "(the paper's broadcast+collect)"
                 + (" across hosts" if self.host_axis is not None else ""),
            paper_broadcast_collect_bytes=broadcast_collect_bytes(pb, n))
        if self.host_axis is not None and self.mesh.shape[self.axis] > 1:
            gb = tree_bytes(self.params)
            cm.register(
                "intra_host_grad_pmean",
                ring_allreduce_bytes(gb, self.mesh.shape[self.axis]),
                axis=self.axis, steps_per_round=1,
                note="per-step gradient pmean inside each fault domain "
                     "(tier 1 of hierarchical local SGD)")

    def _round_latencies(self, round_s):
        """Per-worker latencies for the finished round. A single fused
        XLA program has no native per-worker timer, so the base vector is
        the round wall time for every worker; a chaos-injected stall with
        a worker attribution (stall_worker=W) lands its seconds on W
        alone — its peers finished a stall early, exactly the shape a
        per-host timer would report for a real straggler. In
        hierarchical mode the vector is per-HOST (the membership unit),
        and a chaos slow_host's injected seconds land on that host."""
        n = self.mesh.shape[self.elastic_axis]
        if n <= 1 or round_s is None:
            return None
        lat = [float(round_s)] * n
        if self.chaos is not None:
            if self.staleness is not None:
                # async mode: the straggler's injected seconds never
                # blocked the host loop (round_s IS the median pace), so
                # its latency is attributed VIRTUALLY — the per-worker
                # timer a real async runtime would report
                spec = self.chaos.slow_worker_spec(self._round_idx)
                if spec is not None and 0 <= spec[0] < n:
                    lat[spec[0]] = float(round_s) + float(spec[1])
                return lat
            rep = self.chaos.pop_stall()
            rep = self.chaos.pop_slow_worker() or rep
            if self.host_axis is not None:
                rep = self.chaos.pop_slow_host() or rep
            if rep and rep[0] is not None and 0 <= rep[0] < n:
                w, sec = rep
                base = max(0.0, float(round_s) - float(sec))
                lat = [base] * n
                lat[w] = float(round_s)
        return lat

    def shrink_to_survivors(self):
        """Rebuild the mesh over the live workers' devices — the
        recompile path for a PERSISTENT eviction (ElasticPolicy
        shrink_after), so dead slots stop burning compute. Params/state/
        history are pulled to host and re-placed on the shrunk mesh by
        the next round's jit; membership resets to the new world (the
        evicted device left the mesh, so readmission is over). Callers
        must size subsequent round batches off the NEW world:
        (tau, live*per_worker_batch). Returns True when the mesh
        changed."""
        if self.elastic is None:
            raise ValueError("shrink_to_survivors needs arm_elastic()")
        if self.host_axis is None and len(self.mesh.shape) != 1:
            raise ValueError("mesh shrink supports pure data-axis meshes")
        live = self.elastic.live()
        old = self.mesh.shape[self.elastic_axis]
        if len(live) == old:
            return False
        if self.host_axis is not None:
            # hierarchical: drop the dead HOST rows. When only this
            # process's row survives, the result is a purely local mesh
            # and later rounds never touch the cross-host fabric a dead
            # peer would hang (parallel.multihost.survivor_mesh).
            from .multihost import survivor_mesh
            new_mesh = survivor_mesh(self.mesh, live, device_axis=self.axis)
        else:
            from .mesh import make_mesh
            devices = list(self.mesh.devices.reshape(-1)[live])
            new_mesh = make_mesh({self.axis: len(live)}, devices=devices)
        # host round trip: donated buffers live on the OLD mesh; numpy
        # copies re-place cleanly when the shrunk round first runs
        self.params = jax.device_get(self.params)
        self.state = jax.device_get(self.state)
        self.history = jax.device_get(self.history)
        self.mesh = new_mesh
        self._jit_round = None
        self._jit_train = None
        self._jit_eval = None
        self._comms_registered = False      # re-register with the new n
        self.elastic.reset_world(len(live))
        if self.metrics is not None:
            self.metrics.log("membership", kind="mesh_shrunk",
                             from_world=old, to_world=len(live),
                             unit=self.elastic_unit)
        self.log(f"elastic: mesh shrunk {old} -> {len(live)} "
                 f"{self.elastic_unit}s; the next round recompiles at "
                 "the new world size")
        return True

    def _mesh_host_procs(self):
        """mesh host row -> owning process id (None when a row's
        devices span processes, or on 1-D meshes)."""
        if self.host_axis is None:
            return None
        rows = self.mesh.devices
        procs = []
        for h in range(rows.shape[0]):
            owners = {d.process_index for d in rows[h].flat}
            procs.append(owners.pop() if len(owners) == 1 else None)
        return procs

    def _heartbeat_gate(self, timeout=None):
        """The no-hang contract: arrive at this round's rendezvous and
        wait until every live peer host arrived or its lease expired.
        Lease-dead hosts are evicted at host granularity (zero
        recompiles — the alive mask is an input); when a dead PROCESS
        owns devices of the training mesh, the survivors additionally
        shrink the mesh before dispatching, because a collective over a
        dead process's devices would hang forever. QuorumLost
        propagates to run(), which drives the coordinated restart.

        In the async bounded-staleness mode the caller passes
        ``timeout=0``: arrival is still announced (peers read our round
        version from it) and lease-expired peers are still evicted, but
        the round NEVER waits for stragglers — that is the whole
        point; their contributions are staleness-discounted at the
        exchange instead."""
        from ..resilience.elastic import QuorumLost
        hb = self.heartbeat
        if getattr(self, "_grow_pending", False):
            # late joiner (--grow): fast-forward to the running world's
            # front before the first gate — incumbents' gates accept
            # any arrival at round >= theirs, so the no-hang contract
            # holds from the joiner's very first rendezvous
            self._grow_pending = False
            front = hb.peer_round_max()
            if front >= 0:
                self.log(f"grow: fast-forwarding from round "
                         f"{self._round_idx} to the running world's "
                         f"front (round {front + 1})")
                self._round_idx = front + 1
        if self._relay is not None and self.elastic is not None:
            # grow-mid-run: a fresh out-of-world lease is a late-started
            # --grow process asking in. Admission is pure host-side
            # bookkeeping (the alive mask and the view arrays extend),
            # so the compiled round never recompiles.
            for j in hb.poll_joiners():
                if hb.admit_host(j):
                    self.elastic.admit(j, self._round_idx, via="grow")
        if self.elastic is not None and self.elastic.n == hb.n:
            expect = set(self.elastic.live())
        else:
            expect = set(range(hb.n))
        res = hb.gate(self._round_idx, expect=expect, timeout=timeout)
        if self.health is not None:
            alive_now, ages = hb.view()
            self.health.observe_hosts(self._round_idx, alive=alive_now,
                                      lease_age_s=ages,
                                      lease_s=hb.lease_s,
                                      wait_s=res.wait_s)
        quorum_err = None
        for h in res.dead:
            if self.elastic is None or not (0 <= h < self.elastic.n):
                continue
            try:
                self.elastic.evict(h, self._round_idx, "lease_expired")
            except QuorumLost as e:
                quorum_err = e          # survivors still shrink/snapshot
        if res.dead and self.host_axis is not None and \
                jax.process_count() > 1 and self._relay is None:
            from .mesh import is_local_mesh
            if not is_local_mesh(self.mesh):
                procs = self._mesh_host_procs()
                dead_rows = [h for h, p in enumerate(procs)
                             if p in res.dead]
                if dead_rows and quorum_err is None and \
                        self.elastic is not None:
                    self.shrink_to_survivors()
        if quorum_err is not None:
            raise quorum_err

    def _enqueue_round(self, batches):
        """Dispatch one compiled round of tau steps under the step span
        (the enqueue counts laying the batches over the mesh) and advance
        ``iter``. -> (the closed span, loss, aux)."""
        with self._step_span() as span:
            if self._jit_round is None:
                self._jit_round = self._build_round(batches)
            self.rng, key = jax.random.split(self.rng)
            shard_axes = (self.host_axis, self.axis) \
                if self.host_axis is not None else self.axis
            span.phase("solver.enqueue")
            dev = shard_batch(batches, self.mesh, shard_axes, batch_dim=1)
            args = (self.params, self.state, self.history, dev,
                    jnp.asarray(self.iter, jnp.int32), key,
                    self._alive_mask(), self._staleness_lag())
            span.watch(self._jit_round, args)
            self.params, self.state, self.history, loss, aux = \
                self._jit_round(*args)
            self.iter += self.tau
        return span, loss, aux

    def _train_round_relay(self, batches):
        """The cross-host tier over the rendezvous directory
        (heartbeat.FileConsensus): run the LOCAL compiled round (tier 1
        — this fault domain's devices, per-step pmean), then post the
        result and adopt the masked cross-host average. Same math as
        the compiled masked consensus, on the transport the paper
        itself used (a driver-mediated collect & broadcast every tau
        steps)."""
        import math as _m
        import time as _t
        t0 = _t.perf_counter()
        _, loss, _ = self._enqueue_round(batches)
        # tier 2: fetch (replicated locally — one local device read),
        # exchange through the directory, adopt the consensus
        leaves_p, tdef_p = jax.tree_util.tree_flatten(
            jax.device_get(self.params))
        leaves_s, tdef_s = jax.tree_util.tree_flatten(
            jax.device_get(self.state))
        payload = [np.asarray(x) for x in leaves_p + leaves_s]
        tdef_h = None
        if self.average_history:
            leaves_h, tdef_h = jax.tree_util.tree_flatten(
                jax.device_get(self.history))
            payload += [np.asarray(x) for x in leaves_h]
        local_loss = float(jax.device_get(loss))
        valid = _m.isfinite(local_loss) and \
            all(np.all(np.isfinite(x)) for x in payload)
        alive = self.elastic.live() if self.elastic is not None \
            else list(range(self.heartbeat.n))
        xt0 = _t.perf_counter()
        consensus, aux = self._relay.exchange(
            self._round_idx, payload, valid, local_loss, alive)
        if self.metrics is not None:
            # the cross-host IO tier, timed on its own: the fleet
            # merger renders this as the consensus/relay track and
            # critpath.py splits it out of the round's wall time
            self.metrics.log(
                "relay_io", round=self._round_idx,
                host=self.heartbeat.host,
                seconds=round(_t.perf_counter() - xt0, 4),
                bytes=int(sum(x.nbytes for x in payload)),
                mono=self.heartbeat.clock.monotonic())
        np_ = len(leaves_p)
        ns = np_ + len(leaves_s)
        self.params = jax.tree_util.tree_unflatten(tdef_p, consensus[:np_])
        self.state = jax.tree_util.tree_unflatten(tdef_s,
                                                  consensus[np_:ns])
        if tdef_h is not None:
            self.history = jax.tree_util.tree_unflatten(tdef_h,
                                                        consensus[ns:])
        wl = np.asarray(aux["worker_loss"], np.float64)
        vv = np.asarray(aux["valid"], np.float64) > 0
        round_loss = float(np.nanmean(wl[vv])) if vv.any() \
            else local_loss
        self._obs_step(_t.perf_counter() - t0, round_loss, batches)
        out = self._chaos_loss(jnp.float32(round_loss))
        self._observe_sync_round(
            dict(aux, kind="params"),
            round_s=_t.perf_counter() - t0, round_idx=self._round_idx)
        self._round_idx += 1
        return out

    def train_round(self, batches):
        """One outer round. ``batches``: dict of arrays with leading axes
        (tau, global_batch, ...) — tau steps, batch dim sharded across
        workers (over host x device in hierarchical mode; multi-process
        callers feed their own host rows). Returns mean per-worker loss
        over the round."""
        import time as _t
        batches = {k: np.asarray(v) for k, v in batches.items()}
        async_on = self.staleness is not None
        if self.heartbeat is not None:
            # the round gate: never dispatch a cross-host collective
            # until every supposedly-live peer host has arrived (or its
            # lease expired and it was evicted) — a dead peer must cost
            # an eviction, not a hang inside the collective. The async
            # mode gates with timeout=0: arrival is announced and
            # lease-dead peers are evicted, but stragglers are never
            # waited for (their pushes get staleness-discounted instead)
            self._heartbeat_gate(timeout=0.0 if async_on else None)
        if self._relay is not None:
            return self._train_round_relay(batches)
        span, loss, aux = self._enqueue_round(batches)
        t0 = _t.perf_counter() - span.host_s    # when the enqueue began
        self._obs_step(span.host_s, loss, batches)
        loss = self._chaos_loss(loss)   # may stall (the injected straggler)
        if self.chaos is not None and not async_on:
            # a chaos slow_worker under the SYNCHRONOUS barrier is a
            # real per-round host stall: the collect & average waits for
            # the straggler, so round latency tracks the max worker —
            # exactly the failure mode the async mode absorbs
            self.chaos.maybe_slow_worker(self._round_idx)
        aux = dict(aux, kind="params") if aux else None
        if async_on and self.elastic is not None:
            aux = self._observe_staleness_round(
                aux, _t.perf_counter() - t0)
        if aux:
            # once per sync round (rounds are coarse; the fetch is a few
            # scalars): divergence event + straggler/skew/trend detectors
            self._observe_sync_round(
                aux, round_s=_t.perf_counter() - t0,
                round_idx=self._round_idx)
        self._round_idx += 1
        return loss

    def _observe_staleness_round(self, aux, round_s):
        """Async-mode per-round bookkeeping: advance the per-worker
        version clocks (a chaos slow_worker pays its seconds on ITS
        clock, never the host loop's), run the park/unpark controller,
        attach the lag/park state to the round aux (drift attribution +
        the health detectors), and emit the ``staleness`` metrics event
        the report/monitor staleness sections render. QuorumLost (a
        chronically-parked worker evicted below quorum) propagates."""
        el = self.elastic
        slow = self.chaos.slow_worker_spec(self._round_idx) \
            if self.chaos is not None else None
        lag_used = el.lag()             # the lag the round's weights saw
        el.advance_versions(self._round_idx, round_s, slow=slow)
        el.observe_staleness(self._round_idx)
        aux = dict(aux) if aux else {"kind": "params"}
        aux["lag"] = [int(x) for x in lag_used]
        aux["parked"] = [int(w) for w in np.nonzero(el.parked)[0]]
        if self.metrics is not None:
            self.metrics.log(
                "staleness", round=self._round_idx, s=el.staleness,
                version=[int(v) for v in el.version],
                lag=[int(x) for x in el.lag()],
                parked=aux["parked"],
                park_rounds=[int(r) for r in el.park_rounds],
                weight=[round(float(x), 4)
                        for x in el.consensus_weights()])
        return aux

    def run(self, num_rounds, batch_fn, test_data_fn=None, test_every=10,
            snapshot_prefix=None, snapshot_every=0, resume=None,
            reshard="strict",
            sigint="stop", sighup="snapshot", sigterm="snapshot_stop"):
        """The reference driver loop (CifarApp.scala:92-135): for each round,
        optionally test (every ``test_every`` rounds, :98), then train tau
        steps per worker. ``batch_fn(tau)`` -> batches dict as above.

        Fault tolerance (the opposite of the reference's
        spark.task.maxFailures=1 contract):
          * resume="auto" restores the newest valid snapshot under the
            prefix before the first round (a path restores that
            snapshot); reshard="auto" additionally accepts a snapshot
            stamped by a DIFFERENT world and re-partitions it for this
            one (resilience/checkpoint.reshard_for_world) instead of
            refusing with WorldMismatch
          * signals are polled BETWEEN rounds: SIGHUP snapshots, SIGINT
            stops cleanly, SIGTERM (a preemption notice) snapshots then
            stops — pair with `--resume auto` on relaunch
          * snapshot_every=N also snapshots every N completed rounds
          * an armed RecoveryPolicy (arm_recovery) rolls a NaN/exploding
            round back and redoes it instead of averaging poison
          * an armed ElasticPolicy (arm_elastic) makes every round
            quorum-based: sick workers are evicted from the consensus
            and readmitted after a cooldown; QuorumLost (exit 4) aborts
            the loop after a best-effort snapshot. With shrink_after
            set, persistent evictions shrink the mesh over the
            survivors — batch_fn must then size batches off
            solver.mesh.shape (the live world).
        """
        from ..utils.signals import SignalPolicy
        from ..resilience import checkpoint
        from ..resilience.elastic import QuorumLost
        prefix = snapshot_prefix or (self.param.snapshot_prefix
                                     if self.param.has("snapshot_prefix")
                                     else None)
        if resume == "auto":
            if prefix:
                checkpoint.resume_auto(self, prefix, log_fn=self.log,
                                       reshard=reshard)
            else:
                self.log("resume auto: no snapshot prefix; starting fresh")
        elif resume:
            self.restore(resume, reshard=reshard)
        r = 0
        with SignalPolicy(sigint=sigint, sighup=sighup,
                          sigterm=sigterm) as policy:
            while r < num_rounds:
                if test_data_fn is not None and r % test_every == 0 \
                        and self.test_net is not None:
                    scores = self.test(test_data_fn())
                    for k, v in scores.items():
                        self.log(f"round {r}: test {k} = {v}")
                try:
                    loss = self.train_round(batch_fn(self.tau))
                except QuorumLost:
                    # the consensus up to here is good — keep it. The
                    # designated writer commits it; every survivor then
                    # barriers on the manifest's sha256 (coordinated
                    # restart), so all of them exit 4 holding the SAME
                    # resumable snapshot for the supervisor relaunch.
                    if prefix:
                        self.snapshot(prefix=prefix)
                        self.coordinated_restart(prefix)
                    raise
                if self.elastic is not None and self.elastic.should_shrink():
                    self.shrink_to_survivors()
                v = float(loss)
                if self.watchdog is not None:
                    self.watchdog.beat(v)
                if self.recovery is not None and \
                        self.recovery.observe(self, v):
                    self.log(f"round {r}: rolled back to iter {self.iter}; "
                             "redoing the round")
                    continue
                self.log(f"round {r}: mean local loss = {v:.6g}")
                r += 1
                if self.chaos is not None:
                    self.chaos.maybe_sigterm(r)
                action = policy.pending()
                if prefix and (action in ("snapshot", "snapshot_stop") or
                               (snapshot_every and
                                r % snapshot_every == 0)):
                    self.snapshot(prefix=prefix)
                if action in ("stop", "snapshot_stop"):
                    self.log(f"stopping on signal after round {r}")
                    break
