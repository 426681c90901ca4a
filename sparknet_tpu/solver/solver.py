"""Solver: training orchestration around ONE jitted train step.

The TPU-native replacement for the reference Solver::Step loop
(solver.cpp:193-253): ClearParamDiffs / iter_size x ForwardBackward / loss
smoothing / ApplyUpdate all collapse into a single compiled XLA program per
step — grads via jax.grad, iter_size accumulation via lax.scan, the lr
schedule traced on the iteration index (no recompiles). Evaluation mirrors
the SparkNet-added Solver::TestAndStoreResult (solver.cpp:414-444): run the
TEST-phase net test_iter times and average its output blobs.

Buffer donation keeps params/history resident in HBM across steps — the
analog of Caffe never leaving the GPU between iterations, minus the JVM/JNA
weight copies (Net.scala:126-148) that the reference paid per sync round.
"""

import collections
import os
import re
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..proto import Message, text_format, wire
from ..graph.compiler import CompiledNet, TRAIN, TEST, array_to_blob, \
    blob_to_array
from .lr_policy import make_lr_fn
from .updates import Updater, canonical_type, accum_init, accum_add


def resolve_nets(sp, base_dir="", net_param=None):
    """Resolve train/test NetParameters from a SolverParameter, honoring the
    field precedence of reference solver.cpp InitTrainNet/InitTestNets:
    train_net_param > train_net > net_param > net."""
    def load(path):
        return text_format.load(os.path.join(base_dir, path), "NetParameter")

    train = test = None
    if net_param is not None:
        train = test = net_param
    elif sp.has("train_net_param"):
        train = sp.train_net_param
    elif sp.has("train_net"):
        train = load(sp.train_net)
    elif sp.has("net_param"):
        train = test = sp.net_param
    elif sp.has("net"):
        train = test = load(sp.net)
    if train is None:
        raise ValueError("solver specifies no train net")
    if sp.test_net_param:
        test = sp.test_net_param[0]
    elif sp.test_net:
        test = load(sp.test_net[0])
    return train, test


def sp_test_scheduled(sp):
    """Does the solver schedule testing (test_iter/test_interval set)?"""
    return bool(sp.test_iter) or int(sp.test_interval) > 0


class Solver:
    """Drives training of one net per the SolverParameter schedule.

    data iterators yield batch dicts {blob_name: array}; see
    CompiledNet.feed_blobs() for required keys.
    """

    def __init__(self, solver_param, net_param=None, feed_shapes=None,
                 test_feed_shapes=None, base_dir="", dtype=jnp.float32,
                 log_fn=print, metrics=None, compute_dtype=None,
                 tracer=None, remat=None):
        entry_ns = time.perf_counter_ns()       # the ring's clock
        self.param = solver_param
        self.log = log_fn or (lambda *a: None)
        # structured observability hooks: a JSONL MetricsLogger (or
        # path) armed by default from the CLI, with step accounting +
        # comms metering over it (sparknet_tpu.obs), an optional Watchdog
        # that step() beats once per iteration — and the span tracer,
        # which is always on: the caller's, one over this solver's own
        # metrics stream, or the process-wide default
        self._own_metrics = isinstance(metrics, str)
        if isinstance(metrics, str):
            from ..utils.metrics import MetricsLogger
            metrics = MetricsLogger(metrics)
        self.metrics = metrics
        from ..obs.trace import Tracer, default_tracer, package_import
        if tracer is None:
            tracer = Tracer(self.metrics) if self.metrics is not None \
                else default_tracer()
        self.tracer = tracer
        # what the process did before it had a solver: its imports, the
        # builder's NetParameter (once a process)
        package_import(tracer, entry_ns)
        self.stepstats = self.comms = None
        self._comms_registered = False
        # training-dynamics health layer (obs divergence/health/memstats):
        # armed by default with metrics; sharded solvers compute the
        # divergence aux inside their compiled sync round and this base
        # class fetches/emits it on the step-sample cadence
        self.divergence = self.health = self.memstats = None
        self.last_divergence = None
        if self.metrics is not None:
            from ..obs import (StepAccounting, CommsMeter, DivergenceMeter,
                               HealthMonitor, MemoryMonitor)
            self.stepstats = StepAccounting(self.metrics, tracer=tracer)
            self.comms = CommsMeter(self.metrics)
            self.divergence = DivergenceMeter(self.metrics)
            self.health = HealthMonitor(self.metrics, log_fn=self.log,
                                        solver=self)
            self.memstats = MemoryMonitor(self.metrics, tracer=tracer)
        self.watchdog = None
        # resilience hooks (sparknet_tpu.resilience): keep-N snapshot
        # retention (None = keep all), an optional RecoveryPolicy armed via
        # arm_recovery(), an optional ElasticPolicy armed via arm_elastic()
        # (quorum-based sync rounds on sharded solvers), and the
        # process-wide chaos injector (None unless --chaos /
        # SPARKNET_CHAOS armed one)
        self.snapshot_keep = None
        self.recovery = None
        self.elastic = None
        # bounded-staleness async mode (resilience/elastic.py, ISSUE 7):
        # None = synchronous rounds; an int s >= 0 (arm_staleness) makes
        # the sharded consensus a staleness-weighted average — workers
        # push versioned contributions, stale ones are discounted, over-
        # stale ones are parked, and the round never waits on a straggler
        self.staleness = None
        self.s_decay = 0.5
        # host-level fault domains (resilience/heartbeat.py), armed via
        # arm_heartbeat(): leased liveness for every peer process, the
        # pre-round rendezvous gate, and the coordinated-restart barrier
        self.heartbeat = None
        from ..resilience.chaos import active_chaos
        self.chaos = active_chaos()
        train_np, test_np = resolve_nets(solver_param, base_dir, net_param)
        with self.tracer.hot_span("solver.init"):
            with self.tracer.hot_span("net.build"):
                # NetState from the solver (reference solver.cpp
                # InitTrainNet / InitTestNets: train_state / test_state
                # merge into the filter state — e.g.
                # mnist_autoencoder_solver's per-test-net 'test-on-train'
                # /'test-on-test' stages select among same-named Data
                # layers). Like the single test_net, only test_state[0]
                # is instantiated here.
                ts = solver_param.train_state \
                    if solver_param.has("train_state") else None
                self.net = CompiledNet(
                    train_np, TRAIN, feed_shapes=feed_shapes, dtype=dtype,
                    compute_dtype=compute_dtype,
                    level=int(ts.level) if ts else 0,
                    stages=tuple(ts.stage) if ts else ())
                self.test_net = None
                if test_np is not None:
                    es = solver_param.test_state[0] \
                        if solver_param.test_state else None
                    try:
                        self.test_net = CompiledNet(
                            test_np, TEST,
                            feed_shapes=test_feed_shapes or feed_shapes,
                            dtype=dtype, compute_dtype=compute_dtype,
                            level=int(es.level) if es else 0,
                            stages=tuple(es.stage) if es else ())
                    except ValueError:
                        # a shared `net` whose data layer is TRAIN-only
                        # has no TEST-phase graph; without a test_iter
                        # schedule the reference never instantiates test
                        # nets at all (solver.cpp InitTestNets), so
                        # train-only it is
                        if sp_test_scheduled(solver_param):
                            raise
                        self.log("No TEST-phase net; training without a "
                                 "test net")
                # which part of a step each layer's device time counts
                # under (graph/compiler.py:PART_OF_TYPE), once a net
                now = self.tracer.now_ns()
                self.tracer.record("net.parts", now, now, net=self.net.name,
                                   parts=self.net.parts())
                # a multi-token-prediction module, where the net has one
                depths = self.net.prediction_depths()
                if depths:
                    self.tracer.record(
                        "lm.mtp", now, now, net=self.net.name,
                        depth=len(depths), loss_weight=depths[0][1],
                        losses=[name for name, _ in depths],
                        shared=self.net.shared_params())
            if remat is not None:
                # the policy `set_remat` takes, said where the solver is
                # built (no jit exists yet, so nothing to rebuild)
                from ..graph.compiler import REMAT_POLICIES
                if remat not in REMAT_POLICIES:
                    raise ValueError(f"remat policy {remat!r}: want one "
                                     f"of {REMAT_POLICIES}")
                self.net.remat = remat
            seed = int(solver_param.random_seed)
            self.rng = jax.random.PRNGKey(seed if seed >= 0 else
                                          int(time.time_ns() % (2 ** 31)))
            self.rng, init_key = jax.random.split(self.rng)
            with self.tracer.hot_span("net.init"):
                self.params, self.state = self.net.init(init_key)

        # a sibling after `solver.init`, which reads what it read: the
        # updater's state is 100 to 370 more one-blob fill programs
        with self.tracer.hot_span("solver.history"):
            mults = {}
            for lname, refs in self.net.param_refs.items():
                owned = [k for k in refs if k[0] == lname]
                if owned:
                    mults[lname] = [
                        (self.net.param_meta[k][2],
                         self.net.param_meta[k][3])
                        for k in owned]
            # layers that keep statistics in their state for the tracer
            # (ops/moe.py): read where `step` already waits for a loss
            self._monitors = [(lp.name, impl.monitor)
                              for lp, impl, _, _ in self.net.layers
                              if getattr(impl, "monitor", None)]
            self.updater = Updater(solver_param, mults)
            self.history = self.updater.init(self.params)
        self.lr_fn = make_lr_fn(solver_param)
        self.iter = 0
        self._smoothed = collections.deque(
            maxlen=max(1, int(solver_param.average_loss)))
        self._jit_train = None
        self._jit_eval = None
        # optional on-device input transforms (data/device_transform.py):
        # pure fns applied to the feed dict INSIDE the jitted step, letting
        # the host ship raw uint8 records + tiny offset arrays instead of
        # float32 crops (3-4x fewer H2D bytes)
        self.input_transform = None
        self.test_input_transform = None
        self._raw_feed_shapes = None
        # async-dispatch discipline: fetching ANY value from the device
        # stalls the host until the step queue has drained, so the
        # step loop only materializes a loss at display points, or every
        # _sync_stride steps when display is off. Dispatches queue ahead in
        # between — that queue IS the transfer/compute overlap. The NaN
        # watchdog consequently sees losses with up to that much lag.
        self._sync_stride = max(1, int(os.environ.get(
            "SPARKNET_SYNC_STRIDE", "100")))
        # iteration counter kept ON DEVICE: feeding a fresh host scalar
        # every step is a blocking H2D put; a resident counter is free
        self._it_dev = None

    def _record_monitors(self):
        """Inside a ``solver.fetch`` span, after the loss has come: the
        statistics that layers keep in their state, one instant a layer
        (``moe.load``: share of the token-expert pairs on held experts,
        largest over mean held load). The step has finished by then, so
        this waits for nothing."""
        for lname, (event, fields) in self._monitors:
            vals = jax.device_get(self.state[lname][0])
            self.tracer.instant(event, layer=lname, **{
                f: float(v) for f, v in zip(fields, vals)})

    def smoothed_loss(self):
        """Mean of the average_loss-window losses (one device fetch), or
        None before any step — the value the display line prints."""
        if not self._smoothed:
            return None
        return float(jnp.mean(jnp.stack(
            [jnp.asarray(x) for x in self._smoothed])))

    def set_input_transform(self, fn, raw_overrides=None, test_fn=None):
        """Install on-device input transforms (before any step compiles).
        fn/test_fn: pure fn(batch dict) -> net feed dict; raw_overrides:
        {blob: raw shape} check_batch overrides for the pre-transform feed
        (e.g. the uint8 source extent + '#y'/'#x'/'#flip' aux arrays)."""
        self.input_transform = fn
        self.test_input_transform = test_fn
        self._raw_feed_shapes = dict(raw_overrides) if raw_overrides else None

    def _set_net_knob(self, attr, value):
        """Set a trace-time perf knob on every CompiledNet this solver
        owns and DROP the compiled steps. The policy is read once per
        trace (graph/compiler.py), so flipping it under a live jit would
        silently keep serving the old trace; rebuilding gives the new
        policy a FRESH executable whose cache starts empty — a
        mid-process toggle costs exactly one recompile and cannot leak
        stale cache entries (tests/test_remat.py asserts both)."""
        for name in ("net", "test_net", "local_net", "local_test_net"):
            n = getattr(self, name, None)
            if n is not None:
                setattr(n, attr, value)
        self._jit_train = None
        self._jit_eval = None
        if hasattr(self, "_jit_round"):
            self._jit_round = None

    def set_remat(self, policy):
        """Set the remat policy (the --remat CLI knob): "none", "dots"
        (save matmul outputs, recompute elementwise tails), or "full"
        (recompute a block in the backward pass). Under "dots" and "full"
        the outputs of a block's pallas kernels are kept, so a kernel's
        forward runs once (graph/remat.py): a flash pass's output, the
        size of q, and 4 bytes a row a head of logsumexp; the delta
        rule's output, group states and inverses — memory held from a
        layer's forward to its backward that a bare jax.checkpoint did not
        hold. Overrides the SPARKNET_REMAT env-var fallback."""
        from ..graph.compiler import REMAT_POLICIES
        if policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat policy {policy!r}: want one of {REMAT_POLICIES}")
        self._set_net_knob("remat", policy)

    def set_scan(self, mode):
        """Set the scan-over-layers mode: "auto" (TPU only), "on", or
        "off". Overrides the SPARKNET_SCAN env-var fallback."""
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"scan mode {mode!r}: want auto|on|off")
        self._set_net_knob("scan", mode)

    def _wrapped_loss(self, net):
        """net.loss_fn with the device-side input transform folded in."""
        tf = self.input_transform
        if tf is None:
            return net.loss_fn

        def lf(params, state, batch, rng):
            with jax.named_scope("input_transform"):
                batch = tf(batch)
            return net.loss_fn(params, state, batch, rng)
        return lf

    # -- compiled steps ----------------------------------------------------
    def _build_train_step(self):
        return jax.jit(self._train_step_fn(), donate_argnums=(0, 1, 2))

    def _memory_step_fn(self, batch):
        """The lowerable jit behind train_step (None when this solver
        wraps its jit in a closure and no step has traced yet)."""
        if self._jit_train is None:
            self._jit_train = self._build_train_step()
        return self._jit_train

    def _memory_step_args(self, batch):
        return (self.params, self.state, self.history, batch,
                jnp.asarray(self.iter, jnp.int32), self.rng)

    def compiled_memory_stats(self, batch):
        """Per-device memory footprint of the COMPILED train step from
        XLA's memory_analysis: argument/output/temp/aliased bytes plus
        the peak-HBM proxy arg + out + temp - aliased (params, state
        and history are donated, so their output copies alias the
        inputs). This is the number that says whether a model FITS —
        bench rows and the FSDP does-not-fit proof both read it. On
        backends whose executable does not expose a memory analysis,
        returns None. Lowering does not execute anything; the
        persistent compile cache absorbs the second compile."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        fn = self._memory_step_fn(batch)
        if fn is None or not hasattr(fn, "lower"):
            return None
        try:
            ma = fn.lower(*self._memory_step_args(batch)) \
                   .compile().memory_analysis()
        except NotImplementedError:
            return None
        if ma is None:
            return None
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
        ali = int(ma.alias_size_in_bytes)
        return {"argument_bytes": arg, "output_bytes": out,
                "temp_bytes": tmp, "alias_bytes": ali,
                "peak_bytes": arg + out + tmp - ali}

    def op_scopes(self, batch):
        """{HLO instruction name: op path} of the COMPILED train step,
        e.g. "fusion.306" -> "jit(step)/transpose(jvp(conv1))/
        conv_general_dilated": the layer (its ``jax.named_scope``) and the
        direction (``jvp`` forward, ``transpose(jvp(...))`` backward,
        ``update``, ``input_transform``) of what a device trace lists
        under XLA's own names. jax.profiler.ProfileData shows a TPU
        event's instruction name and no path (the raw XSpace keeps it as
        ``tf_op`` on the event's metadata), so a reducer joins on this. A
        fusion answers with the path of its root; what XLA made itself
        (a relayout) has none. jax leaves metadata out of the persistent
        cache's key: an executable loaded from a cache that an older
        commit wrote answers with the paths of the compile that wrote it
        (PERF.md section 3)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        text = self._memory_step_fn(batch).lower(
            *self._memory_step_args(batch)).compile().as_text()
        return dict(re.findall(
            r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*'
            r'op_name="([^"]+)"', text, re.M))

    def _train_step_fn(self):
        """The pure (uncompiled) train step — subclasses re-jit it with
        sharding annotations (parallel.gspmd) or wrap it in shard_map."""
        iter_size = int(self.param.iter_size)
        net, updater, lr_fn = self.net, self.updater, self.lr_fn
        loss_fn = self._wrapped_loss(net)

        def one_grad(params, state, batch, rng):
            def lf(p):
                loss, (blobs, new_state) = loss_fn(p, state, batch, rng)
                return loss, new_state
            (loss, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            return loss, grads, new_state

        def step(params, state, history, batch, it, rng):
            if iter_size == 1:
                loss, grads, state = one_grad(params, state, batch, rng)
            else:
                # batch leading axis = iter_size micro-batches; accumulate
                # grads like reference solver.cpp:221-223 summing diffs —
                # in fp32 regardless of param dtype (updates.accum_init,
                # the mixed-precision contract; bitwise the old zeros_like
                # path for fp32 params).
                def body(carry, micro):
                    acc, state, i = carry
                    loss, g, state = one_grad(
                        params, state, micro, jax.random.fold_in(rng, i))
                    with jax.named_scope("grad_accum"):
                        acc = accum_add(acc, g)
                    return (acc, state, i + 1), loss
                with jax.named_scope("grad_accum"):
                    (grads, state, _), losses = jax.lax.scan(
                        body, (accum_init(params), state, 0), batch)
                    loss = jnp.mean(losses)
            with jax.named_scope("update"):
                rate = lr_fn(it)
            params, history = updater(params, grads, history, rate, it)
            with jax.named_scope("update"):
                return params, state, history, loss, it + 1

        return step

    def _build_debug_fn(self):
        """SolverParameter.debug_info — per-blob/param mean-|x| dump in
        the reference format (net.cpp ForwardDebugInfo :658 + param
        grads from BackwardDebugInfo). Deviations, documented: the
        reference prints EVERY step mid-pass; here the dump runs at
        display points only (each dump is a device fetch — per-step
        dumps would serialize the async dispatch pipeline this solver is
        built on), BEFORE the displayed iteration's update is applied,
        so data/diff norms describe the same params that produced the
        displayed loss. Dropout-style rng layers draw a different key
        than the training step did, so their norms are same-distribution
        rather than bit-identical. One fused jit computes every norm in
        a single device program."""
        net = self.net
        tf = self.input_transform

        # static label lists, in net layer order (jit outputs are lists
        # of scalars in the same order). Labels carry the layer's SLOT
        # index (the reference prints every slot, shared or owned);
        # positional index into params[ln] rides along separately.
        fwd_keys = [(lp.name, t) for lp, _, _, tops in net.layers
                    for t in tops]
        prm_keys = []            # (label_lname, slot, owner, owner_pos)
        for lp, _, _, _ in net.layers:
            for slot, key in enumerate(net.param_refs[lp.name]):
                owner = key[0]
                owner_owned = [k for k in net.param_refs.get(owner, [])
                               if k[0] == owner]
                if key in owner_owned:
                    prm_keys.append((lp.name, slot, owner,
                                     owner_owned.index(key)))

        def dbg(params, state, batch, rng):
            b = tf(batch) if tf is not None else batch

            def lf(p):
                loss, (blobs, _) = net.loss_fn(p, state, b, rng)
                return loss, blobs
            (loss, blobs), grads = jax.value_and_grad(
                lf, has_aux=True)(params)

            def mabs(x):
                return jnp.mean(jnp.abs(jnp.asarray(x, jnp.float32)))
            fwd = [mabs(blobs[t]) if t in blobs else jnp.float32(0)
                   for _, t in fwd_keys]
            prm = [mabs(params[ow][pos]) for _, _, ow, pos in prm_keys]
            gds = [mabs(grads[ow][pos]) for _, _, ow, pos in prm_keys]
            return fwd, prm, gds

        return jax.jit(dbg), fwd_keys, prm_keys

    def _print_debug_info(self, batch):
        if jax.process_count() > 1:
            if not getattr(self, "_dbg_warned", False):
                self._dbg_warned = True
                self.log("debug_info dump is single-process only; "
                         "skipping (per-host batch slices cannot feed "
                         "the global-shape debug program)")
            return
        if getattr(self, "_jit_debug", None) is None:
            self._jit_debug = self._build_debug_fn()
        dbg, fwd_keys, prm_keys = self._jit_debug
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        # ONE bulk fetch: per-line float() would pay a host round trip
        # per printed norm
        fwd, prm, grads = jax.device_get(
            dbg(self.params, self.state, batch, self.rng))
        for (lname, t), v in zip(fwd_keys, fwd):
            self.log(f"    [Forward] Layer {lname}, top blob {t} "
                     f"data: {float(v):.6g}")
        for (lname, slot, _, _), v in zip(prm_keys, prm):
            self.log(f"    [Forward] Layer {lname}, param blob {slot} "
                     f"data: {float(v):.6g}")
        for (lname, slot, _, _), v in zip(prm_keys, grads):
            self.log(f"    [Backward] Layer {lname}, param blob {slot} "
                     f"diff: {float(v):.6g}")

    def _build_eval_step(self):
        net = self.test_net
        tf = self.test_input_transform

        def ev(params, state, batch):
            if tf is not None:
                batch = tf(batch)
            blobs, _ = net.apply(params, state, batch, train=False)
            return {b: blobs[b] for b in net.output_blobs}

        return jax.jit(ev)

    def arm_watchdog(self, stall_seconds=300.0, **kw):
        """Start a stall/NaN watchdog that step() beats each iteration.
        With kill_on_stall and a configured snapshot_prefix, the exit path
        gets a best-effort emergency snapshot by default."""
        from ..utils.watchdog import Watchdog
        kw.setdefault("metrics", self.metrics)
        if kw.get("kill_on_stall") and "emergency_snapshot" not in kw \
                and self.param.has("snapshot_prefix"):
            kw["emergency_snapshot"] = self.snapshot
        self.watchdog = Watchdog(stall_seconds=stall_seconds, **kw).start()
        return self.watchdog

    # -- resilience (sparknet_tpu.resilience) ------------------------------
    def arm_recovery(self, policy=None, **kw):
        """Install a divergence RecoveryPolicy (NaN/explosion -> rollback
        to last-known-good). The state at arm time becomes the first
        known-good point, so even a first-step NaN has somewhere to go."""
        if policy is None:
            from ..resilience.recovery import RecoveryPolicy
            kw.setdefault("metrics", self.metrics)
            kw.setdefault("log_fn", self.log)
            policy = RecoveryPolicy(**kw)
        self.recovery = policy
        policy.note_good(self)
        return policy

    def arm_elastic(self, policy=None, **kw):
        """Install an elastic membership controller
        (resilience/elastic.py): the sync collectives become validity-
        masked quorum averages, sick workers are evicted/readmitted,
        and dropping below ``quorum`` raises QuorumLost (exit 4). Only
        sharded solvers (a data-axis mesh) act on it; arming rebuilds
        the compiled step/round so the membership aux is traced in.

        Hierarchical solvers (a host axis — parallel.multihost) declare
        elastic_axis/elastic_unit, so membership runs at HOST
        granularity; with the heartbeat relay armed the world spans the
        jax processes rather than the local mesh."""
        mesh = getattr(self, "mesh", None)
        axis = getattr(self, "elastic_axis", None) or \
            getattr(self, "axis", None)
        n = mesh.shape[axis] if mesh is not None and axis in mesh.shape \
            else 1
        if getattr(self, "_relay", None) is not None:
            n = self.heartbeat.n
        if policy is None:
            from ..resilience.elastic import ElasticPolicy
            kw.setdefault("metrics", self.metrics)
            kw.setdefault("log_fn", self.log)
            kw.setdefault("chaos", self.chaos)
            kw.setdefault("unit", getattr(self, "elastic_unit", "worker"))
            kw.setdefault("staleness", self.staleness)
            kw.setdefault("s_decay", self.s_decay)
            policy = ElasticPolicy(n_workers=n, **kw)
        self.elastic = policy
        self._jit_train = None
        if hasattr(self, "_jit_round"):
            self._jit_round = None
        return policy

    def arm_staleness(self, s, decay=0.5, unpark_after=1,
                      evict_parked_after=0):
        """Arm the asynchronous bounded-staleness update mode (`--
        staleness` next to `--tau`): the sharded consensus becomes a
        staleness-weighted average (resilience/elastic.py
        weighted_consensus) over versioned worker contributions — a
        worker ``lag`` rounds behind the fastest live peer is discounted
        by ``decay ** lag``, parked (weight 0, still a member) once
        ``lag > s``, resynced from the replicated consensus after
        ``unpark_after`` rounds, and evicted after
        ``evict_parked_after`` chronic parks (0 = never). s=0 is
        BIT-FOR-BIT the synchronous masked round. Arms elastic
        membership implicitly (quorum 1) when none is armed yet; the
        async file relay (heartbeat.AsyncFileConsensus) is upgraded in
        place when a synchronous relay was already armed."""
        self.staleness = max(0, int(s))
        self.s_decay = float(decay)
        if self.elastic is None:
            self.arm_elastic(quorum=1, unpark_after=unpark_after,
                             evict_parked_after=evict_parked_after)
        else:
            el = self.elastic
            el.staleness = self.staleness
            el.s_decay = self.s_decay
            el.unpark_after = max(1, int(unpark_after))
            el.evict_parked_after = max(0, int(evict_parked_after))
        if getattr(self, "_relay", None) is not None:
            from ..resilience.heartbeat import (AsyncFileConsensus,
                                                FileConsensus)
            if type(self._relay) is FileConsensus:
                self._relay = AsyncFileConsensus(
                    self._relay.coord, s=self.staleness,
                    decay=self.s_decay)
                self.log("staleness: upgraded the cross-host relay to "
                         "the versioned barrier-free delta exchange")
            elif isinstance(self._relay, AsyncFileConsensus):
                self._relay.s = self.staleness
                self._relay.decay = self.s_decay
        self._jit_train = None
        if hasattr(self, "_jit_round"):
            self._jit_round = None
        self.log(f"staleness: async bounded-staleness armed (s="
                 f"{self.staleness}, decay={self.s_decay})")
        return self.elastic

    def arm_heartbeat(self, directory, interval_s=0.5, lease_s=3.0,
                      relay="auto", grow=False, **kw):
        """Arm host-level fault domains (resilience/heartbeat.py): this
        process leases its liveness into ``directory`` (shared storage
        every host reaches), a monitor thread marks peer hosts dead on
        lease expiry, and sharded solvers gate every cross-host round
        on the rendezvous so a dead peer costs an eviction, never a
        hang inside a collective.

        relay: "auto" routes the tau-interval cross-host average
        through the directory (heartbeat.FileConsensus) when the
        backend has no multi-process collectives (multi-process CPU);
        True/False force it. Arm BEFORE arm_elastic so the membership
        world sizes to the process count.

        grow: this is a LATE JOINER (`--grow`) — an independent
        single-jax-process that grows an already-running world through
        the rendezvous dir instead of launching inside a
        jax.distributed fleet (which fixes membership at init and can
        never admit anyone). The joiner scans the fresh leases, takes
        host id max(existing)+1, forces the relay transport on, and
        fast-forwards its round counter to the running world's front
        at its first gate (LocalSGD); the incumbents' gates see the
        new lease and admit it (HeartbeatCoordinator.admit_host +
        ElasticPolicy.admit) with zero recompiles."""
        from ..resilience.heartbeat import (HeartbeatCoordinator,
                                            FileConsensus, fresh_leases)
        host = jax.process_index()
        n = jax.process_count()
        self._grow_pending = False
        if grow:
            if n > 1:
                self.log("heartbeat: WARNING — --grow ignored inside a "
                         f"{n}-process jax.distributed world (its "
                         "membership is fixed at init); launch the "
                         "joiner as a standalone single process")
            else:
                existing = fresh_leases(directory, lease_s)
                if existing:
                    host = max(existing) + 1
                    n = host + 1
                    relay = True if relay == "auto" else relay
                    self._grow_pending = True
                    self.log(f"heartbeat: joining a running world of "
                             f"{len(existing)} host(s) "
                             f"{sorted(existing)} as host {host}")
                else:
                    self.log("heartbeat: --grow found no fresh leases "
                             f"under {directory}; starting a new world")
        kw.setdefault("metrics", self.metrics)
        kw.setdefault("log_fn", self.log)
        kw.setdefault("chaos", self.chaos)
        coord = HeartbeatCoordinator(directory, host=host, n_hosts=n,
                                     interval_s=interval_s,
                                     lease_s=lease_s, **kw).start()
        self.heartbeat = coord
        if relay == "auto":
            from ..parallel.multihost import needs_host_relay
            relay = needs_host_relay()
        if relay and hasattr(self, "_train_round_relay"):
            if self.staleness is not None:
                from ..resilience.heartbeat import AsyncFileConsensus
                self._relay = AsyncFileConsensus(coord, s=self.staleness,
                                                 decay=self.s_decay)
                self.log(f"heartbeat: ASYNC relay consensus armed ({n} "
                         "hosts, versioned barrier-free delta exchange)")
            else:
                self._relay = FileConsensus(coord)
                self.log(f"heartbeat: relay consensus armed ({n} hosts "
                         "through the rendezvous directory)")
        if self.elastic is not None and self.elastic.n != n and \
                getattr(self, "_relay", None) is not None:
            self.log(f"heartbeat: WARNING — elastic world {self.elastic.n}"
                     f" != {n} processes; arm_heartbeat before "
                     "arm_elastic in relay mode")
        return coord

    def coordinated_restart(self, prefix, timeout=30.0):
        """Quorum loss in a multi-host world: barrier with every
        surviving process on the sha256 of the snapshot manifest under
        ``prefix`` before exiting 4, so a supervisor restart resumes
        ONE consistent world (resilience/heartbeat.restart_barrier).
        Single-process (or heartbeat-less) runs: a no-op True."""
        if self.heartbeat is None or jax.process_count() <= 1:
            return True
        from ..resilience.heartbeat import manifest_sha, restart_barrier
        sha = manifest_sha(prefix)
        agreed, _ = restart_barrier(self.heartbeat, sha, timeout=timeout)
        return agreed

    def _alive_mask(self):
        """The (n,) f32 alive mask the compiled step/round consumes —
        all ones without elastic membership, which keeps the masked
        average bit-for-bit the plain pmean. Sized to the mesh's
        membership axis (the host axis of hierarchical solvers); under
        the relay transport the policy world spans PROCESSES instead,
        so the local compiled round sees all-ones and membership is
        applied host-side at the exchange."""
        axis = getattr(self, "elastic_axis", None) or self.axis
        n = self.mesh.shape[axis]
        if self.elastic is not None and self.elastic.n == n:
            return jnp.asarray(self.elastic.alive_f32())
        return jnp.ones((n,), jnp.float32)

    def _staleness_lag(self):
        """The (n,) f32 per-worker version-lag vector the async compiled
        round consumes next to the alive mask — all zeros while the mode
        is off (which keeps the staleness weights exactly 1.0, the
        bit-for-bit anchor) or when the policy world spans processes
        (relay mode applies staleness host-side at the exchange)."""
        axis = getattr(self, "elastic_axis", None) or self.axis
        n = self.mesh.shape[axis]
        if self.staleness is not None and self.elastic is not None \
                and self.elastic.n == n:
            return jnp.asarray(self.elastic.lag(), jnp.float32)
        return jnp.zeros((n,), jnp.float32)

    def _observe_membership(self, aux, round_idx=None):
        """Feed the elastic membership controller one materialized
        round's validity/loss vectors. QuorumLost propagates — the run
        must stop — but nothing else may kill training."""
        if self.elastic is None or not aux:
            return
        from ..resilience.elastic import QuorumLost
        try:
            self.elastic.observe_round(
                round_idx if round_idx is not None else self.iter - 1,
                valid=aux.get("valid"),
                worker_loss=aux.get("worker_loss"))
        except QuorumLost:
            raise
        except Exception as e:
            self.log(f"elastic membership observation failed: {e!r}")

    def scale_lr(self, factor):
        """Scale the lr schedule by ``factor`` from now on. The schedule
        is traced into the compiled step, so the jitted programs are
        invalidated — one recompile per call (rollbacks are rare)."""
        base, factor = self.lr_fn, float(factor)
        self.lr_fn = lambda it: base(it) * factor
        self._jit_train = None
        if hasattr(self, "_jit_round"):
            self._jit_round = None

    def _chaos_loss(self, loss):
        """Apply armed per-step chaos injectors (stall, loss poisoning)
        to the step that just dispatched; no-op when chaos is off."""
        if self.chaos is None:
            return loss
        self.chaos.maybe_stall(self.iter - 1)
        if self.chaos.poison_loss(self.iter - 1):
            return jnp.asarray(float("nan"), jnp.float32)
        return loss

    def _maybe_recover(self, loss):
        """Feed a materialized loss to the recovery policy; True when the
        solver was rolled back (the caller should redo the work)."""
        if self.recovery is None or loss is None:
            return False
        return self.recovery.observe(self, float(loss))

    # -- observability (sparknet_tpu.obs) ----------------------------------
    def _register_comms(self, cm):
        """Declare this solver's per-round collective volume with the
        CommsMeter — overridden by sharded solvers; the base solver only
        has host->device feed traffic."""
        from ..obs.comms import tree_bytes
        cm.set_topology(strategy=type(self).__name__,
                        n_devices=jax.device_count(),
                        param_bytes=tree_bytes(self.params))

    def _obs_step(self, host_s, result, batch, aux=None):
        """Per-step hook called by every train_step/train_round variant:
        h2d byte counting, comms emission, step accounting. No-op (one
        attribute test) when metrics is off. ``aux``: the sync round's
        on-device divergence stats (sharded solvers) — fetched only at
        step-sample points, where the host already paid the device
        sync, so the async-dispatch discipline is preserved."""
        if self.stepstats is None:
            return
        if not self._comms_registered:
            self._comms_registered = True
            try:
                self._register_comms(self.comms)
            except Exception as e:      # accounting must never kill a run
                self.log(f"comms registration failed: {e!r}")
        it = self.iter - 1
        from ..obs.comms import tree_bytes
        self.comms.add_h2d(tree_bytes(batch))
        self.comms.tick(it)
        sampled = self.stepstats.observe(it, host_s, result=result)
        if sampled:
            if self.memstats is not None:
                try:
                    self.memstats.sample(it)
                except Exception as e:
                    self.log(f"memstats sampling failed: {e!r}")
            if aux:
                self._observe_sync_round(aux)

    def _round_latencies(self, round_s):
        """Per-worker latencies for the just-finished sync round, or None
        when the solver has no per-worker attribution. Base solvers have
        one worker; LocalSGDSolver overrides with chaos-stall (and, in
        real fleets, per-host timer) attribution."""
        return None

    def _observe_sync_round(self, aux, round_s=None, round_idx=None):
        """Fetch one sync round's on-device aux stats (a few scalars),
        feed the elastic membership controller, emit the ``divergence``
        event, and feed the health detectors. Called by _obs_step at
        sample points (per-step solvers) or once per round
        (LocalSGDSolver). Only QuorumLost — the membership verdict that
        the run must stop — escapes into the step loop."""
        if not aux:
            return None
        try:
            aux = jax.device_get(aux)
        except Exception as e:          # monitoring must never kill a run
            self.log(f"sync-round aux fetch failed: {e!r}")
            return None
        # membership first: eviction decisions (and the QuorumLost
        # abort) must not depend on the metrics stream being armed.
        # The health detectors below still judge this round against the
        # membership that was IN FORCE while it ran — a worker evicted
        # or readmitted just now must not alarm against the new mask.
        alive_during_round = self.elastic.alive.copy() \
            if self.elastic is not None else None
        self._observe_membership(aux, round_idx)
        if self.divergence is None:
            return None
        try:
            d = self.divergence.observe(
                self.iter - 1, aux, kind=aux.get("kind", "params"),
                tau=getattr(self, "tau", None), round_idx=round_idx)
            self.last_divergence = d
            if self.health is not None:
                self.health.observe_round(
                    self.iter - 1, round_idx=round_idx,
                    worker_losses=aux.get("worker_loss"),
                    latencies=self._round_latencies(round_s)
                    if round_s is not None else None,
                    divergence=d, valid=aux.get("valid"),
                    alive=alive_during_round,
                    lag=aux.get("lag"), parked=aux.get("parked"),
                    staleness=self.staleness)
            return d
        except Exception as e:          # monitoring must never kill a run
            self.log(f"divergence observation failed: {e!r}")
            return None

    def arm_health(self, **kw):
        """(Re)configure the health detectors (CLI --health-* flags).
        Replaces the default monitor, preserving the metrics sink; pass
        enabled=False to disarm."""
        if not kw.pop("enabled", True):
            self.health = None
            return None
        from ..obs import HealthMonitor
        kw.setdefault("log_fn", self.log)
        self.health = HealthMonitor(self.metrics, solver=self, **kw)
        return self.health

    def close(self):
        """Teardown: stop the watchdog thread (a leaked monitor thread
        keeps pytest and short-lived drivers alive), flush step/comms
        summaries, and close an internally-owned metrics stream.
        Idempotent; training can NOT continue afterwards with metrics."""
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self.heartbeat is not None:
            try:
                self.heartbeat.stop()   # the leaser thread must not
            finally:                    # outlive the run (pytest hangs)
                self.heartbeat = None
                if getattr(self, "_relay", None) is not None:
                    self._relay = None
        if self.health is not None:
            try:
                if self.health.alarms and self.metrics is not None:
                    self.metrics.log("health_summary",
                                     **self.health.summary())
            finally:
                self.health = None
        if self.elastic is not None:
            try:
                if self.metrics is not None and \
                        (self.elastic.evictions or
                         self.elastic.readmissions):
                    self.metrics.log("membership", kind="summary",
                                     **self.elastic.summary())
            finally:
                self.elastic = None
        self.divergence = self.memstats = None
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

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- public API --------------------------------------------------------
    def check_batch(self, batch, leading=(), split_across_hosts=True):
        """Fail fast with blob names when a feed array has the wrong shape
        (otherwise the error is a cryptic reshape deep inside some layer).
        Multi-process: each host feeds its 1/process_count slice of the
        batch axis (shard_batch assembles the global array), so the
        expected leading batch dim shrinks accordingly — unless the
        caller feeds every host the full global batch
        (split_across_hosts=False, the SeqParallelSolver discipline)."""
        pcount = jax.process_count() if split_across_hosts else 1
        shapes = dict(self.net.feed_shapes())
        if self._raw_feed_shapes:
            # device-side transform: the host feeds the RAW source extent
            # (+ aux offset arrays), not the net's post-transform shape
            shapes.update(self._raw_feed_shapes)
        for name, want in shapes.items():
            if want is None:
                # produced on-device (e.g. a device-resident dataset feeds
                # data/label from HBM) — the host doesn't ship this blob
                continue
            if name not in batch:
                raise ValueError(f"batch missing feed blob {name!r} "
                                 f"(needs {sorted(shapes)})")
            got = tuple(np.shape(batch[name]))
            expect = tuple(leading) + tuple(want)
            if pcount > 1 and expect:
                bd = len(leading)
                if expect[bd] % pcount:
                    raise ValueError(
                        f"feed blob {name!r}: global batch {expect[bd]} not "
                        f"divisible by {pcount} hosts")
                expect = expect[:bd] + (expect[bd] // pcount,) \
                    + expect[bd + 1:]
            if got != expect:
                raise ValueError(
                    f"feed blob {name!r}: got shape {got}, net was compiled "
                    f"for {expect}"
                    + (f" (this host's slice of {pcount} hosts)"
                       if pcount > 1 else ""))

    def _step_span(self):
        """The span every train_step / train_round variant runs under:
        ``solver.step`` with its ``iter``, opening in its ``solver.prep``
        phase (batch check, key split, wrapping); the caller switches to
        ``solver.enqueue`` at the jitted call."""
        return self.tracer.step("solver.step", self.iter, "solver.prep")

    def train_step(self, batch):
        """One optimization step; returns the (unsmoothed) loss value."""
        with self._step_span() as span:
            if self._jit_train is None:
                self._jit_train = self._build_train_step()
            iter_size = int(self.param.iter_size)
            self.check_batch(batch,
                             leading=(iter_size,) if iter_size > 1 else ())
            self.rng, key = jax.random.split(self.rng)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if self._it_dev is None:
                self._it_dev = jnp.asarray(self.iter, jnp.int32)
            span.phase("solver.enqueue")
            args = (self.params, self.state, self.history, batch,
                    self._it_dev, key)
            span.watch(self._jit_train, args)
            self.params, self.state, self.history, loss, self._it_dev = \
                self._jit_train(*args)
            self.iter += 1
        self._obs_step(span.host_s, loss, batch)
        return self._chaos_loss(loss)

    def step(self, num_iters, data_iter, test_data_fn=None):
        """Run ``num_iters`` steps (the analog of ccaffe solver_step): pulls
        batches from ``data_iter``, displays smoothed loss, runs scheduled
        tests (test_data_fn() -> fresh test batch iterator) and snapshots."""
        sp = self.param
        iter_size = int(sp.iter_size)
        # throughput windows use the WALL clock (an earlier rig's
        # monotonic clock slewed after long device waits). An NTP step
        # can garble one metrics window; the dt > 0 guard drops it.
        t_last, it_last = time.time(), self.iter
        for _ in range(num_iters):
            if sp.test_interval and self.iter % sp.test_interval == 0 and \
                    (self.iter > 0 or sp.test_initialization) and \
                    self.test_net is not None and test_data_fn is not None:
                scores = self.test(test_data_fn())
                for k, v in scores.items():
                    self.log(f"    Test net output: {k} = {v}")
                if self.metrics:
                    self.metrics.log("test", iter=self.iter,
                                     **{k: float(np.mean(v))
                                        for k, v in scores.items()})
                t_last, it_last = time.time(), self.iter
            if iter_size == 1:
                batch = next(data_iter)
            else:
                micros = [next(data_iter) for _ in range(iter_size)]
                batch = {k: np.stack([m[k] for m in micros])
                         for k in micros[0]}
            # debug_info dumps run on PRE-update params (the state that
            # produces this iteration's loss), like the reference's
            # mid-step prints
            if int(sp.debug_info) and sp.display \
                    and self.iter % sp.display == 0:
                micro = batch if iter_size == 1 \
                    else {k: v[0] for k, v in batch.items()}
                self._print_debug_info(micro)
            loss = self.train_step(batch)
            # deferred sync: losses stay device handles; fetching one is a
            # full round trip, so it happens at display points (or every
            # _sync_stride steps) — dispatches queue ahead in between and
            # the host never serializes transfer against compute
            self._smoothed.append(loss)
            disp = sp.display and (self.iter - 1) % sp.display == 0
            if not disp:
                if self.iter % self._sync_stride == 0:
                    with self.tracer.hot_span("solver.fetch",
                                              iter=self.iter - 1):
                        v = float(loss)
                        self._record_monitors()
                    if self.watchdog is not None:
                        self.watchdog.beat(v)
                    if self._maybe_recover(v):
                        t_last, it_last = time.time(), self.iter
                        continue        # rolled back; redo from there
                elif self.watchdog is not None:
                    self.watchdog.beat()
            if disp:
                # ONE fetch for the whole smoothing window
                with self.tracer.hot_span("solver.fetch",
                                          iter=self.iter - 1):
                    sm = self.smoothed_loss()
                    self._record_monitors()
                if self.watchdog is not None:
                    self.watchdog.beat(sm)
                if self._maybe_recover(sm):
                    # rolled back; restart the throughput window too (the
                    # iter counter went backwards)
                    t_last, it_last = time.time(), self.iter
                    continue
                lr = float(self.lr_fn(self.iter - 1))
                self.log(f"Iteration {self.iter - 1}, loss = {sm:.6g}, "
                         f"lr = {lr:.6g}")
                if self.metrics:
                    dt = time.time() - t_last
                    steps = self.iter - it_last
                    bsz = next(iter(self.net.feed_shapes().values()), (0,))
                    self.metrics.log(
                        "train", iter=self.iter - 1, loss=sm, lr=lr,
                        images_per_sec=round(steps * iter_size * bsz[0] / dt,
                                             2) if dt > 0 and bsz else None)
                    t_last, it_last = time.time(), self.iter
            if sp.snapshot and self.iter % sp.snapshot == 0 and \
                    sp.has("snapshot_prefix"):
                self.snapshot()

    def test(self, data_iter, num_iters=None):
        """Average the TEST net's output blobs over test_iter batches
        (reference solver.cpp TestAndStoreResult :414-444)."""
        with self.tracer.span("test", iter=self.iter):
            return self._test(data_iter, num_iters)

    def _test(self, data_iter, num_iters=None):
        if self._jit_eval is None:
            self._jit_eval = self._build_eval_step()
        n = num_iters or (int(self.param.test_iter[0])
                          if self.param.test_iter else 1)
        # accumulate ON DEVICE: each batch's scores stay as async jax
        # arrays, so the n eval dispatches (and their H2D feeds) pipeline;
        # the only host sync is the final fetch
        sums = None
        # sharded solvers that re-place batches themselves (the
        # global-feed path fetches host data per blob) skip the eager
        # device conversion — it would only add a transfer round trip
        to_dev = jax.process_count() == 1
        try:
            for i in range(n):
                batch = next(data_iter)
                if to_dev:
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                out = self._jit_eval(self.params, self.state, batch)
                if sums is None:
                    sums = {k: jnp.asarray(v, jnp.float32)
                            for k, v in out.items()}
                else:
                    sums = {k: sums[k] + jnp.asarray(out[k], jnp.float32)
                            for k in sums}
        finally:
            if hasattr(data_iter, "close"):
                data_iter.close()
        return {k: np.asarray(v, np.float64) / n for k, v in sums.items()}

    # -- checkpointing (reference solver.cpp Snapshot :447-521) ------------
    def snapshot(self, prefix=None, format=None):
        """Write weights + solver state. format: "binaryproto" (default) |
        "hdf5", or taken from SolverParameter.snapshot_format (HDF5=0)."""
        with self.tracer.span("snapshot", iter=self.iter):
            return self._snapshot(prefix, format)

    def _snapshot_paths(self, prefix=None, format=None):
        """-> (model_path, state_path, format) for a snapshot at the
        current iter (reference Snapshot naming, solver.cpp:466-470)."""
        prefix = prefix or self.param.snapshot_prefix
        if format is None:
            format = "hdf5" if int(self.param.snapshot_format) == 0 \
                else "binaryproto"
        ext = ".h5" if format == "hdf5" else ""
        return (f"{prefix}_iter_{self.iter}.caffemodel{ext}",
                f"{prefix}_iter_{self.iter}.solverstate{ext}", format)

    def _write_snapshot_files(self, model_path, state_path, format,
                              learned_net=None):
        """Write the two snapshot files to the given (possibly temporary)
        paths; ``learned_net`` is the model path the state file should
        reference — the FINAL name when writing through the atomic
        checkpoint protocol."""
        from . import hdf5_io
        learned = learned_net or model_path
        if format == "hdf5":
            hdf5_io.save_net_hdf5(model_path, self.net, self.params)
            hdf5_io.save_state_hdf5(state_path, self.iter, learned,
                                    self.net, self.history)
        else:
            net_proto = self.net.params_to_netproto(self.params, self.state)
            wire.dump(net_proto, model_path)
            ss = Message("SolverState", iter=self.iter,
                         learned_net=learned, current_step=0)
            # caffe history_ vector order: slot-major over net-ordered params
            for lname, i, s in hdf5_io.history_order(self.net, self.history):
                ss.history.append(
                    array_to_blob(np.asarray(self.history[lname][i][s])))
            wire.dump(ss, state_path)

    def _snapshot_writer(self):
        """Which process commits snapshots in a multi-process world:
        the lowest-indexed LIVE host (process 0 while healthy). Params/
        state/history are replicated, so N processes writing the same
        files would race each other's renames and manifest commits —
        the bug class the multi-process SIGTERM path used to have."""
        if jax.process_count() <= 1:
            return True
        me = jax.process_index()
        hb = self.heartbeat
        if hb is not None:
            try:
                return me == min(hb.live_processes() + [me])
            except Exception:
                pass
        return me == 0

    def _snapshot(self, prefix=None, format=None):
        # every snapshot goes through the crash-safe commit protocol:
        # temp-write -> fsync -> atomic rename -> manifest (the manifest
        # covers model+state as ONE unit; see resilience/checkpoint.py).
        # Multi-process: the designated writer commits; everyone else
        # barriers on the manifest it produced (satellite: N processes
        # must never race the same snapshot files).
        from ..resilience import checkpoint
        prefix = prefix or self.param.snapshot_prefix
        if not self._snapshot_writer():
            entry = checkpoint.wait_for_manifest(prefix,
                                                 min_iter=self.iter)
            if entry is None:
                self.log(f"snapshot: writer never committed iter "
                         f"{self.iter} under {prefix!r} (timed out); "
                         "continuing without a local copy")
                return None, None
            d = os.path.dirname(prefix)
            self.log(f"snapshot: committed by the writer process "
                     f"(iter {entry.get('iter')})")
            return (os.path.join(d, entry.get("model", "")),
                    os.path.join(d, entry.get("state", "")))
        model_path, state_path = checkpoint.save_snapshot(
            self, prefix, format=format, keep=self.snapshot_keep,
            metrics=self.metrics)
        self.log(f"Snapshotting to {model_path}")
        return model_path, state_path

    def restore(self, state_path, reshard="strict"):
        """Resume from a .solverstate[.h5] (+ its learned_net weights).
        Snapshots a manifest marks partial/corrupt are refused with the
        reason; a snapshot stamped by a DIFFERENT world (process count
        or mesh shape) raises WorldMismatch with the remedy under
        ``reshard="strict"``, while ``reshard="auto"`` re-partitions it
        for this run's world (resilience/checkpoint.py): params and
        optimizer history are replicated across the consensus axis, so
        the blobs restore unchanged and only data ownership re-spreads
        (the reshard_for_world plan, emitted as a `reshard` event); the
        snapshot is re-stamped for this world at the next snapshot."""
        from . import hdf5_io
        from ..resilience import checkpoint
        world = checkpoint.world_signature(self)
        entry = checkpoint.check_restorable(
            state_path, world=world, reshard=reshard)
        self._reshard_plan = None
        if reshard == "auto" and isinstance(entry, dict):
            plan = checkpoint.reshard_for_world(entry.get("world"), world)
            if plan is not None:
                self._reshard_plan = plan
                self.log(
                    f"reshard: snapshot {state_path} written for world "
                    f"{plan['from_world']} ({plan['n_from']} slots); "
                    f"re-partitioning for this world {plan['to_world']} "
                    f"({plan['n_to']} slots, {plan['direction']})")
                if self.metrics is not None:
                    self.metrics.log(
                        "reshard", iter=int(entry.get("iter", 0)),
                        state=state_path,
                        from_world=plan["from_world"],
                        to_world=plan["to_world"],
                        n_from=plan["n_from"], n_to=plan["n_to"],
                        direction=plan["direction"],
                        owners=plan["owners"])
        self._it_dev = None          # re-seed the device iter counter
        if state_path.endswith(".h5"):
            it, learned, self.history = hdf5_io.load_state_hdf5(
                state_path, self.net, self.history)
            self.iter = it
            if learned and os.path.exists(learned):
                self.load_weights(learned)
            return
        ss = wire.load(state_path, "SolverState")
        self.iter = int(ss.iter)
        if ss.has("learned_net") and os.path.exists(ss.learned_net):
            self.load_weights(ss.learned_net)
        blobs = list(ss.history)
        new_history = {k: [list(slot) for slot in v]
                       for k, v in self.history.items()}
        order = list(hdf5_io.history_order(self.net, self.history))
        if len(blobs) != len(order):
            # caffe SGDSolver::RestoreSolverStateFromBinaryProto
            # CHECK_EQ(state.history_size(), history_.size())
            raise ValueError(
                f"{state_path}: solver state has {len(blobs)} history "
                f"blobs, this solver expects {len(order)} — it was written "
                f"by a different solver type")
        for n, (lname, i, s) in enumerate(order):
            ref = new_history[lname][i][s]
            arr = blob_to_array(blobs[n]).reshape(ref.shape)
            new_history[lname][i][s] = jnp.asarray(arr, ref.dtype)
        self.history = new_history

    def load_weights(self, caffemodel_path):
        """CopyTrainedLayersFrom equivalent — accepts stock .caffemodel
        (binaryproto) or .caffemodel.h5 (HDF5)."""
        if caffemodel_path.endswith(".h5"):
            from . import hdf5_io
            self.params = hdf5_io.load_net_hdf5(caffemodel_path, self.net,
                                                self.params)
            return
        net_proto = wire.load(caffemodel_path, "NetParameter")
        self.params, self.state = self.net.load_netproto(
            net_proto, self.params, self.state)
