"""Command-line interface — the reference native CLI (tools/caffe.cpp).

Verbs (mirroring the brew registry, caffe.cpp:55):
  train         train from a -solver prototxt (caffe.cpp:153)
  test          score a model (caffe.cpp:222)
  time          per-layer fwd/bwd timing (caffe.cpp:290)
  device_query  enumerate devices (caffe.cpp:110)
plus the app drivers:
  cifar         CifarApp (reference src/main/scala/apps/CifarApp.scala)
  imagenet      ImageNetApp (reference ImageNetApp.scala)

Signal semantics follow the reference flags -sigint_effect/-sighup_effect
(caffe.cpp:43-46): snapshot / stop / none.
"""

import argparse
import json
import os
import sys
import time

def _mesh_arg(s):
    """"data=8,seq=2" -> {"data": 8, "seq": 2}; "8" -> {"data": 8}."""
    if s.isdigit():
        return {"data": int(s)}
    out = {}
    for part in s.split(","):
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def cmd_device_query(args):
    import jax
    for d in jax.devices():
        print(f"id {d.id}: {d.device_kind} ({d.platform}) "
              f"process {d.process_index}")
    return 0


def _make_data_iter(net, seed=0):
    """Synthetic batch stream matching the net's feed shapes — the fallback
    when a prototxt's DB source doesn't exist on this machine."""
    import numpy as np
    rs = np.random.RandomState(seed)
    shapes = net.feed_shapes()

    def gen():
        while True:
            batch = {}
            for name, shape in shapes.items():
                if len(shape) <= 1 or "label" in name:
                    batch[name] = rs.randint(0, 10, shape).astype(np.int32)
                else:
                    batch[name] = rs.randn(*shape).astype(np.float32)
            yield batch
    return gen()


def _real_feeds(train_np, test_np, base_dir, seed=None,
                device_transform=False):
    """Open the LMDB sources the net's Data layers name, when they exist.
    Returns (train_shapes, train_src, test_shapes, test_src) with None
    entries where no real source is available."""
    from .graph.compiler import TRAIN, TEST
    from .data.db_source import build_db_feed
    train_shapes, train_src = build_db_feed(
        train_np, TRAIN, base_dir, seed=seed,
        device_transform=device_transform)
    test_shapes = test_src = None
    if test_np is not None:
        test_shapes, test_src = build_db_feed(
            test_np, TEST, base_dir, seed=seed,
            device_transform=device_transform)
    return train_shapes, train_src, test_shapes, test_src


def _net_base_dir(sp, solver_path):
    """Stock solver prototxts name their net relative to the caffe repo root
    (e.g. "examples/cifar10/..."); caffe resolves against CWD. Walk up from
    the solver file until the referenced net path exists."""
    import os
    rel = None
    for f in ("net", "train_net"):
        if sp.has(f):
            rel = getattr(sp, f)
            break
    if rel is None or os.path.isabs(rel) or os.path.exists(rel):
        return ""
    d = os.path.dirname(os.path.abspath(solver_path))
    while True:
        if os.path.exists(os.path.join(d, rel)):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return ""
        d = parent


def _feed_shapes_arg(specs):
    """["data=100,3,32,32", ...] -> {"data": (100,3,32,32)} (the shape LMDB
    records would supply in stock caffe)."""
    out = {}
    for s in specs or ():
        name, _, dims = s.partition("=")
        out[name.strip()] = tuple(int(d) for d in dims.replace("x", ",")
                                  .split(","))
    return out


def cmd_train(args):
    from .proto import text_format
    from .solver.solver import Solver, resolve_nets
    from .utils.signals import SignalPolicy
    from .utils.metrics import MetricsLogger
    from .data.prefetch import PrefetchIterator, H2DStager, EchoIterator
    from .obs import Tracer, JaxProfiler

    import os
    # one metrics stream + span tracer for the whole run: the solver's
    # step/comms accounting, the prefetch gauges, and the CLI's phase
    # spans all land in the same JSONL (see sparknet_tpu.obs)
    _apply_perf_flags(args)   # before any net is compiled
    _apply_feed_flags(args)   # before any data source is constructed
    echo = max(1, int(os.environ.get("SPARKNET_ECHO", "1") or 1))
    if echo > 1 and args.host_transform:
        raise SystemExit(
            "--echo > 1 needs the device-transform feed (drop "
            "--host-transform): echoes re-draw crop/mirror on-device")
    metrics = MetricsLogger(args.metrics) if args.metrics else None
    tracer = Tracer(metrics)
    if args.chaos:
        # arm BEFORE solver/data construction so sources and the solver
        # pick the injectors up through active_chaos()
        from .resilience.chaos import ChaosMonkey, install_chaos
        install_chaos(ChaosMonkey.parse(args.chaos, metrics=metrics))
    sp = text_format.load(args.solver, "SolverParameter")
    base_dir = _net_base_dir(sp, args.solver)
    if sp.has("snapshot_prefix") and base_dir \
            and not os.path.isabs(sp.snapshot_prefix):
        # stock prefixes ("examples/cifar10/...") are caffe-root-relative;
        # anchor them where the net/sources resolved, not the process CWD
        sp.snapshot_prefix = os.path.join(base_dir, sp.snapshot_prefix)
    train_np, test_np = resolve_nets(sp, base_dir)
    seed = int(sp.random_seed) if int(sp.random_seed) >= 0 else None
    train_shapes, train_src, test_shapes, test_src = _real_feeds(
        train_np, test_np, base_dir, seed=seed,
        device_transform=not args.host_transform)
    if not args.host_transform and args.strategy == "single":
        # datasets that fit the HBM budget become device-resident: one bulk
        # upload, then each step ships a ~few-hundred-byte control array
        # (data/device_cache.py — the RDD-in-cluster-memory model, HBM
        # edition). SPARKNET_DEVICE_CACHE_MB=0 disables.
        from .data.device_cache import maybe_device_cache
        budget = float(os.environ.get("SPARKNET_DEVICE_CACHE_MB", "2048"))
        if budget > 0:
            isz = int(sp.iter_size)
            train_src = maybe_device_cache(train_src, budget, iter_size=isz,
                                           metrics=metrics)
            if hasattr(train_src, "nbytes"):     # budget is SHARED
                budget -= train_src.nbytes / (1 << 20)
            test_src = maybe_device_cache(test_src, budget, iter_size=isz,
                                          metrics=metrics)
    feed = {**(train_shapes or {}), **_feed_shapes_arg(args.input_shape)}

    with tracer.span("setup", strategy=args.strategy):
        if args.strategy == "dp":
            from .parallel import DataParallelSolver, make_mesh
            solver = DataParallelSolver(
                sp, mesh=make_mesh(_mesh_arg(args.mesh))
                if args.mesh else None, base_dir=base_dir,
                feed_shapes=feed or None, test_feed_shapes=test_shapes,
                metrics=metrics, tracer=tracer)
        else:
            solver = Solver(sp, base_dir=base_dir, feed_shapes=feed or None,
                            test_feed_shapes=test_shapes, metrics=metrics,
                            tracer=tracer)
    # device-transform mode: the source yields raw uint8 records + offset
    # arrays; crop/mirror/mean run inside the jitted step (3-4x fewer H2D
    # bytes — data/device_transform.py). Must install before first compile.
    if train_src is not None and getattr(train_src, "device_mode", False):
        solver.set_input_transform(
            train_src.device_fn, train_src.raw_feed_overrides,
            test_fn=test_src.device_fn
            if test_src is not None
            and getattr(test_src, "device_mode", False) else None)
    elif test_src is not None and getattr(test_src, "device_mode", False):
        solver.set_input_transform(None, None, test_fn=test_src.device_fn)
    solver.snapshot_keep = args.keep or None
    prefix = args.snapshot_prefix or (
        sp.snapshot_prefix if sp.has("snapshot_prefix") else None)
    if args.stall_seconds:
        solver.arm_watchdog(stall_seconds=args.stall_seconds)
    if args.recover:
        solver.arm_recovery(max_rollbacks=args.recover,
                            lr_decay=args.recover_lr_decay,
                            explode_factor=args.recover_explode_factor)
    _apply_health_flags(solver, args)
    _apply_heartbeat_flags(solver, args)     # before elastic: the relay
    _apply_elastic_flags(solver, args)       # world sizes to processes
    hb = solver.heartbeat                    # close() drops the reference
    if args.weights:
        solver.load_weights(args.weights)
    reshard = getattr(args, "reshard", "strict")
    if args.snapshot:
        solver.restore(args.snapshot, reshard=reshard)
    if args.resume:
        from .resilience import checkpoint
        if args.resume == "auto":
            if not prefix:
                raise SystemExit("--resume auto needs a snapshot prefix "
                                 "(--snapshot-prefix or the solver's "
                                 "snapshot_prefix)")
            checkpoint.resume_auto(solver, prefix, log_fn=print,
                                   reshard=reshard)
        else:
            solver.restore(args.resume, reshard=reshard)
    total = args.iterations or int(sp.max_iter) or 1000
    # H2D in the prefetch WORKER thread, so batch k+1's host->HBM copy
    # overlaps step k on the device (the overlap the reference got from
    # cudaMemcpyAsync + prefetch threads). SPARKNET_STAGING=on (default)
    # uses the rotating-slot H2DStager — puts DISPATCH non-blocking and
    # only the transfer the consumer is about to need gets waited on —
    # off reverts to the blocking device_put. Only on the single-device,
    # iter_size==1 path: the dp strategy re-shards via np.asarray (a
    # blocking readback of anything already on device), and iter_size>1
    # stacks micro-batches on the host first.
    import jax
    from .resilience.chaos import active_chaos
    staging = os.environ.get("SPARKNET_STAGING", "on") != "off"
    stager = None
    if args.strategy == "single" and int(sp.iter_size) <= 1:
        if staging:
            stager = H2DStager(slots=2, metrics=metrics, name="train_feed",
                               chaos=active_chaos())
            put = stager
        else:
            put = jax.device_put
    else:
        put = None
    if train_src is not None:
        kind = "device-cached" if hasattr(train_src, "nbytes") else (
            "device-transform" if getattr(train_src, "device_mode", False)
            else "host-transform")
        print(f"Training from {train_src.source} "
              f"({train_src.num_records} records, {kind})")
        extra = {"echo": echo, "staging": int(put is stager
                                              and stager is not None)}
        codec = getattr(train_src, "wire", None)
        if codec is not None:
            extra.update(codec.describe())
        data_iter = PrefetchIterator(iter(train_src), depth=3,
                                     transform=put, metrics=metrics,
                                     name="train_feed", extra=extra)
        if echo > 1:
            if hasattr(train_src, "nbytes"):
                # device-cached feed: each "batch" is already a tiny
                # on-device control array — nothing worth echoing
                print("NOTE: --echo ignored for the device-cached feed")
            elif not hasattr(train_src, "fresh_aux"):
                raise SystemExit(
                    f"--echo > 1 needs a source with re-drawable "
                    f"device-side augmentation; "
                    f"{type(train_src).__name__} has none")
            else:
                data_iter = EchoIterator(
                    data_iter, echo,
                    fresh_aux=lambda b: train_src.fresh_aux())
    else:
        print("WARNING: no Data-layer LMDB source found; "
              "feeding synthetic noise (shapes only)")
        data_iter = _make_data_iter(solver.net)
    if test_src is not None:
        # fresh pass per test, UN-prefetched: a prefetch worker would draw
        # augmentation rng for batches past the test_iter consumed,
        # advancing the source's RandomState nondeterministically between
        # passes. Tests are rare; reproducibility wins.
        test_fn = lambda: iter(test_src)  # noqa: E731
    else:
        test_fn = (lambda: _make_data_iter(solver.test_net, seed=1)) \
            if solver.test_net is not None else None
    policy = SignalPolicy(sigint=args.sigint_effect,
                          sighup=args.sighup_effect,
                          sigterm=args.sigterm_effect)
    prof = JaxProfiler(args.profile)
    from .resilience.chaos import active_chaos
    from .resilience.recovery import RecoveryAbort
    from .resilience.elastic import QuorumLost, EXIT_QUORUM_LOST
    from .utils.exit_codes import EXIT_RECOVERY_ABORT
    blocks_done = 0
    rc = 0
    try:
        with policy:
            while solver.iter < total:
                prof.maybe_start(blocks_done, total - solver.iter)
                n = min(100, total - solver.iter)
                with tracer.span("train_block", iter0=solver.iter, iters=n):
                    try:
                        solver.step(n, data_iter, test_data_fn=test_fn)
                    except RecoveryAbort as e:
                        # clean abort: the run is over, but the last
                        # known-good snapshot (if any) is intact on disk
                        print(f"ABORT: {e}")
                        rc = EXIT_RECOVERY_ABORT
                        break
                    except QuorumLost as e:
                        # too few live workers for a trustworthy
                        # consensus — distinct exit for the supervisor
                        # (DEPLOY.md runbook). The masked consensus up
                        # to here is healthy: keep it for the relaunch,
                        # and in a multi-host world barrier every
                        # survivor on the same manifest before exiting.
                        print(f"QUORUM LOST: {e}")
                        if prefix:
                            solver.snapshot(prefix=prefix)
                            solver.coordinated_restart(prefix)
                        rc = EXIT_QUORUM_LOST
                        break
                blocks_done += 1
                prof.maybe_stop()
                ch = active_chaos()
                if ch is not None:
                    ch.maybe_sigterm(blocks_done)
                action = policy.pending()
                if action in ("snapshot", "snapshot_stop"):
                    solver.snapshot(prefix=prefix or "snap")
                if action in ("stop", "snapshot_stop"):
                    print("stopping early on signal")
                    break
    finally:
        prof.abort()
        if train_src is not None:
            data_iter.close()
            train_src.close()
        if test_src is not None:
            test_src.close()
        solver.close()          # watchdog thread + step/comms summaries
        if args.profile:
            # the host-side twin of the device trace: the run's spans in
            # Chrome trace_event format, next to jax.profiler's output
            tracer.export_chrome(os.path.join(args.profile,
                                              "spans.trace.json"))
    # final snapshot unless disabled or this iter was already snapshotted
    # by the in-loop cadence (reference solver.cpp Solve tail :300-306,
    # snapshot_after_train). The cadence path only fires when the
    # SolverParameter itself carries a prefix (Solver.step), so a
    # --snapshot-prefix-only run must still get its tail snapshot.
    cadence_fired = int(sp.snapshot) and sp.has("snapshot_prefix") \
        and solver.iter % int(sp.snapshot) == 0
    # on a recovery abort the in-memory params may be the diverged ones —
    # never overwrite good snapshots with them
    if prefix and sp.snapshot_after_train and not cadence_fired and rc == 0:
        solver.snapshot(prefix=prefix)
    print(f"Optimization done, iter={solver.iter}")
    if metrics:
        metrics.close()
    # a run that SURVIVED a peer-host death must report ITS exit code,
    # not die in the unreachable jax.distributed shutdown barrier
    from .parallel.multihost import exit_if_peers_died
    exit_if_peers_died(rc, hb)
    return rc


def cmd_test(args):
    import os
    import numpy as np
    from .proto import text_format
    from .solver.solver import Solver, resolve_nets
    from .proto import Message
    from .graph.compiler import TEST
    from .data.db_source import resolve_db_feed

    net_param = text_format.load(args.model, "NetParameter")
    sp = Message("SolverParameter", base_lr=0.0, lr_policy="fixed",
                 display=0)
    sp.net_param = net_param

    # resolve the TEST data layer's source relative to the model file,
    # walking up (stock prototxt sources are caffe-root-relative)
    test_shapes, test_src = resolve_db_feed(
        net_param, TEST, os.path.dirname(os.path.abspath(args.model)))
    # the (unused) TRAIN net compiles with the test shapes — param shapes
    # don't depend on batch size, and only the TEST net is stepped here
    solver = Solver(sp, feed_shapes=_feed_shapes_arg(args.input_shape)
                    or test_shapes, test_feed_shapes=test_shapes)
    if args.weights:
        solver.load_weights(args.weights)
    if test_src is not None:
        print(f"Scoring on {test_src.source} "
              f"({test_src.num_records} records)")
        it = iter(test_src)
    else:
        print("WARNING: no Data-layer LMDB source found; synthetic batches")
        it = _make_data_iter(solver.test_net or solver.net)
    scores = solver.test(it, num_iters=args.iterations)
    for k, v in scores.items():
        print(f"{k} = {np.asarray(v).mean():.6f}")
    if test_src is not None:
        test_src.close()
    return 0


def cmd_convert_cifar(args):
    from . import tools
    tools.convert_cifar_data(args.input, args.output)
    return 0


def cmd_make_synth_cifar(args):
    from . import tools
    tools.make_synth_cifar(args.output, n_train=args.train, n_test=args.test,
                           seed=args.seed, noise=args.noise,
                           label_noise=args.label_noise)
    return 0


def cmd_compute_mean(args):
    from . import tools
    # backend=None -> open_db sniffs the on-disk layout, so LevelDB dirs
    # from `convert_imageset --backend leveldb` work like the reference
    # tool's -backend flag (compute_image_mean.cpp:22)
    tools.compute_image_mean(args.db, args.output, backend=args.backend)
    return 0


def cmd_convert_imageset(args):
    from . import tools
    tools.convert_imageset(args.root, args.listfile, args.db,
                           resize_height=args.resize_height,
                           resize_width=args.resize_width, gray=args.gray,
                           shuffle=args.shuffle, encoded=args.encoded,
                           backend=args.backend)
    return 0


def cmd_upgrade_net_proto(args):
    from . import tools
    tools.upgrade_net_proto(args.input, args.output, binary=args.binary)
    return 0


def cmd_upgrade_solver_proto(args):
    from . import tools
    tools.upgrade_solver_proto(args.input, args.output)
    return 0


def cmd_extract_features(args):
    from . import tools
    blobs = args.blobs.split(",")
    dbs = args.dbs.split(",")
    if args.db_type not in ("lmdb", "leveldb"):
        raise SystemExit(f"unknown db_type {args.db_type!r}")
    weights = None if args.weights.lower() == "none" else args.weights
    tools.extract_features(args.model, blobs, dbs, args.num_batches,
                           weights_path=weights,
                           backend=args.db_type)
    return 0


def cmd_time(args):
    """Per-layer forward/backward timing — `caffe time` (caffe.cpp:290-376).
    Each layer is jitted in isolation on random inputs of its true shapes."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .proto import text_format
    from .graph.compiler import CompiledNet, TRAIN

    net_param = text_format.load(args.model, "NetParameter")
    net = CompiledNet(net_param, TRAIN,
                      feed_shapes=_feed_shapes_arg(args.input_shape))
    params, state = net.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    iters = args.iterations
    print(f"{'layer':<28}{'type':<18}{'fwd ms':>10}{'fwd+bwd ms':>12}")
    total_f = total_fb = 0.0
    for lp, impl, bottoms, tops in net.layers:
        if getattr(impl, "is_feed", False):
            continue
        bvals = [jnp.asarray(rs.randn(*net.blob_shapes[b]), jnp.float32)
                 for b in bottoms]
        lparams = net.resolve_params(params, lp.name)
        lstate = state.get(lp.name)
        rng = jax.random.PRNGKey(0)

        def fwd(lparams, bvals):
            if impl.has_state:
                tv, _ = impl.apply_stateful(lparams, lstate, bvals, True, rng)
            else:
                tv = impl.apply(lparams, bvals, True, rng)
            return sum(jnp.sum(t.astype(jnp.float32)) for t in tv)

        jf = jax.jit(fwd)
        jg = jax.jit(jax.grad(lambda bv: fwd(lparams, bv), argnums=0))
        try:
            float(jf(lparams, bvals))         # compile + sanity
            t0 = time.perf_counter()
            for _ in range(iters):
                r = jf(lparams, bvals)
            float(r)
            f_ms = (time.perf_counter() - t0) / iters * 1e3
            g = jg(bvals)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), g)
            t0 = time.perf_counter()
            for _ in range(iters):
                g = jg(bvals)
            float(jax.tree_util.tree_leaves(g)[0].ravel()[0])
            fb_ms = f_ms + (time.perf_counter() - t0) / iters * 1e3
        except Exception as e:                      # non-differentiable etc.
            print(f"{lp.name:<28}{lp.type:<18}{'—':>10}  ({e})")
            continue
        total_f += f_ms
        total_fb += fb_ms
        print(f"{lp.name:<28}{lp.type:<18}{f_ms:>10.3f}{fb_ms:>12.3f}")
    print(f"{'TOTAL':<28}{'':<18}{total_f:>10.3f}{total_fb:>12.3f}")
    print("note: per-layer jit; the fused full-step is faster "
          "(XLA cross-layer fusion)")
    return 0


def cmd_cifar(args):
    from .apps import CifarApp
    _apply_perf_flags(args)   # before app/solver construction
    _apply_feed_flags(args)   # echo/shard-ingest land as env for the app
    if args.chaos:
        # arm BEFORE app/solver construction so active_chaos() sees it
        from .resilience.chaos import ChaosMonkey, install_chaos
        install_chaos(ChaosMonkey.parse(args.chaos))
    app = CifarApp(num_workers=args.workers, data_dir=args.data,
                   prototxt_dir=args.prototxt_dir, strategy=args.strategy,
                   tau=args.tau, log_path=args.log,
                   metrics_path=args.metrics, hosts=args.hosts)
    from .resilience.chaos import active_chaos
    ch = active_chaos()
    if ch is not None and ch.metrics is None and app.metrics is not None:
        ch.metrics = app.metrics     # chaos events land in the run's JSONL
    _apply_health_flags(app.solver, args)
    _apply_heartbeat_flags(app.solver, args)
    _apply_elastic_flags(app.solver, args)
    hb = getattr(app.solver, "heartbeat", None)   # close() drops the ref
    from .resilience.elastic import QuorumLost, EXIT_QUORUM_LOST
    from .parallel.multihost import exit_if_peers_died
    rc = 0
    try:
        app.run(num_rounds=args.rounds, test_every=args.test_every,
                snapshot_prefix=args.snapshot_prefix,
                snapshot_every=args.snapshot_every,
                resume=args.resume, reshard=args.reshard)
    except QuorumLost as e:
        print(f"QUORUM LOST: {e}")
        # keep the healthy consensus for the supervisor relaunch, and
        # barrier every survivor on the same manifest (same contract as
        # `sparknet train`)
        if args.snapshot_prefix:
            try:
                app.solver.snapshot(prefix=args.snapshot_prefix)
                app.solver.coordinated_restart(args.snapshot_prefix)
            except Exception as snap_err:
                print(f"QUORUM LOST: best-effort snapshot failed "
                      f"({snap_err})")
        rc = EXIT_QUORUM_LOST
    # a run that SURVIVED a peer-host death must report ITS exit code,
    # not die in the unreachable jax.distributed shutdown barrier
    exit_if_peers_died(rc, hb)
    return rc


def cmd_lm(args):
    """Transformer-LM training driver on the synthetic bigram corpus —
    the zoo's long-context family end to end: plain single-device Solver,
    or the GPipe pipeline (--pipeline-stages N -> PipelineLMSolver over a
    "pipe" mesh axis). Emits a JSONL loss curve whose floor (the corpus
    bigram entropy) is logged up front, so convergence is checkable."""
    import time as _time
    import numpy as np
    import jax.numpy as jnp
    from .proto import Message
    from .data.synthetic import lm_batch_stream
    from .utils.metrics import MetricsLogger

    if args.snapshot_every and not args.snapshot_prefix:
        raise SystemExit("--snapshot-every needs --snapshot-prefix")
    _apply_perf_flags(args)   # before any solver traces the net
    sp = Message("SolverParameter", base_lr=args.lr, lr_policy="fixed",
                 display=args.display, type=args.solver_type,
                 random_seed=args.seed,
                 snapshot=args.snapshot_every or 0)
    if args.snapshot_prefix:
        sp.snapshot_prefix = args.snapshot_prefix
    metrics = MetricsLogger(args.metrics) if args.metrics else None
    lm_kw = dict(vocab_size=args.vocab, seq_len=args.seq_len,
                 batch_size=args.batch, d_model=args.d_model,
                 num_heads=args.heads, flash=not args.no_flash)
    # bf16 means MIXED precision: f32 master params (optimizer updates
    # would underflow in bf16 — a d=1024 Adam run measurably stalls at the
    # unigram plateau with bf16 masters), bf16 activations cast at the
    # embedding so every matmul drives the MXU at full rate. --precision
    # is the same policy through the SPARKNET_PRECISION env var (applied
    # above), which CompiledNet resolves when compute_dtype is None.
    import os as _os
    compute_dtype = jnp.bfloat16 if args.dtype == "bf16" else None
    dtype = jnp.float32
    from .parallel.fsdp import fsdp_enabled
    fsdp_on = fsdp_enabled()
    tp_ways = int(_os.environ.get("SPARKNET_TP", "0") or 0)
    if fsdp_on and tp_ways > 1:
        raise SystemExit(
            "--fsdp and --tp do not compose yet: FSDP shards over the "
            "data axis via shard_map, TP annotates a (data, model) mesh "
            "via GSPMD — pick one lever per run")
    if (fsdp_on or tp_ways > 1) and (args.pipeline_stages > 1
                                     or args.ep > 1 or args.sp > 1):
        raise SystemExit("--fsdp/--tp compose with --dp only (not "
                         "--ep/--sp/--pipeline-stages)")
    stream, floor = lm_batch_stream(args.vocab, args.batch, args.seq_len,
                                    seed=args.seed)
    if metrics:
        metrics.log("config", loss_floor_nats=round(floor, 4),
                    d_model=args.d_model, layers=args.layers,
                    seq_len=args.seq_len, batch=args.batch,
                    pipeline_stages=args.pipeline_stages,
                    dtype=args.dtype,
                    precision=_os.environ.get("SPARKNET_PRECISION",
                                              "fp32") or "fp32",
                    fsdp=int(fsdp_on), tp=max(tp_ways, 1))
    print(f"bigram corpus floor: {floor:.4f} nats/token "
          f"(untrained: {np.log(args.vocab):.4f})")

    if tp_ways > 1:
        # tensor parallelism: GSPMD annotations over a (data, model)
        # mesh — wqkv/ffn1/lm_head column-split, wo/ffn2 row-split
        # (parallel/gspmd.py transformer_tp_rule); the batch shards
        # over whatever devices remain on "data"
        from .parallel import GSPMDSolver, transformer_tp_rule
        from .parallel.mesh import make_tp_mesh
        from .models import zoo
        net = zoo.transformer_lm(num_layers=args.layers, **lm_kw)
        solver = GSPMDSolver(
            sp, mesh=make_tp_mesh(tp_ways),
            param_rule=transformer_tp_rule(tp_ways),
            net_param=net, metrics=metrics, dtype=dtype,
            compute_dtype=compute_dtype)
        if args.resume:
            solver.restore(args.resume)
        start_iter = solver.iter
        t0 = _time.time()
        solver.step(args.steps - solver.iter, iter(stream))
    elif args.ep > 1 or args.dp > 1 or args.sp > 1 or fsdp_on:
        # mesh-axis solvers: --ep (x --dp x --sp) -> ExpertParallelSolver
        # (expert weights + optimizer state sharded over "expert", batch
        # over data/expert, sequence over "seq" with ring attention);
        # --sp without MoE -> SeqParallelSolver (dp x sp); --dp alone ->
        # DataParallelSolver
        if args.pipeline_stages > 1:
            raise SystemExit("--ep/--dp/--sp cannot combine with "
                             "--pipeline-stages")
        if args.ep > 1 and not args.moe_experts:
            raise SystemExit("--ep needs --moe-experts")
        from .parallel import make_mesh
        from .models import zoo
        if args.sp > 1:
            lm_kw = dict(lm_kw, flash=False)   # ring attention path
        net = zoo.transformer_lm(num_layers=args.layers,
                                 moe_experts=args.moe_experts,
                                 moe_aux_weight=args.moe_aux_weight,
                                 moe_stats=bool(args.moe_experts),
                                 ring=args.sp > 1, **lm_kw)
        if args.moe_experts:
            from .parallel import ExpertParallelSolver
            axes = {"data": args.dp}
            if args.sp > 1:
                axes["seq"] = args.sp
            axes["expert"] = args.ep
            solver = ExpertParallelSolver(
                sp, mesh=make_mesh(axes),
                seq_axis="seq" if args.sp > 1 else None,
                net_param=net, metrics=metrics, dtype=dtype,
                compute_dtype=compute_dtype)
        elif args.sp > 1:
            from .parallel import SeqParallelSolver
            solver = SeqParallelSolver(
                sp, mesh=make_mesh({"data": args.dp, "seq": args.sp}),
                net_param=net, metrics=metrics, dtype=dtype,
                compute_dtype=compute_dtype)
        else:
            # --dp alone (or --fsdp, which implies the data axis): the
            # per-step allreduce family. FSDP swaps in the sharded-state
            # twin — params + optimizer state dim0-sharded over "data",
            # all-gather at use, reduce-scatter grads (parallel/fsdp.py)
            from .parallel import DataParallelSolver, FSDPSolver
            cls = FSDPSolver if fsdp_on else DataParallelSolver
            dp_axes = {"data": args.dp if args.dp > 1 else -1}
            solver = cls(
                sp, mesh=make_mesh(dp_axes), net_param=net,
                metrics=metrics, dtype=dtype,
                compute_dtype=compute_dtype)
            import jax as _jax
            if _jax.process_count() > 1:
                # DataParallelSolver's multi-host discipline is per-host
                # batch SLICES (unlike the global-feed EP/Seq branches);
                # every host draws the identical seeded stream, so each
                # takes its own slice of it
                from .parallel import local_batch_slice

                def _host_slice(it, B=args.batch):
                    for b in it:
                        s0, ln = local_batch_slice(B)
                        yield {k: v[s0:s0 + ln] for k, v in b.items()}
                stream = _host_slice(stream)
        if args.resume:
            solver.restore(args.resume)
        start_iter = solver.iter
        t0 = _time.time()
        chunk = args.display or 50
        while solver.iter < args.steps:
            solver.step(min(chunk, args.steps - solver.iter), stream)
            if not args.moe_experts:
                continue
            # routing diagnostics: one TEST-phase forward; the stats tops
            # (per-expert token fractions + overflow) pmean'd over the mesh
            scores = solver.test(iter([next(stream)]), num_iters=1)
            stats = {k: np.asarray(v) for k, v in scores.items()
                     if k.endswith("/moe_stats")}
            if stats:
                util = np.mean([s[:-1] for s in stats.values()], axis=0)
                overflow = float(np.mean([s[-1] for s in stats.values()]))
                print(f"    iter {solver.iter}: expert util "
                      f"[{', '.join(f'{u:.3f}' for u in util)}] "
                      f"overflow {overflow:.4f}")
                if metrics:
                    # eval_ce = the SoftmaxWithLoss top alone — the
                    # train "loss" series includes the weighted aux terms
                    ce = scores.get("loss")
                    metrics.log("moe", iter=solver.iter,
                                eval_ce=round(float(np.mean(ce)), 4)
                                if ce is not None else None,
                                expert_util=[round(float(u), 4)
                                             for u in util],
                                overflow_fraction=round(overflow, 5),
                                **{k.replace("/moe_stats", "_util"):
                                   [round(float(x), 4) for x in s[:-1]]
                                   for k, s in stats.items()})
    elif args.pipeline_stages > 1:
        from .parallel import PipelineLMSolver, make_mesh
        if args.moe_experts:
            raise SystemExit("--moe-experts is not supported under "
                             "--pipeline-stages (dense-FFN blocks only)")
        solver = PipelineLMSolver(
            sp, mesh=make_mesh({"pipe": args.pipeline_stages}),
            num_layers=args.layers,
            num_microbatches=args.microbatches or None,
            metrics=metrics, dtype=dtype, compute_dtype=compute_dtype,
            **lm_kw)
        solver.snapshot_prefix = args.snapshot_prefix
        if args.resume:
            solver.restore(args.resume)
        start_iter = solver.iter
        t0 = _time.time()
        solver.step(args.steps - solver.iter, stream)
    else:
        from .solver.solver import Solver
        from .models import zoo
        net = zoo.transformer_lm(num_layers=args.layers,
                                 moe_experts=args.moe_experts,
                                 moe_aux_weight=args.moe_aux_weight,
                                 **lm_kw)
        solver = Solver(sp, net_param=net, metrics=metrics, dtype=dtype,
                        compute_dtype=compute_dtype)
        if args.resume:
            solver.restore(args.resume)
        start_iter = solver.iter
        t0 = _time.time()
        solver.step(args.steps - solver.iter, iter(stream))
    dt = _time.time() - t0
    executed = solver.iter - start_iter
    toks = executed * args.batch * args.seq_len
    final = solver.smoothed_loss()
    if args.snapshot_prefix:
        solver.snapshot(args.snapshot_prefix)
    rate = toks / dt if dt > 0 else 0
    print(f"done: {executed} steps, {rate:,.0f} tokens/s wall, "
          f"final loss {final}")
    if metrics:
        metrics.log("summary", steps=executed,
                    tokens_per_sec=round(rate, 1),
                    final_loss=final, loss_floor_nats=round(floor, 4))
    if hasattr(solver, "close"):
        solver.close()          # flush step/comms summaries, stop threads
    if metrics:
        metrics.close()
    return 0


def cmd_report(args):
    """Aggregate a --metrics JSONL into a run report (sparknet_tpu.obs):
    per-phase time breakdown, step-time percentiles, comms volume,
    recompile count, training-health (divergence/stragglers/alarms),
    loss-curve summary — human-readable on stdout, machine-readable with
    --json, Chrome trace_event spans with --chrome."""
    from .obs import report as obs_report
    events = [s for s in (args.event.split(",") if args.event else [])
              if s.strip()]
    try:
        obs_report.report_file(args.jsonl, json_out=args.json,
                               chrome_out=args.chrome,
                               since=args.since,
                               event_types=events or None,
                               fmt=args.format)
    except obs_report.MetricsFileError as e:
        # missing/empty/unreadable metrics is an operator error, not a
        # crash: one line on stderr, distinct exit code
        print(f"sparknet report: error: {e}", file=sys.stderr)
        return 2
    except BrokenPipeError:
        # `sparknet report | head`: downstream closed the pipe mid-render
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    return 0


def cmd_monitor(args):
    """Tail a --metrics JSONL and render a live terminal summary
    (sparknet_tpu.obs.monitor): round/iter/loss, per-worker losses,
    divergence, stragglers, memory, last health alarm."""
    from .obs import monitor as obs_monitor
    from .obs.report import MetricsFileError
    try:
        state = obs_monitor.monitor_file(
            args.jsonl, interval=args.interval, once=args.once,
            wait=args.wait, duration=args.duration)
    except MetricsFileError as e:
        print(f"sparknet monitor: error: {e}", file=sys.stderr)
        return 2
    except BrokenPipeError:
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    return 0 if state.events else 2


def cmd_trace(args):
    """`sparknet trace`: merge N per-host metrics JSONLs into one
    clock-aligned fleet timeline (obs/fleettrace.py). --chrome writes a
    single Chrome trace_event file with one track group per host plus
    the solved per-host clock offsets; --critpath renders the per-round
    critical-path decomposition (obs/critpath.py) naming the blocking
    host and phase; --round N limits the critpath to one round. With
    neither flag, prints the alignment summary. Also consumes a single
    multiplexed `sparknet simfleet --metrics` stream unchanged."""
    import json as _json
    from .obs import critpath as obs_critpath
    from .obs import fleettrace as obs_fleettrace
    from .obs.report import MetricsFileError, load_events
    try:
        streams, bad = [], 0
        for path in args.metrics:
            evs, b = load_events(path)
            streams.append(evs)
            bad += b
        if not any(streams):
            raise MetricsFileError(
                "no parseable events in "
                + ", ".join(args.metrics)
                + (f" ({bad} malformed line(s) skipped)" if bad else ""))
        ft = obs_fleettrace.merge_streams(streams)
        if bad:
            print(f"sparknet trace: WARNING: {bad} malformed JSONL "
                  "line(s) skipped", file=sys.stderr)
        if args.chrome:
            obs_fleettrace.export_chrome(args.chrome, ft)
            n_hosts = len(ft.hosts)
            print(f"wrote {args.chrome} ({n_hosts} host track(s), "
                  f"{sum(len(v) for v in ft.events.values())} event(s))")
        if args.critpath:
            cp = obs_critpath.compute(ft, round_filter=args.round)
            if args.json:
                print(_json.dumps(cp, indent=1, sort_keys=True,
                                  default=str))
            else:
                obs_critpath.render(cp)
        if not args.chrome and not args.critpath:
            summ = obs_fleettrace.align_summary(ft)
            if args.json:
                print(_json.dumps(summ, indent=1, sort_keys=True))
            else:
                print(f"fleet: {len(summ['hosts'])} track(s), "
                      f"{summ['beacons']} clock beacon(s)")
                for h, o in sorted(summ["offsets"].items()):
                    if not o.get("aligned"):
                        print(f"  host {h}: unaligned (no beacon path)")
                        continue
                    err = o.get("err_s")
                    err_txt = "one-sided bound" if err is None \
                        else f"±{err * 1e3:.1f} ms"
                    print(f"  host {h}: offset "
                          f"{o.get('offset_s', 0.0) * 1e3:+.1f} ms "
                          f"({err_txt}, {o.get('samples', 0)} "
                          "beacon(s))")
    except MetricsFileError as e:
        print(f"sparknet trace: error: {e}", file=sys.stderr)
        return 2
    except BrokenPipeError:
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    return 0


def cmd_simfleet(args):
    """`sparknet simfleet`: the discrete-event fleet simulator
    (sparknet_tpu.sim) — thousands of virtual hosts driving the REAL
    heartbeat/consensus/elastic-policy code against a simulated clock
    and in-memory rendezvous dir. One run, a --sweep grid, or the
    replay-validation pair (--record_real / --replay). With --serve,
    the SERVING-fleet simulator instead (sim/servefleet.py): virtual
    replicas + the real router under open-loop arrival traces. Exit 0
    on success, 1 on a replay mismatch or a lost serving request
    (no-lost-request-without-429 invariant), 2 on a bad chaos/sweep
    spec, 4 (EXIT_QUORUM_LOST) when the simulated fleet loses quorum —
    the same exit a real run would take."""
    import json as _json
    import tempfile
    from .utils.exit_codes import EXIT_QUORUM_LOST
    from .utils.metrics import MetricsLogger
    from .sim import FleetSim, ServeFleetSim, replay, sweep

    metrics = MetricsLogger(args.metrics) if args.metrics else None
    log = print if args.verbose else None
    try:
        if args.serve:
            if args.sweep:
                cells = []
                for spec in args.sweep:
                    cells.extend(sweep.parse_serve_grid(spec))
                results = sweep.run_sweep(cells, metrics=metrics,
                                          log_fn=print,
                                          budget_s=args.budget_s,
                                          cell_fn=sweep.run_serve_cell)
                print(sweep.render_serve_table(results))
                if args.json:
                    with open(args.json, "w") as f:
                        _json.dump(results, f, indent=1)
                lost = sum(r["lost"] for r in results)
                if lost:
                    print(f"sparknet simfleet: {lost} request(s) LOST "
                          "without an explicit 429/5xx — the serving "
                          "invariant is broken", file=sys.stderr)
                    return 1
                return 0
            sim = ServeFleetSim(
                replicas=args.replicas, windows=args.windows,
                window_s=args.window_s, interval_s=args.interval,
                lease_s=args.lease, service_ms=args.service_ms,
                queue_limit=args.queue_limit, rate=args.rate,
                trace=args.trace, spike_x=args.spike_x,
                slo_p99_ms=args.slo_p99_ms, slo_depth=args.slo_depth,
                breach_windows=args.breach_windows,
                idle_windows=args.idle_windows,
                max_replicas=args.max_replicas, canary_w=args.canary_w,
                canary_pct=args.canary_pct, canary_err=args.canary_err,
                canary_min_requests=args.canary_min_requests,
                die_w=args.die_w, rejoin_w=args.rejoin_w,
                chaos=args.chaos, seed=args.seed,
                trace_sample=args.trace_sample,
                tail_ms=args.trace_tail_ms, slo_burn=args.slo_burn,
                burn_scale=args.burn_scale, metrics=metrics,
                log_fn=log)
            s = sim.run()
            print(f"servefleet: {s['replicas']} replicas x "
                  f"{s['windows']} windows (sim {s['sim_s']}s) "
                  f"trace={s['trace']} rate={s['rate']:g}/s "
                  f"lease={s['lease_s']:g} interval={s['interval_s']:g}")
            print(f"traffic: {s['arrivals']} arrivals -> {s['ok']} ok, "
                  f"{s['rejected']} rejected (429), {s['errors']} "
                  f"errors, {s['retries']} retried; lost {s['lost']}")
            print(f"availability {s['availability']}  "
                  f"p99 {s['p99_ms']}ms"
                  + (f"  top stage {s['top_stage']}"
                     if s.get("top_stage") else ""))
            if s.get("burn"):
                b = s["burn"]
                print(f"slo burn: fast x{b.get('fast')}"
                      f"/{b.get('fast_long')} slow x{b.get('slow')}"
                      f"/{b.get('slow_long')} budget left "
                      f"{b.get('budget_left')}"
                      + (f"  ALERT {b['alert']}" if b.get("alert")
                         else ""))
            print(f"membership: {s['evictions']} evictions, "
                  f"{s['readmissions']} readmissions, "
                  f"{s['admissions']} admissions; final live "
                  f"{s['replicas_final']}; grow {s['grow']} shrink "
                  f"{s['shrink']}; canary rollbacks "
                  f"{s['canary_rollbacks']}"
                  + ("  QUORUM LOST" if s["quorum_lost"] else ""))
            if args.json:
                with open(args.json, "w") as f:
                    _json.dump(s, f, indent=1)
            if s["quorum_lost"]:
                return EXIT_QUORUM_LOST
            return 1 if s["lost"] else 0
        if args.record_real:
            with tempfile.TemporaryDirectory() as d:
                rec = replay.record_real(
                    d, hosts=min(args.hosts, 4), rounds=args.rounds,
                    interval_s=args.interval, lease_s=args.lease,
                    round_s=args.round_s or 0.12,
                    evict_after=args.evict_after,
                    readmit_after=args.readmit_after,
                    quorum=args.quorum, log_fn=log)
            with open(args.record_real, "w") as f:
                _json.dump(rec, f, indent=1)
            print(f"simfleet: recorded real {rec['config']['hosts']}-"
                  f"coordinator run -> {args.record_real} "
                  f"({len(rec['sequence'])} membership events)")
            return 0
        if args.replay:
            with open(args.replay) as f:
                rec = _json.load(f)
            ok, real_seq, sim_seq = replay.replay_sim(
                rec, metrics=metrics, log_fn=log)
            if ok:
                print(f"simfleet: REPLAY MATCH — {len(sim_seq)} "
                      "membership events reproduced exactly")
                return 0
            print("simfleet: REPLAY MISMATCH", file=sys.stderr)
            print(f"  real: {real_seq}", file=sys.stderr)
            print(f"  sim:  {sim_seq}", file=sys.stderr)
            return 1
        if args.sweep:
            cells = []
            for spec in args.sweep:
                cells.extend(sweep.parse_grid(spec))
            results = sweep.run_sweep(cells, metrics=metrics,
                                      log_fn=print,
                                      budget_s=args.budget_s)
            print(sweep.render_table(results))
            if args.json:
                with open(args.json, "w") as f:
                    _json.dump(results, f, indent=1)
            return 0
        sim = FleetSim(hosts=args.hosts, rounds=args.rounds,
                       interval_s=args.interval, lease_s=args.lease,
                       round_s=args.round_s, jitter=args.jitter,
                       tau=args.tau, step_s=args.step_s,
                       quorum=args.quorum, evict_after=args.evict_after,
                       readmit_after=args.readmit_after,
                       staleness=args.staleness, s_decay=args.s_decay,
                       consensus=args.consensus,
                       recover_after=args.recover_after,
                       chaos=args.chaos, seed=args.seed,
                       metrics=metrics, log_fn=log)
        s = sim.run()
        w = s["gate_wait_s"]
        print(f"fleet: {s['hosts']} hosts x {s['rounds']} rounds "
              f"(sim {s['sim_s']}s) consensus={s['consensus']} "
              f"lease={s['lease_s']:g} interval={s['interval_s']:g} "
              f"round_s={s['round_s']:g}")
        print(f"membership: {s['evictions']} evictions, "
              f"{s['readmissions']} readmissions, "
              f"{s['admissions']} admissions; "
              f"final live {s['live_final']}/{s['hosts']}"
              + ("  QUORUM LOST" if s["quorum_lost"] else ""))
        print(f"gate wait: mean {w['mean']}s p50 {w['p50']}s "
              f"p95 {w['p95']}s max {w['max']}s")
        print(f"staleness: parks {s['parks']} unparks {s['unparks']}"
              + (f" max_lag {s['max_lag']}" if "max_lag" in s else "")
              + f"  rollbacks {s['rollbacks']}"
              + f"  retry_exhausted {s['retry_exhausted']}")
        if args.json:
            with open(args.json, "w") as f:
                _json.dump(s, f, indent=1)
        return EXIT_QUORUM_LOST if s["quorum_lost"] else 0
    except ValueError as e:
        # a typo'd chaos/sweep spec must fail loudly, not run vacuously
        print(f"sparknet simfleet: error: {e}", file=sys.stderr)
        return 2
    finally:
        if metrics is not None:
            metrics.close()


def cmd_serve(args):
    """`sparknet serve`: weights-only inference over a resilient
    checkpoint prefix — continuous batching, hot reload, graceful
    drain. Exit 0 after a clean SIGTERM/SIGINT drain; exit 3
    (EXIT_RECOVERY_ABORT) when the checkpoint has no servable model
    blob, before the socket ever opens; exit 2 on a bad --chaos spec.
    With --fleet_dir the replica leases into the fleet rendezvous
    (serve/fleet.py) for `sparknet route` to discover."""
    from .utils.signals import SignalPolicy
    from .utils.metrics import MetricsLogger
    from .utils.exit_codes import EXIT_RECOVERY_ABORT
    from .obs.tracing import TraceSampler
    from .serve import ServeEngine, Batcher, serve_http

    _apply_perf_flags(args)   # before any net is compiled
    net_param = None
    if args.model:
        from .proto import text_format
        net_param = text_format.load(args.model, "NetParameter")
    metrics = MetricsLogger(args.metrics) if args.metrics else None
    chaos = None
    if args.chaos:
        from .resilience.chaos import ChaosMonkey
        try:
            chaos = ChaosMonkey.parse(args.chaos, metrics=metrics)
        except ValueError as e:
            print(f"sparknet serve: error: {e}", file=sys.stderr)
            if metrics:
                metrics.close()
            return 2
    engine = ServeEngine(args.prefix, net_param=net_param,
                         max_batch=args.max_batch, metrics=metrics)
    try:
        engine.load()
    except ValueError as e:
        print(f"sparknet serve: error: {e}", file=sys.stderr)
        if metrics:
            metrics.close()
        return EXIT_RECOVERY_ABORT
    if not args.no_warmup:
        engine.warmup()           # trace every bucket before traffic
    batcher = Batcher(max_batch=args.max_batch,
                      max_wait_s=args.max_wait_ms / 1e3,
                      queue_limit=args.queue_limit, metrics=metrics)
    member = None
    if args.fleet_dir:
        from .serve import ReplicaMember
        member = ReplicaMember(args.fleet_dir, args.replica,
                               replicas=args.replicas, engine=engine,
                               batcher=batcher,
                               interval_s=args.heartbeat_interval,
                               lease_s=args.lease, metrics=metrics)
    tracer = TraceSampler(sample=args.trace_sample,
                          tail_ms=args.trace_tail_ms)
    # SIGTERM = the scheduler's preemption notice -> drain, exit 0
    policy = SignalPolicy(sigint="stop", sighup="none", sigterm="stop")
    with policy:
        rc = serve_http(engine, batcher, host=args.host, port=args.port,
                        metrics=metrics, policy=policy,
                        reload_poll_s=args.reload_poll,
                        request_timeout_s=args.request_timeout,
                        member=member, chaos=chaos,
                        replica=args.replica, tracer=tracer)
    if metrics:
        metrics.close()
    return rc


def cmd_route(args):
    """`sparknet route`: the serving-fleet router (serve/fleet.py) —
    lease-based membership over --fleet_dir, least-queue-depth dispatch
    with retry-once failover, SLO autoscaling decisions, canary
    auto-rollback. Exit 0 after a clean SIGTERM/SIGINT drain."""
    from .utils.signals import SignalPolicy
    from .utils.metrics import MetricsLogger
    from .obs.tracing import BurnRateLedger, TraceSampler
    from .serve import (Router, SLOAutoscaler, CanaryController,
                        route_http)

    metrics = MetricsLogger(args.metrics) if args.metrics else None
    canary = CanaryController(
        pct=args.canary_pct, min_requests=args.canary_min_requests,
        max_err_delta=args.canary_err_delta,
        max_p99_delta_ms=args.canary_p99_delta_ms, metrics=metrics)
    tracer = TraceSampler(sample=args.trace_sample,
                          tail_ms=args.trace_tail_ms)
    slo = None
    if not args.no_slo_burn:
        slo = BurnRateLedger(
            slo_ms=(args.slo_ms if args.slo_ms is not None
                    else args.slo_p99_ms),
            objective=args.slo_objective, scale=args.burn_scale,
            metrics=metrics)
    router = Router(args.fleet_dir, replicas=args.replicas,
                    lease_s=args.lease, canary=canary, metrics=metrics,
                    tracer=tracer, slo=slo)
    autoscaler = None
    if not args.no_autoscale:
        autoscaler = SLOAutoscaler(
            p99_ms=args.slo_p99_ms, depth=args.slo_depth,
            windows=args.breach_windows, idle_windows=args.idle_windows,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas, metrics=metrics)
    policy = SignalPolicy(sigint="stop", sighup="none", sigterm="stop")
    with policy:
        rc = route_http(router, autoscaler=autoscaler, host=args.host,
                        port=args.port, window_s=args.window_s,
                        policy=policy,
                        request_timeout_s=args.request_timeout)
    if metrics:
        metrics.close()
    return rc


def cmd_serve_bench(args):
    """`sparknet serve-bench`: load-generate against a running
    `sparknet serve` endpoint (closed and/or open loop)."""
    from .utils.metrics import MetricsLogger
    from .serve import run_loadgen

    metrics = MetricsLogger(args.metrics) if args.metrics else None
    modes = ("closed", "open") if args.mode == "both" else (args.mode,)
    results = []
    for mode in modes:
        results.append(run_loadgen(
            args.url, mode=mode, concurrency=args.concurrency,
            rate=args.rate, duration_s=args.duration, rows=args.rows,
            timeout=args.request_timeout, metrics=metrics))
    if metrics:
        metrics.close()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    bad = sum(r["errors"] for r in results)
    return 0 if bad == 0 else 1


def _add_perf_flags(p, scan=False):
    """--remat (and for the LM driver --scan): the trace-time perf knobs
    of graph/compiler.py. The flags write the SPARKNET_* env vars before
    any solver is constructed, so the env vars stay the back-compat
    fallback (SPARKNET_REMAT=0/1 still means none/full) and every code
    path — including nets built by apps — sees one consistent policy."""
    p.add_argument("--remat", choices=("none", "dots", "full"),
                   default=None,
                   help="rematerialization policy for the train trace: "
                        "none (store everything), dots (checkpoint_dots "
                        "— keep matmul outputs, recompute elementwise), "
                        "full (recompute whole segments). Under dots "
                        "and full the outputs of a segment's pallas "
                        "kernels are kept, not computed a second time: "
                        "a flash pass's output and logsumexp, one "
                        "attention output a layer more in memory; the "
                        "delta rule's output, states and inverses. "
                        "Default: SPARKNET_REMAT env var, else none")
    if scan:
        p.add_argument("--scan", choices=("auto", "on", "off"),
                       default=None,
                       help="scan-over-layers for isomorphic block "
                            "stacks: one traced body + lax.scan instead "
                            "of N unrolled copies (auto: TPU only). "
                            "Default: SPARKNET_SCAN env var, else auto")
    p.add_argument("--precision", choices=("bf16", "fp32"), default=None,
                   help="mixed-precision policy: bf16 activations with "
                        "fp32 master weights + fp32 grad accumulation, "
                        "or the untouched fp32 path. Default: "
                        "SPARKNET_PRECISION env var, else fp32")


def _add_sharding_flags(p):
    """--fsdp / --tp: the one-big-model levers (parallel/fsdp.py,
    parallel/gspmd.py). Same discipline as the perf flags: each writes
    its SPARKNET_* env var before any solver is constructed."""
    p.add_argument("--fsdp", choices=("on", "off"), default=None,
                   help="ZeRO/FSDP sharding: params + optimizer state "
                        "live dim0-sharded over the data axis "
                        "(all-gather at use, reduce-scatter grads, "
                        "per-shard update — bit-for-bit the replicated "
                        "DP path at fp32). Default: SPARKNET_FSDP env "
                        "var, else off")
    p.add_argument("--tp", type=int, default=None, metavar="N",
                   help="N>1: Megatron-style tensor parallelism for the "
                        "LM's matmuls over an N-way \"model\" mesh axis "
                        "(GSPMD annotations; remaining devices form the "
                        "data axis). Default: SPARKNET_TP env var, "
                        "else 1")


def _apply_perf_flags(args):
    import os
    if getattr(args, "remat", None) is not None:
        os.environ["SPARKNET_REMAT"] = args.remat
    if getattr(args, "scan", None) is not None:
        os.environ["SPARKNET_SCAN"] = args.scan
    if getattr(args, "precision", None) is not None:
        os.environ["SPARKNET_PRECISION"] = args.precision
    if getattr(args, "fsdp", None) is not None:
        os.environ["SPARKNET_FSDP"] = args.fsdp
    if getattr(args, "tp", None) is not None:
        os.environ["SPARKNET_TP"] = str(args.tp)


def _add_feed_flags(p):
    """Input-pipeline levers (PERF.md "Input pipeline"). Like the perf
    flags, each writes its SPARKNET_* env var before any source/solver is
    constructed — env-only use keeps working, and an A/B run differs by
    exactly one variable."""
    p.add_argument("--wire", default=None,
                   choices=("raw", "precrop", "pack", "precrop+pack"),
                   help="wire format for the device-transform feed: raw "
                        "uint8 records (default), host-side pre-crop to "
                        "the net's input geometry (crop/mirror still "
                        "applied on-device, bit-exact), lossless bit-pack "
                        "for low-entropy sources, or both. Default: "
                        "SPARKNET_WIRE env var, else raw")
    p.add_argument("--wire-bits", type=int, choices=(1, 2, 4, 8),
                   default=None,
                   help="pack width for --wire pack modes (8 = no pack); "
                        "default: SPARKNET_WIRE_BITS env var, else "
                        "inferred from the first record and enforced "
                        "losslessly (out-of-range batches raise)")
    p.add_argument("--staging", choices=("on", "off"), default=None,
                   help="true double-buffered H2D staging: dispatch batch "
                        "N+1's transfer non-blocking into a rotating slot "
                        "while step N runs (data/prefetch.py H2DStager). "
                        "off = the blocking device_put in the prefetch "
                        "worker. Default: SPARKNET_STAGING env var, "
                        "else on")
    p.add_argument("--echo", type=int, default=None, metavar="E",
                   help="data echoing: serve each transferred batch E "
                        "times, with fresh on-device crop/mirror draws "
                        "per echo (Choi et al.) — for transfer-bound "
                        "links. Default: SPARKNET_ECHO env var, else 1")
    p.add_argument("--shard-ingest", choices=("on", "off"), default=None,
                   help="per-host sharded ingest in multi-process runs: "
                        "each host reads only its owned record partition "
                        "(data/ingest.py; ownership re-spreads with "
                        "elastic membership). Default: "
                        "SPARKNET_SHARD_INGEST env var, else on")


def _apply_feed_flags(args):
    import os
    if getattr(args, "wire", None) is not None:
        os.environ["SPARKNET_WIRE"] = args.wire
    if getattr(args, "wire_bits", None) is not None:
        os.environ["SPARKNET_WIRE_BITS"] = str(args.wire_bits)
    if getattr(args, "staging", None) is not None:
        os.environ["SPARKNET_STAGING"] = args.staging
    if getattr(args, "echo", None) is not None:
        os.environ["SPARKNET_ECHO"] = str(args.echo)
    if getattr(args, "shard_ingest", None) is not None:
        os.environ["SPARKNET_SHARD_INGEST"] = args.shard_ingest
    echo = int(os.environ.get("SPARKNET_ECHO", "1") or 1)
    wire = os.environ.get("SPARKNET_WIRE", "raw") or "raw"
    if echo > 1 and "precrop" in wire:
        raise SystemExit(
            "--echo > 1 is incompatible with a precrop wire mode: "
            "pre-cropping bakes the crop window into the shipped bytes, "
            "so echoes could not get fresh crop draws (use --wire raw "
            "or --wire pack with echo)")


def _add_heartbeat_flags(p):
    """--heartbeat-dir / --lease-s / --heartbeat-interval: host-level
    fault domains (resilience/heartbeat.py). Passing --heartbeat-dir
    arms leased liveness + the pre-round rendezvous gate; in a
    multi-process world it also selects the snapshot writer and the
    coordinated-restart barrier."""
    p.add_argument("--heartbeat-dir", metavar="DIR",
                   help="shared rendezvous directory (every host must "
                        "reach it): arms leased heartbeats, host-level "
                        "eviction on lease expiry, the no-hang round "
                        "gate, and coordinated restart on quorum loss")
    p.add_argument("--lease-s", type=float, default=3.0,
                   help="heartbeat lease: a host silent this long is "
                        "dead (evicted at the next round gate)")
    p.add_argument("--heartbeat-interval", type=float, default=0.5,
                   help="seconds between heartbeat re-leases (must be "
                        "well under --lease-s)")
    p.add_argument("--grow", action="store_true",
                   help="late-join an already-RUNNING world through "
                        "--heartbeat-dir: this standalone process scans "
                        "the fresh leases, takes the next host id, and "
                        "is admitted at the incumbents' next round gate "
                        "(zero recompiles); pair with --resume auto "
                        "--reshard auto to bootstrap weights from the "
                        "running world's checkpoint")


def _apply_heartbeat_flags(solver, args):
    if not getattr(args, "heartbeat_dir", None) or \
            not hasattr(solver, "arm_heartbeat"):
        return
    solver.arm_heartbeat(args.heartbeat_dir,
                         interval_s=args.heartbeat_interval,
                         lease_s=args.lease_s,
                         grow=getattr(args, "grow", False))


def _add_elastic_flags(p):
    """--quorum / --evict-after / --readmit-after: the elastic
    membership layer (resilience/elastic.py). Passing any of them arms
    an ElasticPolicy on the sharded solver."""
    p.add_argument("--quorum", type=int, default=0, metavar="N",
                   help="arm elastic membership: sync rounds become "
                        "validity-masked quorum averages that survive "
                        "worker loss; abort with exit 4 when fewer than "
                        "N workers are live (0 = elasticity off unless "
                        "--evict-after/--readmit-after is given, then "
                        "quorum defaults to 1)")
    p.add_argument("--evict-after", type=int, default=None, metavar="R",
                   help="evict a worker after R consecutive rounds with "
                        "an invalid (non-finite) contribution "
                        "(default 2); its data shard re-spreads over "
                        "the survivors")
    p.add_argument("--readmit-after", type=int, default=None, metavar="R",
                   help="readmit an evicted worker after an R-round "
                        "cooldown, restarting it from the consensus "
                        "weights (default 5; 0 = never readmit)")
    p.add_argument("--staleness", type=int, default=None, metavar="S",
                   help="arm the ASYNC bounded-staleness update mode "
                        "(the knob next to --tau): rounds are barrier-"
                        "free — a worker up to S rounds behind the "
                        "fastest live peer still contributes "
                        "(staleness-discounted), beyond S it is parked "
                        "and resynced from the consensus; the round "
                        "never waits for a straggler. S=0 is bit-for-"
                        "bit the synchronous masked round")
    p.add_argument("--s-decay", type=float, default=0.5,
                   help="geometric per-round-of-lag discount applied "
                        "to stale contributions in async mode "
                        "(1.0 = no discount inside the bound)")
    p.add_argument("--unpark-after", type=int, default=1, metavar="R",
                   help="rounds a parked (over-stale) worker spends "
                        "resyncing before it rejoins at the front "
                        "(async mode; default 1)")
    p.add_argument("--evict-stale-after", type=int, default=0,
                   metavar="K",
                   help="evict a worker after K chronic parks without "
                        "a sustained in-bound stretch (async mode; "
                        "0 = park/resync forever, never evict)")


def _apply_elastic_flags(solver, args):
    if not hasattr(solver, "arm_elastic"):
        return
    on = args.quorum > 0 or args.evict_after is not None \
        or args.readmit_after is not None
    if on:
        solver.arm_elastic(
            quorum=max(1, args.quorum),
            evict_after=args.evict_after
            if args.evict_after is not None else 2,
            readmit_after=args.readmit_after
            if args.readmit_after is not None else 5)
    if getattr(args, "staleness", None) is not None and \
            hasattr(solver, "arm_staleness"):
        # after arm_elastic: the policy the flags armed gains the
        # staleness fields (arm_staleness updates it in place)
        solver.arm_staleness(args.staleness, decay=args.s_decay,
                             unpark_after=args.unpark_after,
                             evict_parked_after=args.evict_stale_after)


def _add_health_flags(p):
    """--health-* threshold flags shared by the training verbs; applied
    via _apply_health_flags after the solver is built."""
    p.add_argument("--no-health", action="store_true",
                   help="disable the training-dynamics health detectors")
    p.add_argument("--health-straggler-factor", type=float, default=1.5,
                   help="flag a worker whose round latency exceeds this "
                        "factor x the median of its peers")
    p.add_argument("--health-loss-skew-factor", type=float, default=3.0,
                   help="flag when the per-worker loss spread jumps past "
                        "this factor x its rolling EMA")
    p.add_argument("--health-div-abs", type=float, default=0.0,
                   help=">0: critical alarm when mean worker divergence "
                        "crosses this absolute L2 threshold")
    p.add_argument("--health-trend-rounds", type=int, default=5,
                   help="divergence-trend alarm window (consecutive "
                        "growing observations)")
    p.add_argument("--health-trend-factor", type=float, default=2.0,
                   help="total growth over the trend window that "
                        "triggers the divergence-trend alarm")
    p.add_argument("--health-cooldown", type=int, default=5,
                   help="min observations between same-kind alarms")
    p.add_argument("--health-arm-recovery", action="store_true",
                   help="critical health alarms arm the divergence "
                        "RecoveryPolicy if none is armed yet")


def _apply_health_flags(solver, args):
    if getattr(solver, "metrics", None) is None or \
            not hasattr(solver, "arm_health"):
        return
    if getattr(args, "no_health", False):
        solver.arm_health(enabled=False)
        return
    solver.arm_health(
        straggler_factor=args.health_straggler_factor,
        loss_skew_factor=args.health_loss_skew_factor,
        div_abs=args.health_div_abs,
        trend_rounds=args.health_trend_rounds,
        trend_factor=args.health_trend_factor,
        cooldown=args.health_cooldown,
        arm_recovery=args.health_arm_recovery)


def cmd_lint(args):
    """JAX-aware static analysis (sparknet_tpu.analysis): host-sync /
    recompile / PRNG-reuse / collective-axis hazards in compiled code
    plus the guarded-by lock-discipline race checker for the threaded
    host side. No jax import — runs on any checkout."""
    from .analysis.cli import run_lint
    return run_lint(args)


def cmd_imagenet(args):
    from .apps import ImageNetApp
    app = ImageNetApp(num_workers=args.workers, strategy=args.strategy,
                      tau=args.tau, batch=args.batch, log_path=args.log,
                      num_classes=args.classes, metrics_path=args.metrics)
    app.run(num_rounds=args.rounds)
    return 0


# deprecated tool shims (reference tools/{train,test,finetune}_net.cpp,
# net_speed_benchmark.cpp: LOG(FATAL) pointing at the real verb). Handled
# before argparse so legacy flag syntax still reaches the redirect message.
_DEPRECATED_VERBS = {
    "train_net": "train --solver=... [--snapshot=...]",
    "test_net": "test --model=... --weights=... [--iterations=50]",
    "finetune_net": "train --solver=... --weights=...",
    "net_speed_benchmark": "time --model=... [--iterations=50]",
}


def main(argv=None):
    args0 = sys.argv[1:] if argv is None else argv
    if args0 and args0[0] in _DEPRECATED_VERBS:
        print(f"Deprecated. Use sparknet {_DEPRECATED_VERBS[args0[0]]} "
              "instead.", file=sys.stderr)
        return 1
    p = argparse.ArgumentParser(
        prog="sparknet",
        description="TPU-native SparkNet: train/test/time/apps")
    sub = p.add_subparsers(dest="verb", required=True)

    t = sub.add_parser("train", help="train from a solver prototxt")
    t.add_argument("--solver", required=True)
    t.add_argument("--weights", help=".caffemodel to finetune from")
    t.add_argument("--snapshot", help=".solverstate to resume from")
    t.add_argument("--iterations", type=int, default=None)
    t.add_argument("--strategy", choices=("single", "dp"), default="single")
    t.add_argument("--mesh", help='e.g. "data=8"')
    t.add_argument("--snapshot-prefix",
                   help="override the solver's snapshot_prefix")
    t.add_argument("--input-shape", action="append", default=[],
                   help='feed blob shape hint, e.g. "data=100,3,32,32" '
                        "(stands in for the LMDB record shape)")
    t.add_argument("--metrics", help="JSONL metrics output path")
    t.add_argument("--profile",
                   help="write a jax.profiler trace of one steady-state "
                        "100-iter block to this directory (`caffe time`'s "
                        "deeper sibling; view with tensorboard/xprof)")
    t.add_argument("--stall-seconds", type=float, default=0,
                   help="arm a stall/NaN watchdog with this timeout")
    t.add_argument("--host-transform", action="store_true",
                   help="apply crop/mirror/mean on the HOST (native kernel) "
                        "and ship float32 crops, instead of the default "
                        "on-device transform fed raw uint8 records")
    t.add_argument("--sigint_effect", default="stop",
                   choices=("snapshot", "stop", "snapshot_stop", "none"))
    t.add_argument("--sighup_effect", default="snapshot",
                   choices=("snapshot", "stop", "snapshot_stop", "none"))
    t.add_argument("--sigterm_effect", default="snapshot_stop",
                   choices=("snapshot", "stop", "snapshot_stop", "none"),
                   help="preemption-notice handling; the default snapshots "
                        "then stops, so `--resume auto` can continue")
    t.add_argument("--resume", metavar="auto|STATE",
                   help="'auto': continue from the newest valid snapshot "
                        "under the snapshot prefix (partial/corrupt ones "
                        "are skipped with a reason); or an explicit "
                        ".solverstate[.h5] path")
    t.add_argument("--reshard", choices=("strict", "auto"),
                   default="strict",
                   help="cross-world restore policy: 'strict' refuses a "
                        "snapshot stamped by a different world "
                        "(WorldMismatch names both worlds); 'auto' "
                        "re-partitions it for THIS world — an 8-way "
                        "run's checkpoint resumes on 4 or 16 "
                        "(resilience/checkpoint.reshard_for_world)")
    t.add_argument("--keep", type=int, default=5,
                   help="snapshot retention: keep the newest N manifested "
                        "snapshots, delete older ones (0 = keep all)")
    t.add_argument("--recover", type=int, default=0, metavar="N",
                   help="arm divergence recovery: roll back to the last "
                        "known-good state on NaN/exploding loss, up to N "
                        "consecutive times before a clean abort (exit 3)")
    t.add_argument("--recover-lr-decay", type=float, default=1.0,
                   help="multiply the lr schedule by this on every "
                        "rollback (e.g. 0.5)")
    t.add_argument("--recover-explode-factor", type=float, default=0.0,
                   help=">0: also roll back when the loss exceeds this "
                        "factor times its recent healthy EMA")
    _add_perf_flags(t)
    _add_feed_flags(t)
    t.add_argument("--chaos", metavar="SPEC",
                   help="deterministic fault injection, e.g. "
                        "'nan_step=30,io_p=0.02,sigterm_round=3,seed=1' "
                        "(also via SPARKNET_CHAOS; see "
                        "sparknet_tpu/resilience/chaos.py)")
    _add_health_flags(t)
    _add_elastic_flags(t)
    _add_heartbeat_flags(t)
    t.set_defaults(fn=cmd_train)

    te = sub.add_parser("test", help="score a model")
    te.add_argument("--model", required=True)
    te.add_argument("--weights")
    te.add_argument("--iterations", type=int, default=50)
    te.add_argument("--input-shape", action="append", default=[])
    te.set_defaults(fn=cmd_test)

    ti = sub.add_parser("time", help="per-layer timing")
    ti.add_argument("--model", required=True)
    ti.add_argument("--iterations", type=int, default=10)
    ti.add_argument("--input-shape", action="append", default=[])
    ti.set_defaults(fn=cmd_time)

    d = sub.add_parser("device_query", help="list devices")
    d.set_defaults(fn=cmd_device_query)

    cc = sub.add_parser("convert_cifar_data",
                        help="CIFAR-10 .bin batches -> train/test LMDBs")
    cc.add_argument("input", help="dir with data_batch_*.bin + test_batch.bin")
    cc.add_argument("output", help="dir to create cifar10_{train,test}_lmdb")
    cc.set_defaults(fn=cmd_convert_cifar)

    ms = sub.add_parser("make_synth_cifar",
                        help="synthetic CIFAR-format dataset (zero-egress "
                             "stand-in for get_cifar10.sh)")
    ms.add_argument("output", help="dir for data_batch_*.bin/test_batch.bin")
    ms.add_argument("--train", type=int, default=50000)
    ms.add_argument("--test", type=int, default=10000)
    ms.add_argument("--seed", type=int, default=0)
    ms.add_argument("--noise", type=float, default=28.0)
    ms.add_argument("--label-noise", type=float, default=0.0,
                    help="fraction of labels resampled uniformly (hard "
                         "mode: caps accuracy at (1-p)+p/10)")
    ms.set_defaults(fn=cmd_make_synth_cifar)

    cm = sub.add_parser("compute_image_mean",
                        help="Datum DB -> mean image .binaryproto")
    cm.add_argument("db")
    cm.add_argument("output")
    cm.add_argument("--backend", choices=("lmdb", "leveldb"), default=None,
                    help="DB backend (default: sniff the directory layout)")
    cm.set_defaults(fn=cmd_compute_mean)

    ci = sub.add_parser("convert_imageset",
                        help='images + "path label" listfile -> Datum DB')
    ci.add_argument("root", help="root folder of image paths")
    ci.add_argument("listfile")
    ci.add_argument("db")
    ci.add_argument("--resize_height", type=int, default=0)
    ci.add_argument("--resize_width", type=int, default=0)
    ci.add_argument("--gray", action="store_true")
    ci.add_argument("--shuffle", action="store_true")
    ci.add_argument("--encoded", action="store_true")
    ci.add_argument("--backend", choices=["lmdb", "leveldb"],
                    default="lmdb")
    ci.set_defaults(fn=cmd_convert_imageset)

    for verb, bin_ in (("upgrade_net_proto_text", False),
                       ("upgrade_net_proto_binary", True)):
        u = sub.add_parser(verb,
                           help="V0/V1 NetParameter file -> latest format")
        u.add_argument("input")
        u.add_argument("output")
        u.set_defaults(fn=cmd_upgrade_net_proto, binary=bin_)

    us = sub.add_parser("upgrade_solver_proto_text",
                        help="solver_type enum -> type string")
    us.add_argument("input")
    us.add_argument("output")
    us.set_defaults(fn=cmd_upgrade_solver_proto)

    ef = sub.add_parser("extract_features",
                        help="forward a net, write named blobs as "
                             "float-Datum DBs — positional order matches "
                             "the reference binary "
                             "(tools/extract_features.cpp): "
                             "weights model blobs dbs n [db_type]")
    ef.add_argument("weights",
                    help=".caffemodel (the reference's pretrained_net_param "
                         "first positional); pass `none` for random init")
    ef.add_argument("model", help="feature-extraction prototxt with a "
                                  "TEST data layer")
    ef.add_argument("blobs", help="blob_name1[,name2,...]")
    ef.add_argument("dbs", help="db_path1[,path2,...]")
    ef.add_argument("num_batches", type=int)
    ef.add_argument("db_type", nargs="?", default="lmdb")
    ef.set_defaults(fn=cmd_extract_features)

    c = sub.add_parser("cifar", help="CifarApp driver")
    c.add_argument("--workers", type=int, default=None)
    c.add_argument("--data", help="dir with CIFAR-10 .bin batches")
    c.add_argument("--prototxt-dir", help="dir with stock cifar10 prototxts")
    c.add_argument("--strategy", choices=("local_sgd", "dp"),
                   default="local_sgd")
    c.add_argument("--hosts", type=int, default=0,
                   help="N>0: hierarchical local SGD over N host fault "
                        "domains (two-tier: per-step grad pmean inside "
                        "a host, tau-interval masked averaging across "
                        "hosts; membership/eviction at host "
                        "granularity). Single-process: N virtual "
                        "domains partition the local devices; "
                        "multi-process: one domain per process")
    c.add_argument("--tau", type=int, default=10)
    c.add_argument("--rounds", type=int, default=20)
    c.add_argument("--test-every", type=int, default=10,
                   help="test every N rounds (CifarApp.scala:98)")
    c.add_argument("--log")
    c.add_argument("--metrics", help="JSONL metrics output path")
    c.add_argument("--snapshot-prefix",
                   help="write periodic snapshots under this prefix "
                        "(enables --resume auto and the QuorumLost "
                        "best-effort snapshot)")
    c.add_argument("--snapshot-every", type=int, default=0,
                   help="snapshot every N rounds (0 disables)")
    c.add_argument("--resume", metavar="auto|STATE",
                   help="'auto': continue from the newest valid snapshot "
                        "under --snapshot-prefix; or an explicit "
                        ".solverstate[.h5] path")
    c.add_argument("--reshard", choices=("strict", "auto"),
                   default="strict",
                   help="cross-world restore policy: 'auto' re-partitions "
                        "a snapshot stamped by a different world for THIS "
                        "world (8-way checkpoint resumes on 4 or 16); "
                        "'strict' refuses with WorldMismatch")
    c.add_argument("--chaos", metavar="SPEC",
                   help="deterministic fault injection (e.g. "
                        "'stall_step=10,stall_s=2,stall_worker=1' to "
                        "simulate a straggler, or "
                        "'kill_worker=1,kill_round=3' to crash a worker "
                        "mid-run; also via SPARKNET_CHAOS)")
    _add_perf_flags(c)
    _add_feed_flags(c)
    _add_health_flags(c)
    _add_elastic_flags(c)
    _add_heartbeat_flags(c)
    c.set_defaults(fn=cmd_cifar)

    lm = sub.add_parser("lm", help="transformer-LM driver (synthetic "
                                   "bigram corpus; optional GPipe pipeline)")
    lm.add_argument("--vocab", type=int, default=512)
    lm.add_argument("--seq-len", type=int, default=256)
    lm.add_argument("--batch", type=int, default=8)
    lm.add_argument("--d-model", type=int, default=256)
    lm.add_argument("--layers", type=int, default=4)
    lm.add_argument("--heads", type=int, default=8)
    lm.add_argument("--steps", type=int, default=500)
    lm.add_argument("--lr", type=float, default=3e-4)
    lm.add_argument("--solver-type", default="Adam")
    lm.add_argument("--seed", type=int, default=0)
    lm.add_argument("--display", type=int, default=50)
    lm.add_argument("--dtype", choices=("f32", "bf16"), default="f32")
    lm.add_argument("--no-flash", action="store_true",
                    help="dense attention instead of the pallas kernel")
    lm.add_argument("--moe-experts", type=int, default=0)
    lm.add_argument("--moe-aux-weight", type=float, default=0.01,
                    help="Switch load-balancing aux loss weight")
    lm.add_argument("--ep", type=int, default=1,
                    help="N>1: ExpertParallelSolver over an N-way "
                         "\"expert\" mesh axis (needs --moe-experts)")
    lm.add_argument("--dp", type=int, default=1,
                    help="data-parallel ways composed with --ep "
                         "(mesh {data: dp, expert: ep})")
    lm.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel ways composed with --ep: "
                         "dp x sp x ep long-context MoE (ring attention "
                         "over \"seq\")")
    lm.add_argument("--pipeline-stages", type=int, default=1,
                    help="N>1: run the trunk as an N-stage GPipe pipeline "
                         "over a pipe mesh axis (PipelineLMSolver)")
    lm.add_argument("--microbatches", type=int, default=0)
    _add_perf_flags(lm, scan=True)
    _add_sharding_flags(lm)
    lm.add_argument("--metrics", help="JSONL loss-curve output path")
    lm.add_argument("--snapshot-every", type=int, default=0)
    lm.add_argument("--snapshot-prefix")
    lm.add_argument("--resume", help=".lm.npz (pipeline) or "
                                     ".solverstate.h5 to resume from")
    lm.set_defaults(fn=cmd_lm)

    rp = sub.add_parser("report",
                        help="aggregate a --metrics JSONL into a run "
                             "report (phases, step percentiles, comms, "
                             "recompiles, loss curve)")
    rp.add_argument("jsonl", help="metrics JSONL written by --metrics")
    rp.add_argument("--json", help="also write machine-readable report "
                                   "JSON here (BENCH_*.json-comparable)")
    rp.add_argument("--chrome", help="also export the run's spans as a "
                                     "Chrome trace_event file")
    rp.add_argument("--since", type=float, default=None, metavar="T",
                    help="only aggregate events from T seconds into the "
                         "run on (the JSONL 't' field); selecting zero "
                         "events is an error (exit 2), never an empty "
                         "report that reads as healthy")
    rp.add_argument("--event", metavar="KINDS",
                    help="comma-separated event kinds to aggregate "
                         "(e.g. 'health,divergence'); selecting zero "
                         "events is an error (exit 2)")
    rp.add_argument("--format", choices=("text", "json"), default="text",
                    help="json: print the report dict itself on stdout "
                         "(stable keys mirroring the rendered sections) "
                         "for CI / perf-gate assertions")
    rp.set_defaults(fn=cmd_report)

    mo = sub.add_parser("monitor",
                        help="tail a --metrics JSONL and render a live "
                             "terminal summary (round/loss per worker, "
                             "divergence, stragglers, memory, alarms)")
    mo.add_argument("jsonl", help="metrics JSONL a run is writing "
                                  "via --metrics")
    mo.add_argument("--interval", type=float, default=1.0,
                    help="refresh period in seconds")
    mo.add_argument("--once", action="store_true",
                    help="render the current state once and exit")
    mo.add_argument("--wait", action="store_true",
                    help="wait for the file to appear instead of erroring "
                         "(a run that hasn't started writing yet)")
    mo.add_argument("--duration", type=float, default=None,
                    help="stop after this many seconds (default: forever)")
    mo.set_defaults(fn=cmd_monitor)

    tr = sub.add_parser(
        "trace",
        help="merge per-host metrics JSONLs into one clock-aligned "
             "fleet timeline: Chrome trace export (one track per host "
             "+ clock-offset metadata) and per-round critical-path "
             "attribution naming the blocking host and phase")
    tr.add_argument("metrics", nargs="+",
                    help="metrics JSONL file(s) — one per host, or one "
                         "multiplexed simfleet stream")
    tr.add_argument("--chrome", metavar="OUT",
                    help="write the merged Chrome trace_event file here")
    tr.add_argument("--critpath", action="store_true",
                    help="render the per-round critical-path "
                         "decomposition (blocking host, phases, top "
                         "blockers, comms exposure)")
    tr.add_argument("--round", type=int, default=None, metavar="N",
                    help="limit --critpath to round N")
    tr.add_argument("--json", action="store_true",
                    help="emit the critpath/alignment result as JSON "
                         "on stdout instead of text")
    tr.set_defaults(fn=cmd_trace)

    sf = sub.add_parser(
        "simfleet",
        help="discrete-event fleet simulator: thousands of virtual "
             "hosts drive the real heartbeat/consensus/elastic-policy "
             "code (simulated clock, in-memory rendezvous) — single "
             "runs, --sweep grids, and replay validation against a "
             "recorded real multi-coordinator run")
    sf.add_argument("--hosts", type=int, default=64,
                    help="virtual fleet size")
    sf.add_argument("--rounds", type=int, default=50,
                    help="simulated training rounds")
    sf.add_argument("--interval", type=float, default=0.5,
                    help="heartbeat interval_s, simulated seconds")
    sf.add_argument("--lease", type=float, default=3.0,
                    help="heartbeat lease_s, simulated seconds")
    sf.add_argument("--round_s", type=float, default=None,
                    help="simulated round duration (default: "
                         "tau * step_s)")
    sf.add_argument("--tau", type=int, default=4,
                    help="local steps per consensus round (round_s = "
                         "tau * step_s — sweeping tau changes how much "
                         "compute amortizes each gate)")
    sf.add_argument("--step_s", type=float, default=0.25,
                    help="simulated seconds per local step")
    sf.add_argument("--jitter", type=float, default=0.15,
                    help="per-host round-duration jitter (std dev "
                         "fraction, seeded)")
    sf.add_argument("--quorum", type=int, default=1,
                    help="ElasticPolicy quorum (exit 4 below it)")
    sf.add_argument("--evict_after", type=int, default=1,
                    help="ElasticPolicy evict_after")
    sf.add_argument("--readmit_after", type=int, default=0,
                    help="ElasticPolicy readmit cooldown (0 = never)")
    sf.add_argument("--staleness", type=int, default=None,
                    help="bounded-staleness s (parking past it)")
    sf.add_argument("--s_decay", type=float, default=0.5,
                    help="staleness consensus weight decay per lag")
    sf.add_argument("--consensus",
                    choices=("auto", "sync", "async", "none"),
                    default="auto",
                    help="cross-host transport: the real File/"
                         "AsyncFileConsensus at small fleets, policy-"
                         "level version clocks at scale (auto)")
    sf.add_argument("--recover_after", type=int, default=0,
                    help="revive chaos-killed hosts after this many "
                         "rounds (0 = never) — the MTBF repair half")
    sf.add_argument("--chaos",
                    help="chaos spec, e.g. 'fail_rate=0.001,"
                         "fail_seed=7,fail_corr=8' or 'kill_host=2,"
                         "kill_host_round=5' (resilience/chaos.py)")
    sf.add_argument("--seed", type=int, default=0,
                    help="master seed: same spec + seed = same "
                         "timeline, to the event")
    sf.add_argument("--metrics",
                    help="JSONL metrics output — the standard stream; "
                         "renders through `sparknet report`/`monitor` "
                         "unchanged")
    sf.add_argument("--json", help="write the summary (or sweep "
                                   "results) JSON here")
    sf.add_argument("--sweep", action="append", metavar="GRID",
                    help="axis grid 'hosts=200:1000,fail_rate="
                         "0.0005:0.005' (Cartesian; repeatable — "
                         "cells accumulate)")
    sf.add_argument("--budget_s", type=float, default=None,
                    help="real wall-clock budget for a sweep; unfired "
                         "cells are reported, never silently dropped")
    sf.add_argument("--record_real", metavar="OUT",
                    help="run a REAL multi-coordinator SIGKILL-shaped "
                         "scenario (threads + wall clock + on-disk "
                         "rendezvous) and record its membership "
                         "sequence to OUT for --replay")
    sf.add_argument("--replay", metavar="REC",
                    help="re-run a recording in the simulator; exit 1 "
                         "unless the membership sequence matches "
                         "exactly")
    sf.add_argument("-v", "--verbose", action="store_true",
                    help="log the simulated fleet's membership story")
    # -- the SERVING-fleet simulator (sim/servefleet.py) --
    sf.add_argument("--serve", action="store_true",
                    help="simulate the serving fleet instead: virtual "
                         "replicas + the REAL router/autoscaler/canary "
                         "under open-loop arrival traces; exit 1 when "
                         "any request is lost without an explicit "
                         "429/5xx")
    sf.add_argument("--replicas", type=int, default=3,
                    help="(--serve) initial replica count")
    sf.add_argument("--windows", type=int, default=30,
                    help="(--serve) router windows to simulate")
    sf.add_argument("--window_s", type=float, default=1.0,
                    help="(--serve) router window, simulated seconds")
    sf.add_argument("--service_ms", type=float, default=20.0,
                    help="(--serve) per-request service time")
    sf.add_argument("--queue_limit", type=int, default=64,
                    help="(--serve) per-replica queue bound (429 past "
                         "it)")
    sf.add_argument("--rate", type=float, default=40.0,
                    help="(--serve) base arrival rate, req/s")
    sf.add_argument("--trace",
                    choices=("flat", "diurnal", "spike", "flash"),
                    default="flat",
                    help="(--serve) open-loop arrival shape")
    sf.add_argument("--spike_x", type=float, default=4.0,
                    help="(--serve) spike/flash rate multiplier")
    sf.add_argument("--slo_p99_ms", type=float, default=500.0,
                    help="(--serve) autoscaler p99 target")
    sf.add_argument("--slo_depth", type=int, default=32,
                    help="(--serve) autoscaler queue-depth target")
    sf.add_argument("--breach_windows", type=int, default=3,
                    help="(--serve) consecutive breach windows before "
                         "grow")
    sf.add_argument("--idle_windows", type=int, default=10,
                    help="(--serve) consecutive idle windows before "
                         "shrink")
    sf.add_argument("--max_replicas", type=int, default=8,
                    help="(--serve) autoscaler growth ceiling")
    sf.add_argument("--canary_w", type=int, default=0,
                    help="(--serve) window at which one replica "
                         "hot-reloads to a faulty sha (0 = never)")
    sf.add_argument("--canary_pct", type=float, default=20.0,
                    help="(--serve) canary traffic percentage")
    sf.add_argument("--canary_err", type=float, default=1.0,
                    help="(--serve) canary per-request fault "
                         "probability")
    sf.add_argument("--canary_min_requests", type=int, default=10,
                    help="(--serve) canary verdict sample floor")
    sf.add_argument("--die_w", type=int, default=None,
                    help="(--serve) window at which the lowest live "
                         "replica dies (deterministic kill)")
    sf.add_argument("--rejoin_w", type=int, default=None,
                    help="(--serve) window at which a dead replica "
                         "rejoins")
    sf.add_argument("--trace_sample", type=float, default=1.0,
                    help="(--serve) serve_trace head-sampling rate "
                         "(1.0 = every request)")
    sf.add_argument("--trace_tail_ms", type=float, default=None,
                    help="(--serve) always keep serve_trace exemplars "
                         "at/above this latency, regardless of "
                         "sampling")
    sf.add_argument("--slo_burn", action="store_true",
                    help="(--serve) track the SLO error budget and "
                         "multi-window burn-rate alerts")
    sf.add_argument("--burn_scale", type=float, default=1.0,
                    help="(--serve) burn-rate window scale (0.01 "
                         "shrinks the 5m/1h/6h windows 100x for "
                         "short sims)")
    sf.set_defaults(fn=cmd_simfleet)

    sv = sub.add_parser(
        "serve",
        help="serve a resilient checkpoint over HTTP: weights-only "
             "load, continuous batching into power-of-two buckets, "
             "hot reload on new snapshots, graceful SIGTERM drain")
    sv.add_argument("--prefix", required=True,
                    help="snapshot prefix (the training run's "
                         "--snapshot_prefix; reads <prefix>.latest.json)")
    sv.add_argument("--model",
                    help="deploy/net prototxt (optional for binaryproto "
                         "checkpoints — the model blob is "
                         "self-describing; required for .h5)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (announced on stdout)")
    sv.add_argument("--max_batch", type=int, default=8,
                    help="largest padding bucket; buckets are powers "
                         "of two up to this, one jit each")
    sv.add_argument("--max_wait_ms", type=float, default=5.0,
                    help="deadline: a batch closes once its oldest "
                         "request waited this long, even unfilled")
    sv.add_argument("--queue_limit", type=int, default=64,
                    help="queued-row bound; submissions beyond it get "
                         "429 (backpressure, not a latency tail)")
    sv.add_argument("--reload_poll", type=float, default=2.0,
                    help="seconds between manifest polls for hot "
                         "reload (0 disables)")
    sv.add_argument("--request_timeout", type=float, default=30.0,
                    help="per-request inference timeout (504 past it)")
    sv.add_argument("--no_warmup", action="store_true",
                    help="skip tracing every bucket before traffic")
    sv.add_argument("--metrics", help="JSONL metrics output path")
    sv.add_argument("--fleet_dir",
                    help="fleet rendezvous directory: lease this "
                         "replica into the serving fleet "
                         "(serve/fleet.py) for `sparknet route`")
    sv.add_argument("--replica", type=int, default=0,
                    help="this replica's id in the fleet (also tags "
                         "the chaos injectors)")
    sv.add_argument("--replicas", type=int, default=0,
                    help="initial fleet size hint (a higher --replica "
                         "grows the world, the PR 12 admission path)")
    sv.add_argument("--lease", type=float, default=3.0,
                    help="fleet lease_s: the router evicts this "
                         "replica when its beat goes stale past this")
    sv.add_argument("--heartbeat_interval", type=float, default=0.5,
                    help="fleet beat cadence (also bounds how stale "
                         "the router's queue-depth view can be)")
    sv.add_argument("--chaos",
                    help="chaos spec, e.g. 'kill_replica=0,kill_req=20'"
                         " (SIGKILL self after the 20th request) or "
                         "'slow_replica=0,slow_ms=50' "
                         "(resilience/chaos.py)")
    sv.add_argument("--trace_sample", type=float, default=1.0,
                    help="serve_trace head-sampling rate (1.0 = every "
                         "request emits a trace event)")
    sv.add_argument("--trace_tail_ms", type=float, default=250.0,
                    help="always keep serve_trace exemplars at/above "
                         "this latency, regardless of sampling")
    _add_perf_flags(sv, scan=True)
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser(
        "route",
        help="serving-fleet router: discovers `sparknet serve "
             "--fleet_dir` replicas through their leases, spreads "
             "POST /predict by least queue depth with retry-once "
             "failover, makes SLO autoscaling decisions, auto-rolls-"
             "back a bad canary checkpoint")
    rt.add_argument("--fleet_dir", required=True,
                    help="the fleet rendezvous directory replicas "
                         "lease into")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (announced on stdout)")
    rt.add_argument("--replicas", type=int, default=1,
                    help="expected initial fleet size (late replicas "
                         "grow the world on admission)")
    rt.add_argument("--lease", type=float, default=3.0,
                    help="lease_s: a replica whose beat is staler "
                         "than this is evicted (failover window)")
    rt.add_argument("--window_s", type=float, default=1.0,
                    help="membership/SLO evaluation cadence")
    rt.add_argument("--request_timeout", type=float, default=30.0,
                    help="per-dispatch timeout toward a replica")
    rt.add_argument("--no_autoscale", action="store_true",
                    help="disable SLO autoscaling decisions")
    rt.add_argument("--slo_p99_ms", type=float, default=500.0,
                    help="autoscaler p99 target")
    rt.add_argument("--slo_depth", type=int, default=32,
                    help="autoscaler queue-depth target")
    rt.add_argument("--breach_windows", type=int, default=3,
                    help="consecutive breach windows before a grow "
                         "decision (scale events; an orchestrator "
                         "launches the replica)")
    rt.add_argument("--idle_windows", type=int, default=30,
                    help="consecutive idle windows before a shrink "
                         "(drain order to the highest replica)")
    rt.add_argument("--min_replicas", type=int, default=1)
    rt.add_argument("--max_replicas", type=int, default=8)
    rt.add_argument("--canary_pct", type=float, default=20.0,
                    help="traffic share for a second checkpoint sha "
                         "while a canary is in flight")
    rt.add_argument("--canary_min_requests", type=int, default=20,
                    help="canary responses required before a verdict")
    rt.add_argument("--canary_err_delta", type=float, default=0.05,
                    help="rollback when canary error rate exceeds "
                         "baseline by this")
    rt.add_argument("--canary_p99_delta_ms", type=float, default=500.0,
                    help="rollback when canary p99 exceeds baseline "
                         "by this")
    rt.add_argument("--metrics", help="JSONL metrics output path "
                                      "(route/scale/canary + "
                                      "membership events)")
    rt.add_argument("--trace_sample", type=float, default=1.0,
                    help="serve_trace head-sampling rate at the "
                         "router (1.0 = every request)")
    rt.add_argument("--trace_tail_ms", type=float, default=250.0,
                    help="always keep serve_trace exemplars at/above "
                         "this latency, regardless of sampling")
    rt.add_argument("--slo_ms", type=float, default=None,
                    help="error-budget SLO latency bound (default: "
                         "--slo_p99_ms)")
    rt.add_argument("--slo_objective", type=float, default=0.999,
                    help="error-budget availability objective "
                         "(fraction of requests that must be good)")
    rt.add_argument("--burn_scale", type=float, default=1.0,
                    help="burn-rate window scale (0.01 shrinks the "
                         "5m/1h/6h windows 100x for short runs)")
    rt.add_argument("--no_slo_burn", action="store_true",
                    help="disable the SLO error-budget ledger")
    rt.set_defaults(fn=cmd_route)

    sb = sub.add_parser(
        "serve-bench",
        help="load-generate against a running `sparknet serve` "
             "(closed loop = capacity, open loop = honest tail "
             "latency at a fixed arrival rate)")
    sb.add_argument("--url", required=True,
                    help="server base URL, e.g. http://127.0.0.1:8080")
    sb.add_argument("--mode", choices=("closed", "open", "both"),
                    default="closed")
    sb.add_argument("--concurrency", type=int, default=4,
                    help="closed loop: workers with one request in "
                         "flight each (also bounds open-loop dispatch)")
    sb.add_argument("--rate", type=float, default=50.0,
                    help="open loop: offered requests/second")
    sb.add_argument("--duration", type=float, default=5.0,
                    help="seconds per mode")
    sb.add_argument("--rows", type=int, default=1,
                    help="rows per request")
    sb.add_argument("--request_timeout", type=float, default=10.0)
    sb.add_argument("--metrics", help="JSONL metrics output path "
                                      "(bench rows)")
    sb.add_argument("--json", help="write per-mode summaries here")
    sb.set_defaults(fn=cmd_serve_bench)

    li = sub.add_parser(
        "lint",
        help="static analysis: JAX hazard rules (host syncs/recompiles/"
             "PRNG reuse/axis mismatches in jitted code), the "
             "guarded-by lock-discipline race checker, deadlock rules "
             "(lock-order cycles, blocking/callbacks under locks), "
             "distributed file-protocol rules (atomic rendezvous "
             "writes, bounded gates, canonical exit codes), and the "
             "metrics event-schema rules")
    li.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the "
                         "sparknet_tpu package source)")
    li.add_argument("--strict", action="store_true",
                    help="exit 1 on ANY non-baselined finding (warnings "
                         "included), stale baseline entries, or "
                         "baseline entries without a justification — "
                         "the CI mode (scripts/lint.sh)")
    li.add_argument("--baseline",
                    help="baseline file (default: "
                         ".sparknet-lint-baseline.json next to the lint "
                         "root, then CWD)")
    li.add_argument("--write-baseline", action="store_true",
                    help="accept the current findings into the baseline "
                         "(new entries need --justification; stale "
                         "entries expire)")
    li.add_argument("--justification",
                    help="justification text recorded on entries newly "
                         "added by --write-baseline")
    li.add_argument("--select", metavar="CODES",
                    help="comma-separated rule codes to run "
                         "(e.g. SPK101,SPK201), or a profile: @tests "
                         "(parse/file-protocol/exit-code rules for the "
                         "test tree), @tools (those plus the JAX "
                         "host-sync hazards, for scripts/ and "
                         "experiments/)")
    li.add_argument("--exclude", action="append", default=[],
                    metavar="PATTERN",
                    help="skip files whose path matches (substring, "
                         "glob, or path-component glob); repeatable — "
                         "e.g. --exclude fixtures")
    li.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="lint files across N forked workers (the "
                         "parsed project index is shared "
                         "copy-on-write)")
    li.add_argument("--cache", action="store_true",
                    help="reuse per-file results keyed on content + "
                         "rule sources + cross-module summaries "
                         "(.sparknet-lint-cache.json next to the "
                         "root; safe to delete any time)")
    li.add_argument("--write-event-schema", action="store_true",
                    help="regenerate sparknet_tpu/obs/event_schema.py "
                         "from the repo's metrics emit sites and exit "
                         "(rules SPK401/402 and "
                         "tests/test_event_schema.py check against "
                         "it)")
    li.add_argument("--root", help="directory finding paths are "
                                   "reported relative to (default: "
                                   "CWD, or the package parent when "
                                   "linting the default target)")
    li.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    li.add_argument("-v", "--verbose", action="store_true",
                    help="also print baselined findings with their "
                         "justifications")
    li.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    li.set_defaults(fn=cmd_lint)

    i = sub.add_parser("imagenet", help="ImageNetApp driver")
    i.add_argument("--workers", type=int, default=None)
    i.add_argument("--strategy", choices=("local_sgd", "dp"),
                   default="local_sgd")
    i.add_argument("--tau", type=int, default=50)
    i.add_argument("--batch", type=int, default=256)
    i.add_argument("--classes", type=int, default=1000)
    i.add_argument("--rounds", type=int, default=2)
    i.add_argument("--log")
    i.add_argument("--metrics", help="JSONL metrics output path")
    i.set_defaults(fn=cmd_imagenet)

    args = p.parse_args(argv)
    if args.verb in ("train", "test", "time", "device_query", "cifar",
                     "imagenet", "lm", "serve"):
        # one persistent compile cache for every verb that compiles,
        # placed by JAX_COMPILATION_CACHE_DIR or fixed in the checkout
        from .utils.compile_cache import configure_compile_cache
        configure_compile_cache()
        # multi-host bootstrap (no-op single-process; SPARKNET_COORDINATOR
        # et al. select the jax.distributed rendezvous — see DEPLOY.md)
        from .parallel import distributed_init
        distributed_init()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
