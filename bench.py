"""Headline benchmark: CaffeNet (AlexNet-class) training throughput.

Reference baseline (BASELINE.md): stock Caffe trains CaffeNet at 256-image
batches in 26.5 s / 20 iters on a K40 (~193 img/s), 19.2 s with cuDNN
(~267 img/s). We time the same workload — batch 256, 227x227, full
forward+backward+momentum-SGD update — as ONE jitted XLA step, mixed
precision (fp32 params, bf16 activations driving the MXU).

stdout: ONE JSON line {"metric", "value", "unit", "vs_baseline"} — the
synthetic-fed headline number (input pipeline excluded, like the reference's
in-memory LMDB page cache).
stderr: supplementary rows ("#BENCH {...}"): host-fed throughput (uint8
source batches shipped raw; crop/mirror/mean runs INSIDE the jitted step —
the honest end-to-end number, with a transfer-vs-compute breakdown), a
batch-512 variant, GoogLeNet, and transformer-LM rows at toy and real
scale. All rows also land in --details (bench_details.json, git-ignored).

Every timed row runs N windows (default 5, --windows N): the headline value
is the BEST window, and each row carries min/median/max across windows so
the spread is part of the record, not a caveat.

A measurement needs its device: no TPU, a device kind that _PEAK does not
list, or a row that fails is a non-zero exit — never a CPU number, a row
without MFU, or a skipped row behind exit code 0.
"""

import json
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC = 267.0   # K40 + cuDNN, caffe/docs/performance_hardware.md:19-25
WARMUP = 3
ITERS = 20
WINDOWS = 5

# bf16 peak FLOP/s by device kind (public TPU specs; MFU denominators)
_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def peak_flops(device_kind):
    """bf16 peak of a device kind from _PEAK. A device that is not in the
    table ends the run: MFU without its denominator silently vanishing
    is how a row stops being a measurement."""
    for k, v in _PEAK.items():
        if k.lower() in device_kind.lower():
            return v
    raise SystemExit(f"bench.py: device kind {device_kind!r} is not in "
                     f"_PEAK ({', '.join(_PEAK)}): add its published "
                     "bf16 peak before benchmarking on it")


def bench_device():
    """The chip this run measures. Anything else is refused: a CPU run
    must never produce rows under a device heading."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py: no TPU (jax found {dev.platform}: "
                         f"{dev.device_kind}); this benchmark does not "
                         "fall back to another backend")
    return dev


def model_train_flops_per_image(solver):
    """Analytic MXU FLOPs: 2*MACs forward for conv/fc, x3 for training
    (grad wrt activations + grad wrt weights each re-run the matmuls).
    Elementwise/LRN/pool FLOPs are excluded — this is the standard MFU
    numerator, so the reported MFU slightly *understates* utilization."""
    net = solver.net
    fwd = 0
    batch = None
    for lp, impl, bottoms, tops in net.layers:
        if lp.type == "Convolution":
            out = net.blob_shapes[tops[0]]
            n, co, ho, wo = out
            batch = batch or n
            ci = net.blob_shapes[bottoms[0]][1]
            cp = lp.convolution_param
            ks = [int(x) for x in cp.kernel_size]
            if ks:
                kh = kw = ks[0]
            else:                        # DSL nets use kernel_h/kernel_w
                kh = int(cp.kernel_h)
                kw = int(cp.kernel_w)
            group = int(cp.group) if cp.has("group") else 1
            fwd += 2 * n * co * ho * wo * (ci // group) * kh * kw
        elif lp.type == "InnerProduct":
            out = net.blob_shapes[tops[0]]
            n = out[0]
            batch = batch or n
            cin = int(np.prod(net.blob_shapes[bottoms[0]][1:]))
            fwd += 2 * n * out[1] * cin
    return 3 * fwd // (batch or 1)


def _time_windows(step, sync, iters=ITERS, windows=None):
    """Time `iters` steps per window, `windows` times. -> (best_dt, [dts]).
    Best-of-N is the headline;
    the full list feeds the min/median/max spread in each row."""
    dts = []
    for _ in range(windows or WINDOWS):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step()
        sync(out)   # a value fetch of the last step's output: the window
        # ends when the device has finished, not when dispatch returned
        dts.append(time.perf_counter() - t0)
    return min(dts), dts


def _rate_stats(unit_per_window, dts):
    """Per-window rates -> {"min","median","max","windows"} (rounded)."""
    rates = sorted(unit_per_window / dt for dt in dts)
    n = len(rates)
    med = rates[n // 2] if n % 2 else 0.5 * (rates[n // 2 - 1]
                                             + rates[n // 2])
    return {"min": round(rates[0], 1), "median": round(med, 1),
            "max": round(rates[-1], 1), "windows": n}


def _mem_cols(solver, batch):
    """Peak-HBM columns from the compiled step's memory_analysis —
    XLA's own accounting of what the step RESIDES in, per device (the
    number that decides whether a model fits, where throughput only
    says how fast it runs)."""
    ms = solver.compiled_memory_stats(batch)
    if not ms:
        raise RuntimeError("the compiled step exposes no memory analysis")
    mb = 1.0 / 2 ** 20
    return {"peak_hbm_mb": round(ms["peak_bytes"] * mb, 2),
            "hbm_argument_mb": round(ms["argument_bytes"] * mb, 2),
            "hbm_temp_mb": round(ms["temp_bytes"] * mb, 2)}


def _mk_solver(net_param, base_lr=0.01, compute_dtype=None):
    from sparknet_tpu.proto import Message
    from sparknet_tpu.solver.solver import Solver
    sp = Message("SolverParameter", base_lr=base_lr, lr_policy="fixed",
                 momentum=0.9, weight_decay=0.0005, display=0, random_seed=0)
    return Solver(sp, net_param=net_param, compute_dtype=compute_dtype)


def bench_synthetic(name, net_param, batch_size, shape, classes, peak):
    import jax.numpy as jnp
    solver = _mk_solver(net_param)
    rs = np.random.RandomState(0)
    data = jnp.asarray(rs.randn(batch_size, *shape), jnp.bfloat16)
    label = jnp.asarray(rs.randint(0, classes, batch_size), jnp.int32)
    batch = {"data": data, "label": label}
    for _ in range(WARMUP):
        loss = solver.train_step(batch)
    float(loss)
    dt, dts = _time_windows(lambda: solver.train_step(batch), float)
    img_s = batch_size * ITERS / dt
    flops = model_train_flops_per_image(solver)
    row = {"model": name, "mode": "synthetic", "batch": batch_size,
           "images_per_sec": round(img_s, 2),
           "images_per_sec_spread": _rate_stats(batch_size * ITERS, dts),
           "train_gflops_per_image": round(flops / 1e9, 2),
           "model_tflops_per_sec": round(img_s * flops / 1e12, 2)}
    row["mfu"] = round(img_s * flops / peak, 4)
    return row, solver


def bench_hostfed(name, net_param, batch_size, src_size, crop, classes,
                  peak):
    """The honest end-to-end row, transfer-minimal by design: the host
    ships the RAW uint8 source batch (src_size^2*3 bytes/img — 3.2x fewer
    than float32 crops) plus per-image crop/mirror draws, and the jitted
    step crops/mirrors/mean-subtracts on-chip (data/device_transform.py,
    semantics of reference data_transformer.cpp:42-51). A prefetch worker
    device_puts ahead of the step, so transfer overlaps compute.

    Also measures the two legs separately — pure H2D transfer of one
    uint8 batch, and the device step with a resident batch — so the row
    records *why* end-to-end lands where it does: good overlap means
    end-to-end ~= max(transfer, step).

    The input-pipeline levers (PERF.md "Input pipeline") are read from
    their SPARKNET_* env vars, so one env var flips this row between the
    raw baseline and any lever arm: SPARKNET_WIRE re-encodes the shipped
    batch (data/wire.py — h2d_kb_per_image reports the ACTUAL shipped
    bytes), SPARKNET_STAGING=on routes the feed through the rotating-slot
    H2DStager, SPARKNET_ECHO=E serves each transferred batch E times with
    fresh crop/mirror draws."""
    import os
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.data.prefetch import (PrefetchIterator, H2DStager,
                                            EchoIterator)
    from sparknet_tpu.data.device_transform import DeviceTransformer
    from sparknet_tpu.data.transforms import DataTransformer
    from sparknet_tpu.data.wire import (WireCodec, wire_mode_from_env,
                                        wire_bits_from_env)
    from sparknet_tpu.proto import Message

    solver = _mk_solver(net_param)
    tp = Message("TransformationParameter", crop_size=crop, mirror=1)
    tp.mean_value.extend([104.0, 117.0, 123.0])
    host_t = DataTransformer(tp, phase=0, rng=np.random.RandomState(1))
    devt = DeviceTransformer(host_t)
    rec_shape = (3, src_size, src_size)

    rs = np.random.RandomState(0)
    pool = rs.randint(0, 256, (batch_size * 2, 3, src_size, src_size),
                      dtype=np.uint8)
    labels = rs.randint(0, classes, batch_size * 2).astype(np.int32)
    prng = np.random.RandomState(2)

    wire_mode = wire_mode_from_env()
    echo = max(1, int(os.environ.get("SPARKNET_ECHO", "1") or 1))
    staging = os.environ.get("SPARKNET_STAGING", "") == "on"
    codec = WireCodec(devt, rec_shape, mode=wire_mode,
                      bits=wire_bits_from_env(), sample=pool) \
        if wire_mode != "raw" else None
    if echo > 1 and codec is not None and codec.precrop:
        raise ValueError("SPARKNET_ECHO > 1 is incompatible with a "
                         "precrop wire mode (crops are baked into the "
                         "shipped bytes)")

    inner0 = devt.device_fn(precropped=codec.precrop if codec else False)

    def cast_fn(b):
        # match the synthetic row's activation dtype (bf16) so the two
        # rows isolate the input pipeline, not a compute-dtype change
        b = inner0(b)
        b["data"] = b["data"].astype(jnp.bfloat16)
        return b
    tf = codec.device_fn(inner=cast_fn) if codec else cast_fn
    over = codec.raw_overrides(batch_size) if codec \
        else devt.raw_overrides(batch_size, rec_shape)
    solver.set_input_transform(tf, raw_overrides=over)

    def host_batch():
        idx = prng.randint(0, len(pool) - batch_size + 1)
        b = {"data": pool[idx:idx + batch_size],
             "label": labels[idx:idx + batch_size],
             **devt.aux(batch_size, rec_shape)}
        return codec.encode(b) if codec else b

    # ACTUAL shipped bytes per image (pixel wire + labels + aux draws)
    kb_per_image = sum(v.nbytes for v in host_batch().values()) \
        / batch_size / 1024.0

    def _sync_d(d):
        return float(jnp.sum(d["data"].ravel()[:4].astype(jnp.float32)))

    # leg 1: pure H2D transfer (encoded batch + aux), synced per batch
    def put_once():
        return {k: jax.device_put(v) for k, v in host_batch().items()}
    _sync_d(put_once())
    t_dt, t_dts = _time_windows(put_once, _sync_d, iters=5, windows=3)
    transfer_img_s = batch_size * 5 / t_dt

    # leg 2: device step with a RESIDENT raw batch (no transfer in loop)
    resident = put_once()
    for _ in range(WARMUP):
        loss = solver.train_step(resident)
    float(loss)
    s_dt, _ = _time_windows(lambda: solver.train_step(resident), float,
                            windows=3)
    step_img_s = batch_size * ITERS / s_dt

    # end to end: the feed staged ahead of the step in a prefetch worker —
    # rotating-slot non-blocking staging when SPARKNET_STAGING=on, the
    # classic blocking device_put-in-worker otherwise
    stager = H2DStager(slots=2) if staging else None

    def produce():
        while True:
            if stager is not None:
                yield host_batch()
            else:
                yield {k: jax.device_put(v) for k, v in host_batch().items()}

    it = PrefetchIterator(produce(), depth=3, transform=stager)
    if echo > 1:
        it = EchoIterator(it, echo,
                          fresh_aux=lambda b: devt.aux(batch_size,
                                                       rec_shape))
    try:
        for _ in range(WARMUP):
            loss = solver.train_step(next(it))
        float(loss)
        dt, dts = _time_windows(lambda: solver.train_step(next(it)), float)
    finally:
        it.close()
    img_s = batch_size * ITERS / dt
    flops = model_train_flops_per_image(solver)
    row = {"model": name, "mode": "host_fed", "batch": batch_size,
           "images_per_sec": round(img_s, 2),
           "images_per_sec_spread": _rate_stats(batch_size * ITERS, dts),
           "h2d_kb_per_image": round(kb_per_image, 1),
           "wire": wire_mode, "echo": echo, "staging": int(staging),
           "transfer_only_images_per_sec": round(transfer_img_s, 2),
           "transfer_only_spread": _rate_stats(batch_size * 5, t_dts),
           "device_step_images_per_sec": round(step_img_s, 2),
           # sharded-ingest view: this process's feed leg, and what the
           # fleet aggregates to when every host feeds its own partition
           "per_host_feed_images_per_sec": round(transfer_img_s, 2),
           "feed_processes": jax.process_count(),
           "aggregate_feed_images_per_sec": round(
               transfer_img_s * jax.process_count(), 2)}
    if codec is not None and codec.packing:
        row["wire_bits"] = codec.bits
    row["mfu"] = round(img_s * flops / peak, 4)
    bound = min(transfer_img_s, step_img_s)
    if bound > 0:
        # >=1.0 means the feed overlap hides the cheaper leg entirely;
        # with echo, served img/s can exceed the transfer bound by up
        # to the echo factor — that excess IS the lever working
        row["overlap_efficiency"] = round(img_s / bound, 3)
    if transfer_img_s < 0.1 * step_img_s:
        # machine-readable guard: this row measures the host-to-device
        # leg, not the chip — downstream tooling must not read it as a
        # compute number
        row["transfer_bound"] = True
    if transfer_img_s < 0.5 * step_img_s:
        row["note"] = ("transfer-bound: end-to-end tracks the H2D leg, "
                       "not the device step")
    return row


def bench_transformer_lm(peak, seq_len=4096, batch=4, d_model=512,
                         num_layers=6, num_heads=8, vocab=8192):
    """Long-context row: causal transformer LM with the pallas flash
    kernel (zoo.transformer_lm) — the workload the reference never had."""
    import jax.numpy as jnp
    from sparknet_tpu.models import zoo
    # mixed precision: f32 master params, activations cast bf16 at the
    # embedding (compute_dtype) — tokens enter as int32, so unlike the
    # CNN rows the feed can't choose the compute dtype itself
    solver = _mk_solver(zoo.transformer_lm(
        vocab_size=vocab, seq_len=seq_len, batch_size=batch,
        d_model=d_model, num_layers=num_layers, num_heads=num_heads,
        flash=True), compute_dtype=jnp.bfloat16)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, vocab, (batch, seq_len))
    batch_d = {"data": jnp.asarray(toks, jnp.int32),
               "label": jnp.asarray((toks + 1) % vocab, jnp.int32)}
    for _ in range(WARMUP):
        loss = solver.train_step(batch_d)
    float(loss)
    dt, dts = _time_windows(lambda: solver.train_step(batch_d), float)
    tok_s = batch * seq_len * ITERS / dt
    # analytic train FLOPs/token: 12*d^2 dense MACs/layer + causal
    # attention S*d MACs/layer + d*vocab head MACs, x2 FLOP x3 train
    flops = 3 * 2 * (num_layers * (12 * d_model ** 2 + seq_len * d_model)
                     + d_model * vocab)
    row = {"model": "transformer_lm", "mode": "synthetic",
           "batch": batch, "seq_len": seq_len, "d_model": d_model,
           "num_layers": num_layers,
           "tokens_per_sec": round(tok_s, 1),
           "tokens_per_sec_spread": _rate_stats(batch * seq_len * ITERS,
                                                dts),
           "train_kflops_per_token": round(flops / 1e3, 1),
           "model_tflops_per_sec": round(tok_s * flops / 1e12, 2)}
    row.update(_mem_cols(solver, batch_d))
    row["mfu"] = round(tok_s * flops / peak, 4)
    return row


# --------------------------------------------------------------- ablations

# lever -> (env var, baseline arm value, lever arm value). Each lever's
# natural workload is the row it is supposed to move (ISSUE/PERF.md):
# epilogue -> googlenet b256 (the one 3-op conv+relu+lrn site lives in
# its conv2 tower), scan/remat -> the d512x6 LM row (per-layer dispatch
# overhead), overlap -> data-parallel caffenet (the grad allreduce).
# The input-pipeline levers (wire/staging/echo) A/B the HOST-FED feed
# path instead of a compute trace — run_feed_ablation.
# The sharding/precision levers (fsdp/tp/bf16) A/B the LM over the
# device mesh: fsdp swaps DataParallelSolver for FSDPSolver (throughput
# should hold, peak_hbm_mb is the payoff column), tp swaps in a
# GSPMDSolver over the (data, model) mesh, bf16 flips
# SPARKNET_PRECISION on a single-device LM.
ABLATE_ENVS = {
    "epilogue": ("SPARKNET_EPILOGUE", "off", "on"),
    "scan": ("SPARKNET_SCAN", "off", "on"),
    "remat": ("SPARKNET_REMAT", "none", "dots"),
    "overlap": ("SPARKNET_OVERLAP", "off", "on"),
    "wire": ("SPARKNET_WIRE", "raw", "precrop+pack"),
    "staging": ("SPARKNET_STAGING", "off", "on"),
    "echo": ("SPARKNET_ECHO", "1", "4"),
    "fsdp": ("SPARKNET_FSDP", "off", "on"),
    "tp": ("SPARKNET_TP", "1", "2"),
    "bf16": ("SPARKNET_PRECISION", "fp32", "bf16"),
}
FEED_LEVERS = ("wire", "staging", "echo")


def run_ablation(lever, peak, emit):
    """--ablate LEVER: paired baseline/lever rows from ONE process.

    Both arms trace under their own env value (the knobs are read at
    trace time), then the timed windows INTERLEAVE arms — the
    experiments/ab_s2d.py discipline — so chip-contention drift lands on
    both arms equally and the delta is the lever's, not the hour's. Rows
    carry {"ablation": lever, "arm": ...} for A/B provenance in
    bench_metrics.jsonl."""
    import os
    import jax.numpy as jnp
    from sparknet_tpu.models import zoo
    if lever in FEED_LEVERS:
        return run_feed_ablation(lever, peak, emit)
    env, off_v, on_v = ABLATE_ENVS[lever]
    rs = np.random.RandomState(0)
    # SPARKNET_BENCH_TINY=1: shrink every workload to check the A/B
    # plumbing in seconds. It still needs the chip (main() refuses
    # anything else), and rows carry the shrunk shapes.
    tiny = bool(os.environ.get("SPARKNET_BENCH_TINY"))

    if lever in ("scan", "remat"):
        seq, d, nl, vocab, batch = (128, 64, 3, 256, 2) if tiny \
            else (4096, 512, 6, 8192, 4)
        toks = rs.randint(0, vocab, (batch, seq))
        batch_d = {"data": jnp.asarray(toks, jnp.int32),
                   "label": jnp.asarray((toks + 1) % vocab, jnp.int32)}
        unit, unit_key = batch * seq * ITERS, "tokens_per_sec"
        fixed_flops = 3 * 2 * (nl * (12 * d ** 2 + seq * d) + d * vocab)
        base = {"model": "transformer_lm", "batch": batch, "seq_len": seq,
                "d_model": d, "num_layers": nl}

        def mk():
            return _mk_solver(zoo.transformer_lm(
                vocab_size=vocab, seq_len=seq, batch_size=batch,
                d_model=d, num_layers=nl, num_heads=8, flash=True),
                compute_dtype=jnp.bfloat16)
    elif lever in ("fsdp", "tp", "bf16"):
        # the "one big model" lever set: same LM both arms, the env var
        # picks the solver/precision. fsdp and tp need every device in
        # the timed program, so batch rows must divide the mesh.
        seq, d, nl, vocab, batch = (128, 64, 2, 256, 8) if tiny \
            else (1024, 1024, 8, 8192, 8)
        toks = rs.randint(0, vocab, (batch, seq))
        batch_d = {"data": jnp.asarray(toks, jnp.int32),
                   "label": jnp.asarray((toks + 1) % vocab, jnp.int32)}
        unit, unit_key = batch * seq * ITERS, "tokens_per_sec"
        fixed_flops = 3 * 2 * (nl * (12 * d ** 2 + seq * d) + d * vocab)
        base = {"model": "transformer_lm", "batch": batch, "seq_len": seq,
                "d_model": d, "num_layers": nl}

        def mk():
            from sparknet_tpu.proto import Message
            net = zoo.transformer_lm(
                vocab_size=vocab, seq_len=seq, batch_size=batch,
                d_model=d, num_layers=nl, num_heads=8, flash=not tiny)
            if lever == "bf16":
                # compute_dtype=None -> CompiledNet resolves the
                # SPARKNET_PRECISION env var: that resolution IS the arm
                return _mk_solver(net)
            sp = Message("SolverParameter", base_lr=0.01,
                         lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0, display=0, random_seed=0)
            if lever == "tp":
                from sparknet_tpu.parallel import (GSPMDSolver,
                                                   transformer_tp_rule)
                from sparknet_tpu.parallel.mesh import make_tp_mesh
                ways = int(os.environ.get("SPARKNET_TP", "1") or 1)
                return GSPMDSolver(sp, mesh=make_tp_mesh(ways),
                                   param_rule=transformer_tp_rule(ways),
                                   net_param=net)
            from sparknet_tpu.parallel import (DataParallelSolver,
                                               FSDPSolver, fsdp_enabled)
            cls = FSDPSolver if fsdp_enabled() else DataParallelSolver
            return cls(sp, net_param=net)
    elif lever == "epilogue":
        batch, side, classes = (8, 32, 10) if tiny else (256, 224, 1000)
        batch_d = {"data": jnp.asarray(rs.randn(batch, 3, side, side),
                                       jnp.bfloat16),
                   "label": jnp.asarray(rs.randint(0, classes, batch),
                                        jnp.int32)}
        unit, unit_key = batch * ITERS, "images_per_sec"
        fixed_flops = None          # per-arm, from the solver's graph
        base = {"model": "cifar10_full" if tiny else "googlenet",
                "batch": batch}

        def mk():
            if tiny:                # conv/relu fusion sites without the
                return _mk_solver(  # 27M-param googlenet build time
                    zoo.cifar10_full(batch_size=batch))
            return _mk_solver(zoo.googlenet(batch_size=batch,
                                            num_classes=1000))
    else:                           # overlap: DP caffenet, grads allreduce
        from sparknet_tpu.parallel import DataParallelSolver
        from sparknet_tpu.proto import Message
        batch, side, classes = (16, 28, 10) if tiny else (256, 227, 1000)
        batch_d = {"data": jnp.asarray(rs.randn(batch, 1 if tiny else 3,
                                                side, side), jnp.bfloat16),
                   "label": jnp.asarray(rs.randint(0, classes, batch),
                                        jnp.int32)}
        unit, unit_key = batch * ITERS, "images_per_sec"
        fixed_flops = None
        base = {"model": "lenet_dp" if tiny else "caffenet_dp",
                "batch": batch}

        def mk():
            sp = Message("SolverParameter", base_lr=0.01,
                         lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005, display=0, random_seed=0)
            net = zoo.lenet(batch_size=batch) if tiny \
                else zoo.caffenet(batch_size=batch, num_classes=1000)
            return DataParallelSolver(sp, net_param=net)

    arms = {}
    for arm, val in (("baseline", off_v), (lever, on_v)):
        old = os.environ.get(env)
        os.environ[env] = val
        try:
            s = mk()
            for _ in range(WARMUP):     # first step traces under `val`
                loss = s.train_step(batch_d)
            float(loss)
            # memory columns lower under the SAME env value the arm
            # traced with (the knobs are read at trace time)
            arms[arm] = (s, val, _mem_cols(s, batch_d))
        finally:
            os.environ.pop(env, None)
            if old is not None:
                os.environ[env] = old

    dts = {a: [] for a in arms}
    for _ in range(WINDOWS):
        for a, (s, _v, _m) in arms.items():
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = s.train_step(batch_d)
            float(out)
            dts[a].append(time.perf_counter() - t0)

    for a, (s, val, mem) in arms.items():
        flops = fixed_flops if fixed_flops is not None \
            else model_train_flops_per_image(s)
        rate = unit / min(dts[a])
        row = dict(base, mode="ablation", ablation=lever, arm=a, **mem)
        row[env] = val
        row[unit_key] = round(rate, 1)
        row[unit_key + "_spread"] = _rate_stats(unit, dts[a])
        row["model_tflops_per_sec"] = round(rate * flops / 1e12, 2)
        row["mfu"] = round(rate * flops / peak, 4)
        emit(row)
    return 0


def run_feed_ablation(lever, peak, emit):
    """--ablate {wire,staging,echo}: paired A/B over the HOST-FED feed
    path. Same interleaved-window discipline as run_ablation, but each
    arm builds the full pipeline — source pool, wire codec, prefetch,
    staging, echo — under its env value, because these levers live in
    the feed, not the compute trace.

    The wire arm feeds a LOW-ENTROPY pool (pixel values 0..3, 2-bit
    packable — the "optional lossless pack for low-entropy sources"
    case) so the pack stage is active and the row's pool_bits field
    says so; staging/echo arms feed full-range uint8."""
    import os
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.data.prefetch import (PrefetchIterator, H2DStager,
                                            EchoIterator)
    from sparknet_tpu.data.device_transform import DeviceTransformer
    from sparknet_tpu.data.transforms import DataTransformer
    from sparknet_tpu.data.wire import (WireCodec, wire_mode_from_env,
                                        wire_bits_from_env)
    from sparknet_tpu.models import zoo
    from sparknet_tpu.proto import Message

    env, off_v, on_v = ABLATE_ENVS[lever]
    tiny = bool(os.environ.get("SPARKNET_BENCH_TINY"))
    low_entropy = lever == "wire"
    if tiny:
        # lenet keeps the crop geometry in play at smoke scale: 1x32x32
        # source records cropped to lenet's 1x28x28 input
        batch, ch, src, crop, classes = 16, 1, 32, 28, 10
        model, mean_vals = "lenet", [128.0]

        def mk_net():
            return zoo.lenet(batch_size=batch)
    else:
        batch, ch, src, crop, classes = 256, 3, 256, 227, 1000
        model, mean_vals = "caffenet", [104.0, 117.0, 123.0]

        def mk_net():
            return zoo.caffenet(batch_size=batch, num_classes=1000)
    base = {"model": model, "batch": batch}

    def build():
        """Full feed pipeline under the CURRENT env -> (solver, it,
        closers, info)."""
        solver = _mk_solver(mk_net())
        tp = Message("TransformationParameter", crop_size=crop, mirror=1)
        tp.mean_value.extend(mean_vals)
        devt = DeviceTransformer(
            DataTransformer(tp, phase=0, rng=np.random.RandomState(1)))
        rec_shape = (ch, src, src)
        rs = np.random.RandomState(0)
        pool = rs.randint(0, 4 if low_entropy else 256,
                          (batch * 2, ch, src, src)).astype(np.uint8)
        labels = rs.randint(0, classes, batch * 2).astype(np.int32)
        prng = np.random.RandomState(2)
        wire_mode = wire_mode_from_env()
        codec = WireCodec(devt, rec_shape, mode=wire_mode,
                          bits=wire_bits_from_env(), sample=pool) \
            if wire_mode != "raw" else None
        inner0 = devt.device_fn(precropped=codec.precrop if codec
                                else False)

        def cast_fn(b):
            b = inner0(b)
            b["data"] = b["data"].astype(jnp.bfloat16)
            return b
        tf = codec.device_fn(inner=cast_fn) if codec else cast_fn
        over = codec.raw_overrides(batch) if codec \
            else devt.raw_overrides(batch, rec_shape)
        solver.set_input_transform(tf, raw_overrides=over)

        def host_batch():
            i = prng.randint(0, len(pool) - batch + 1)
            b = {"data": pool[i:i + batch], "label": labels[i:i + batch],
                 **devt.aux(batch, rec_shape)}
            return codec.encode(b) if codec else b

        kb = sum(v.nbytes for v in host_batch().values()) / batch / 1024.0
        staging = os.environ.get("SPARKNET_STAGING", "") == "on"
        echo = max(1, int(os.environ.get("SPARKNET_ECHO", "1") or 1))
        stager = H2DStager(slots=2) if staging else None

        def produce():
            while True:
                if stager is not None:
                    yield host_batch()
                else:
                    yield {k: jax.device_put(v)
                           for k, v in host_batch().items()}

        it = PrefetchIterator(produce(), depth=3, transform=stager)
        if echo > 1:
            it = EchoIterator(it, echo,
                              fresh_aux=lambda b: devt.aux(batch,
                                                           rec_shape))
        info = {"h2d_kb_per_image": round(kb, 1), "wire": wire_mode,
                "echo": echo, "staging": int(staging)}
        if low_entropy:
            info["pool_bits"] = 2
        if codec is not None and codec.packing:
            info["wire_bits"] = codec.bits
        return solver, it, info

    arms = {}
    for arm, val in (("baseline", off_v), (lever, on_v)):
        old = os.environ.get(env)
        os.environ[env] = val
        try:
            s, it, info = build()
            for _ in range(WARMUP):
                loss = s.train_step(next(it))
            float(loss)
            arms[arm] = (s, it, info, val)
        finally:
            os.environ.pop(env, None)
            if old is not None:
                os.environ[env] = old

    try:
        dts = {a: [] for a in arms}
        for _ in range(WINDOWS):
            for a, (s, it, _info, _v) in arms.items():
                t0 = time.perf_counter()
                for _ in range(ITERS):
                    out = s.train_step(next(it))
                float(out)
                dts[a].append(time.perf_counter() - t0)

        unit = batch * ITERS
        for a, (s, it, info, val) in arms.items():
            flops = model_train_flops_per_image(s)
            rate = unit / min(dts[a])
            row = dict(base, mode="ablation", ablation=lever, arm=a,
                       **info)
            row[env] = val
            row["images_per_sec"] = round(rate, 1)
            row["images_per_sec_spread"] = _rate_stats(unit, dts[a])
            row["mfu"] = round(rate * flops / peak, 4)
            emit(row)
    finally:
        for _a, (_s, it, _info, _v) in arms.items():
            it.close()
    return 0


# --------------------------------------------------- multi-chip projection

# Ring-allreduce cost model: a pmean of B bytes over N peers moves
# 2*(N-1)/N * B past every chip (reduce-scatter + all-gather), so
#   t_comm = 2*(N-1)/N * B / bw_per_chip.
# Link-budget defaults (public TPU specs; override by flag):
#   v5e ICI: 2D torus, 4 links/chip x ~50 GB/s -> one bidirectional ring
#   axis sustains ~90 GB/s per chip. DCN (between slices/regions, the
#   SparkNet EC2 regime): ~12.5 GB/s per host.
ICI_GBPS = 90.0
DCN_GBPS = 12.5


def project_multichip(step_sec, batch, param_bytes, n_chips, tau=1,
                      bw_gbps=ICI_GBPS):
    """Projected img/s for N-chip data parallelism from the measured
    single-chip step. tau=1 is per-step DP (allreduce of GRADIENTS every
    step); tau>1 is local SGD (one allreduce of WEIGHTS per tau steps —
    the SparkNet algorithm, CifarApp.scala:92-135). Conservative: no
    comm/compute overlap is assumed, though XLA overlaps the ring with
    the tail of the backward pass in practice."""
    t_comm = 2 * (n_chips - 1) / n_chips * param_bytes / (bw_gbps * 1e9)
    t_round = tau * step_sec + t_comm
    return n_chips * batch * tau / t_round, t_comm


def run_projection(args):
    """bench.py --project: analytic scaling table, inputs shown.

    The compute leg comes from bench_details.json's measured synthetic
    rows (median window — the projection must not inherit best-window
    luck); the comm leg from the ring model above. The reference's own
    published scaling claim for this workload class is ~1.8x at 2 GPUs
    and ~3.5x weak-scaling at 4 (caffe/docs/multigpu.md); the BASELINE.md
    north star is >=4x wall-clock at v4-32."""
    with open(args.details) as f:
        details = json.load(f)
    rows = [r for r in details["rows"]
            if r.get("model") == "caffenet" and r.get("mode") == "synthetic"]
    if not rows:
        raise SystemExit("no caffenet synthetic rows in bench_details.json; "
                         "run `python bench.py` first")
    from sparknet_tpu.models import zoo
    from sparknet_tpu.graph.compiler import CompiledNet, TRAIN
    net = CompiledNet(zoo.caffenet(batch_size=8, num_classes=1000), TRAIN)
    param_bytes = 4 * sum(
        int(np.prod(shape))
        for layer in net.layers for shape, *_ in layer[1].param_shapes())
    out = {"model": "caffenet", "param_bytes": param_bytes,
           "comm_model": "ring allreduce 2(N-1)/N * B / bw, no overlap",
           "ici_gbps": args.ici_gbps, "dcn_gbps": args.dcn_gbps,
           "projections": []}
    for r in rows:
        batch = r["batch"]
        med = r.get("images_per_sec_spread", {}).get("median",
                                                     r["images_per_sec"])
        step = batch / med
        for n in args.chips:
            dp, c_dp = project_multichip(step, batch, param_bytes, n,
                                         bw_gbps=args.ici_gbps)
            ls, c_ls = project_multichip(step, batch, param_bytes, n,
                                         tau=50, bw_gbps=args.ici_gbps)
            ls_dcn, c_dcn = project_multichip(step, batch, param_bytes, n,
                                              tau=50, bw_gbps=args.dcn_gbps)
            out["projections"].append({
                "batch_per_chip": batch, "n_chips": n,
                "measured_step_ms": round(step * 1e3, 3),
                "dp_img_per_sec": round(dp, 1),
                "dp_comm_ms": round(c_dp * 1e3, 3),
                "dp_scaling_eff": round(dp / (n * med), 3),
                "local_sgd_tau50_img_per_sec": round(ls, 1),
                "local_sgd_scaling_eff": round(ls / (n * med), 3),
                "local_sgd_tau50_dcn_img_per_sec": round(ls_dcn, 1),
                "dcn_scaling_eff": round(ls_dcn / (n * med), 3),
            })
    print(json.dumps(out, indent=1))
    return 0


def _check_row_key(r):
    """Identity of a bench row across runs: workload coordinates only,
    never measured values."""
    return tuple(str(r.get(k)) for k in
                 ("model", "mode", "batch", "seq_len", "d_model",
                  "num_layers"))


def _check_row_median(r):
    """(median, metric_name, spread|None) for a row — the spread median
    when recorded (best-window headline values inherit contention luck;
    the median is the comparable number), else the headline value."""
    for k in ("images_per_sec", "tokens_per_sec"):
        sp = r.get(f"{k}_spread")
        if isinstance(sp, dict) and \
                isinstance(sp.get("median"), (int, float)):
            return float(sp["median"]), k, sp
        if isinstance(r.get(k), (int, float)):
            return float(r[k]), k, None
    return None, None, None


def run_check(args):
    """bench.py --check: the perf-regression gate (ISSUE 16).

    Compares the rows in --details (the current run's output) against
    baseline medians (--check-baseline: a details file kept from an
    earlier chip run; a BASELINE.json with published rows is accepted
    too). Per row the threshold is noise-tolerant: the
    current median must stay above

        baseline_median * (1 - max(--check-tolerance, baseline
                                   median-to-min spread ratio))

    so a workload whose committed windows already vary by 30% is not
    gated at 15%. Any breach (or a baseline row missing from the
    current file) fails with the offending row named; exit 1. Runs
    without jax or an accelerator — pure JSON compare — so CI gates on
    any machine."""
    try:
        with open(args.check_baseline) as f:
            base = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench --check: cannot read baseline "
              f"{args.check_baseline}: {e}", file=sys.stderr)
        return 2
    base_rows = base.get("rows") or base.get("published") or []
    if isinstance(base_rows, dict):
        base_rows = list(base_rows.values())
    if not base_rows:
        print(f"bench --check: baseline {args.check_baseline} has no "
              "rows to gate against", file=sys.stderr)
        return 2
    try:
        with open(args.details) as f:
            cur = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench --check: cannot read current rows "
              f"{args.details}: {e}", file=sys.stderr)
        return 2
    cur_by_key = {_check_row_key(r): r for r in (cur.get("rows") or [])}
    failures, checked = [], 0
    for br in base_rows:
        med_b, metric, sp = _check_row_median(br)
        if med_b is None or med_b <= 0:
            continue
        key = _check_row_key(br)
        name = " ".join(k for k in key if k != "None")
        cr = cur_by_key.get(key)
        if cr is None:
            failures.append(f"row MISSING from {args.details}: {name} "
                            f"(baseline {metric} median {med_b:,.1f})")
            continue
        med_c, _, _ = _check_row_median(cr)
        if med_c is None:
            failures.append(f"row has no {metric} in {args.details}: "
                            f"{name}")
            continue
        tol = args.check_tolerance
        if sp and isinstance(sp.get("min"), (int, float)) and med_b > 0:
            tol = max(tol, (med_b - float(sp["min"])) / med_b)
        floor = med_b * (1.0 - tol)
        checked += 1
        verdict = "ok" if med_c >= floor else "REGRESSED"
        line = (f"  {verdict:<9} {name}: {metric} median "
                f"{med_c:,.1f} vs baseline {med_b:,.1f} "
                f"(floor {floor:,.1f}, tol {tol:.0%})")
        print(line, file=sys.stderr)
        if med_c < floor:
            failures.append(
                f"{name}: {metric} median {med_c:,.1f} fell below "
                f"{floor:,.1f} ({med_b:,.1f} - {tol:.0%} noise "
                "tolerance)")
    if failures:
        print(f"bench --check: FAIL — {len(failures)} failing row(s), "
              f"{checked} compared:", file=sys.stderr)
        for fmsg in failures:
            print(f"  {fmsg}", file=sys.stderr)
        return 1
    print(f"bench --check: OK — {checked} row(s) within noise "
          f"tolerance of {args.check_baseline}", file=sys.stderr)
    return 0


def main():
    import argparse
    global WINDOWS

    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, default=WINDOWS,
                    help="timing windows per row (spread is recorded)")
    ap.add_argument("--metrics", default="bench_metrics.jsonl",
                    help="JSONL metrics stream for every bench row "
                         "('' disables)")
    ap.add_argument("--project", action="store_true",
                    help="print the analytic multi-chip projection from "
                         "the measured single-chip rows and exit")
    ap.add_argument("--ablate", choices=sorted(ABLATE_ENVS),
                    help="run ONE paired baseline/lever A/B for a perf "
                         "lever (same process, interleaved windows) and "
                         "exit; rows land in --metrics and "
                         "bench_ablation.json with ablation provenance")
    ap.add_argument("--details", default="bench_details.json")
    ap.add_argument("--chips", type=int, nargs="+", default=[2, 4, 8, 32])
    ap.add_argument("--ici-gbps", type=float, default=ICI_GBPS)
    ap.add_argument("--dcn-gbps", type=float, default=DCN_GBPS)
    ap.add_argument("--check", action="store_true",
                    help="perf-regression gate: compare the rows in "
                         "--details against the committed baseline "
                         "medians and exit 1 naming any row below its "
                         "noise-tolerant floor (no jax needed)")
    ap.add_argument("--check-baseline",
                    default="tests/fixtures/bench_check_rows.json",
                    help="baseline rows for --check (a details file of an "
                         "earlier chip run, or a BASELINE.json with "
                         "published rows; the default is a made-up "
                         "fixture that only exercises the gate)")
    ap.add_argument("--check-tolerance", type=float, default=0.15,
                    help="minimum allowed regression fraction before "
                         "--check fails a row; widened per-row to the "
                         "baseline's own median-to-min window spread")
    args = ap.parse_args()
    WINDOWS = max(1, args.windows)
    if args.check:
        raise SystemExit(run_check(args))
    if args.project:
        raise SystemExit(run_projection(args))

    from sparknet_tpu.models import zoo
    from sparknet_tpu.utils.compile_cache import configure_compile_cache

    # persistent compile cache: repeat bench runs skip the (minutes-long)
    # XLA compiles; keyed by HLO so code changes still recompile
    configure_compile_cache()
    dev = bench_device()
    peak = peak_flops(dev.device_kind)
    rows = []
    # every row also goes through the structured metrics stream
    # (sparknet_tpu.obs backend), so `sparknet report bench_metrics.jsonl`
    # reconstructs a run's provenance from the JSONL alone
    from sparknet_tpu.utils.metrics import MetricsLogger
    mlog = MetricsLogger(args.metrics) if args.metrics else None
    if mlog:
        mlog.log("bench_config", device=dev.device_kind,
                 platform=dev.platform, peak_bf16_flops=peak,
                 windows=WINDOWS, warmup=WARMUP, iters_per_window=ITERS)

    # ablation A/Bs get their own details file: a lever smoke run must
    # never clobber a full run's bench_details.json
    details_path = args.details
    if args.ablate and details_path == "bench_details.json":
        details_path = "bench_ablation.json"

    def emit(row):
        # stream rows as they finish: a killed/timed-out run still leaves
        # every completed measurement on stderr and in the details file
        # (written atomically so a mid-write kill can't truncate it)
        import os
        rows.append(row)
        print("#BENCH " + json.dumps(row), file=sys.stderr, flush=True)
        if mlog:
            mlog.log("bench", **row)
        with open(details_path + ".tmp", "w") as f:
            json.dump({"device": dev.device_kind, "platform": dev.platform,
                       "peak_bf16_flops": peak, "rows": rows}, f, indent=1)
        os.replace(details_path + ".tmp", details_path)

    if args.ablate:
        rc = run_ablation(args.ablate, peak, emit)
        if mlog:
            mlog.close()
        return rc

    # headline: CaffeNet batch 256, synthetic-fed (the reference workload).
    # The driver's ONE JSON line prints immediately — supplementary rows
    # below must not be able to take it down with them.
    head, solver = bench_synthetic(
        "caffenet", zoo.caffenet(batch_size=256, num_classes=1000),
        256, (3, 227, 227), 1000, peak)
    headline = {
        "metric": "caffenet_train_throughput",
        "value": head["images_per_sec"],
        "unit": "images/sec",
        "vs_baseline": round(head["images_per_sec"] / BASELINE_IMG_PER_SEC,
                             3),
    }
    print(json.dumps(headline), flush=True)
    if mlog:
        mlog.log("bench_headline", **headline)
    emit(head)

    del solver
    failed = []

    def row(name, fn):
        # a failing row must not take the later rows down with it, and
        # must not hide behind exit code 0 either
        try:
            emit(fn())
        except Exception as e:
            failed.append(name)
            print(f"#BENCH-FAIL {name}: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)

    # honest row: same model+batch fed raw uint8 from the host, with the
    # crop/mirror/mean transform running inside the jitted step
    row("host_fed", lambda: bench_hostfed(
        "caffenet", zoo.caffenet(batch_size=256, num_classes=1000),
        256, 256, 227, 1000, peak))

    # bigger batches: larger MXU tiles amortize the small spatial dims
    for bsz in (512, 1024):
        row(f"caffenet_b{bsz}", lambda bsz=bsz: bench_synthetic(
            "caffenet", zoo.caffenet(batch_size=bsz, num_classes=1000),
            bsz, (3, 227, 227), 1000, peak)[0])

    # GoogLeNet (the reference's third headline model family), batch 256
    row("googlenet", lambda: bench_synthetic(
        "googlenet", zoo.googlenet(batch_size=256, num_classes=1000),
        256, (3, 224, 224), 1000, peak)[0])

    # long-context: flash-attention transformer LM at S=4096 — the toy
    # scale (d=512) and a real scale (d=1024 x 12 layers, ~160M params)
    # where MFU is meaningful. heads=8 -> head_dim 128 == the TPU lane
    # width: head_dim 64 half-fills every (..., D)-minor tile
    row("transformer_lm", lambda: bench_transformer_lm(peak))
    row("transformer_lm_1024", lambda: bench_transformer_lm(
        peak, batch=4, d_model=1024, num_layers=12, num_heads=8))
    if mlog:
        mlog.close()
    if failed:
        print(f"bench.py: {len(failed)} row(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
