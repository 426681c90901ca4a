#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that today's code runs on the chip.

    python chip_smoke.py            one chip: three phases, each a child
    python chip_smoke.py --chips 4  four chips: the mesh phase and its
                                    one-device comparison, nothing else

One chip, in this order, each phase failing the run if it fails:

  cnn    `python -m sparknet_tpu imagenet` — CaffeNet at the 227 crop,
         batch 256, 1000 classes, tau-step local-SGD rounds (the SparkNet
         algorithm) on the synthetic class-gaussian source.
  lm     `python -m sparknet_tpu lm` — d1024 x 12 layers, 8 heads, S=4096,
         vocab 8192, bf16, flash/scan/remat as the code picks them on a
         TPU; then a child lowers the same step and reads its compiled
         text for `tpu_custom_call` (the kernels compiled, no interpreter)
         and `while` (the layer scan engaged).
  serve  `python -m sparknet_tpu train --solver` snapshots a CaffeNet,
         `python -m sparknet_tpu serve` answers /predict for 3x227x227
         inputs, SIGTERM drains it (exit 0), and a child compares the
         answers with a direct forward of the same weights.

A chip belongs to one process at a time, so THIS process never imports
jax (nor sparknet_tpu.cli): every phase is a child, one after another, and
the device in the last line is what the first child reported. All children
share one compile cache (sparknet_tpu/utils/compile_cache.py).

The last line of stdout is the result, and only a run on a TPU prints one:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Without a TPU the script exits 1 before any phase. `--toy` shrinks every
size so the control flow can be rehearsed on the CPU; a toy run never
prints "ok": true and never exits 0.

The rows it prints (steps a second, round times) are a bring-up check
through the CLI and no record of speed: `benchmark/` is the record
(`python benchmark/run.py --workload <cell>`, PERF.md).
"""

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, "_work", "chip_smoke")            # git-ignored
LOGS = os.path.join(HERE, "chiprun_out", "chip_smoke")      # comes back
T0 = time.time()

# full width / toy rehearsal. Depth, steps and rounds are the only cuts.
FULL = dict(
    cnn=dict(batch=256, classes=1000, tau=5, rounds=6),
    lm=dict(vocab=8192, seq_len=4096, batch=4, d_model=1024, layers=12,
            heads=8, steps=8),
    serve=dict(classes=1000, train_batch=32, train_iters=2,
               request_rows=(1, 3, 4), max_batch=4),
    mesh=dict(per_chip=64, classes=1000, steps=3, tau=2, rounds=2),
    sync_n=4096, sync_iters=200)
TOY = dict(
    cnn=dict(batch=4, classes=10, tau=2, rounds=3),
    lm=dict(vocab=64, seq_len=128, batch=2, d_model=32, layers=2,
            heads=2, steps=4),
    serve=dict(classes=10, train_batch=2, train_iters=1,
               request_rows=(1, 2), max_batch=2),
    mesh=dict(per_chip=2, classes=10, steps=2, tau=2, rounds=2),
    sync_n=256, sync_iters=20)


class Failed(Exception):
    pass


def say(msg):
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


# ------------------------------------------------------------ children --
_live = []      # Popen objects of children that may still run


def _child_env(toy, chips):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    # jax then logs every persistent-cache hit, miss and refusal to write,
    # which cache_report() reads back from the child's log
    env.setdefault("JAX_DEBUG_LOG_MODULES", "jax._src.compiler")
    if toy and chips > 1 and "xla_force_host_platform" not in \
            env.get("XLA_FLAGS", ""):
        # rehearsal of the mesh phase on virtual CPU devices
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}").strip()
    return env


def _spawn(cmd, log_name, env):
    os.makedirs(LOGS, exist_ok=True)
    log_path = os.path.join(LOGS, log_name + ".log")
    logf = open(log_path, "w")
    p = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=logf,
                         stderr=subprocess.STDOUT, start_new_session=True)
    p.log_path, p.logf = log_path, logf
    _live.append(p)
    return p


def _reap(p):
    """Stop a child and everything it started: its process group, which
    can outlive the child itself."""
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    p.wait()
    p.logf.close()
    if p in _live:
        _live.remove(p)


def _tail(path, n=3000):
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - n))
        return f.read().decode("utf-8", "replace")


def run_child(cmd, log_name, env, timeout):
    """Run one child to its end. -> its output. Raises Failed, with the
    end of the child's log, on a non-zero exit or the time limit."""
    t0 = time.time()
    p = _spawn(cmd, log_name, env)
    try:
        rc = p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _reap(p)
        raise Failed(f"{log_name}: no end after {timeout}s\n"
                     + _tail(p.log_path))
    _reap(p)
    if rc != 0:
        raise Failed(f"{log_name}: exit code {rc}\n" + _tail(p.log_path))
    with open(p.log_path, errors="replace") as f:
        out = f.read()
    return out, time.time() - t0


def child_json(out):
    """The one `CHILD_JSON {...}` line a --child prints."""
    for line in reversed(out.splitlines()):
        if line.startswith("CHILD_JSON "):
            return json.loads(line[len("CHILD_JSON "):])
    raise Failed("child printed no CHILD_JSON line:\n" + out[-2000:])


_JAX_HELPERS = {"jit_add", "jit_multiply", "jit_broadcast_in_dim",
                "jit_convert_element_type", "jit_concatenate", "jit_fn"}


def cache_report(out):
    """What jax said about the persistent compile cache in one child."""
    hits = re.findall(r"Persistent compilation cache hit for '([^']+)'", out)
    miss = re.findall(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'",
                      out)
    kept_out = sorted(set(re.findall(
        r"Not writing persistent cache entry for '([^']+)' because it "
        r"(uses host callbacks|took <)", out)))
    slow = [f"{n} ({why})" for n, why in kept_out if why != "took <"]

    def named(mods):        # the programs of the repo, not jax's helpers
        return sorted({m for m in mods if not m.startswith("jit__")
                       and m not in _JAX_HELPERS})
    return (f"compile cache: {len(hits)} hits {named(hits)}, "
            f"{len(miss)} misses {named(miss)}"
            + (f"; never written: {slow}" if slow else ""))


def self_child(name, cfg):
    return [sys.executable, os.path.abspath(__file__), "--child", name,
            "--cfg", json.dumps(cfg)]


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def hbm_peak(events):
    """memory_stats() peak from the run's `hbm` events. On the TPU this
    counts the allocator's buffers (params, optimizer state, batches); the
    compiled step's temporaries are in memory_analysis, not here."""
    peaks = [e["peak_bytes_in_use"] for e in events
             if e.get("event") == "hbm" and "peak_bytes_in_use" in e]
    return f"{max(peaks) / 2**30:.2f} GiB (memory_stats: live buffers, " \
        "without the step's temporaries)" if peaks \
        else "not reported by this backend"


def median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def check_losses(name, losses, first_expected, first_tol, toy):
    if not losses or not all(math.isfinite(v) for v in losses):
        raise Failed(f"{name}: losses not all finite: {losses}")
    if toy:          # a batch of 4 at lr 0.01 rehearses control flow only
        return
    if abs(losses[0] - first_expected) > first_tol:
        raise Failed(f"{name}: first loss {losses[0]:.4f} is not within "
                     f"{first_tol} of {first_expected:.4f}")
    if not min(losses[1:]) < losses[0]:
        raise Failed(f"{name}: no later loss below the first: {losses}")


# -------------------------------------------------------------- phases --
def phase_cnn(c, env, toy):
    mpath = os.path.join(LOGS, "cnn.jsonl")
    cmd = [sys.executable, "-m", "sparknet_tpu", "imagenet",
           "--workers", "1", "--strategy", "local_sgd",
           "--batch", str(c["batch"]), "--classes", str(c["classes"]),
           "--tau", str(c["tau"]), "--rounds", str(c["rounds"]),
           "--metrics", mpath]
    say("cnn: " + " ".join(cmd[1:]))
    out, wall = run_child(cmd, "cnn", env, timeout=700)
    ev = read_jsonl(mpath)
    rounds = [e for e in ev if e.get("event") == "round"]
    if len(rounds) != c["rounds"]:
        raise Failed(f"cnn: {len(rounds)} rounds logged, "
                     f"{c['rounds']} asked")
    losses = [r["loss"] for r in rounds]
    tests = {e["metric"]: e["value"] for e in ev if e.get("event") == "test"}
    imgs = c["batch"] * c["tau"]
    secs = [imgs / r["images_per_s"] for r in rounds]
    steps = [e for e in ev if e.get("event") == "step"]
    say(f"cnn: CaffeNet 3x227x227 b{c['batch']} {c['classes']} classes, "
        f"tau={c['tau']}, {c['rounds']} rounds, feed=synthetic "
        f"class-gaussians (host: mean image, crop, mirror)")
    say(f"cnn: round-0 test before training: {tests}")
    say("cnn: round losses (mean over tau steps): "
        + " ".join(f"{v:.4f}" for v in losses))
    # the loss of a round is a mean over its tau steps, so the first one
    # already holds tau-1 updates: judge it against ln(classes) loosely
    check_losses("cnn", losses, math.log(c["classes"]), 1.0, toy)
    say("cnn: round seconds, waiting for input included (round 0 compiles,"
        " round 1 compiles again for the donated layouts; a value fetch of"
        " the round loss ends each): " + " ".join(f"{s:.2f}" for s in secs))
    warm = median(secs[2:]) if len(secs) > 2 else secs[-1]
    say(f"cnn: warm round median {warm:.3f}s = {warm / c['tau']:.4f} s/step"
        f" = {imgs / warm:,.0f} img/s end to end")
    if steps:
        # at a sampled round the queue was drained before dispatch, so
        # dispatch + block_until_ready is the device's share of the round
        # (H2D of the round's batches and tau steps)
        last = steps[-1]
        dev = (last["host_ms"] + last["sync_ms"]) / 1e3
        pf = [e for e in ev if e.get("event") == "prefetch"]
        bound = "the host's synthetic source" if dev < 0.5 * secs[-1] \
            else "the device"
        say(f"cnn: last sampled round, dispatch to block_until_ready: "
            f"{dev:.3f}s of a {secs[-1]:.2f}s round: {bound} is the bound"
            + (f" (round_feed queue empty at {pf[-1]['empty_frac']:.0%} "
               f"of gets)" if pf else ""))
    say(f"cnn: peak HBM {hbm_peak(ev)}; child wall {wall:.1f}s")
    say("cnn: " + cache_report(out))


def phase_lm(c, env, toy):
    mpath = os.path.join(LOGS, "lm.jsonl")
    cmd = [sys.executable, "-m", "sparknet_tpu", "lm",
           "--vocab", str(c["vocab"]), "--seq-len", str(c["seq_len"]),
           "--batch", str(c["batch"]), "--d-model", str(c["d_model"]),
           "--layers", str(c["layers"]), "--heads", str(c["heads"]),
           "--dtype", "bf16", "--steps", str(c["steps"]),
           "--display", "1", "--metrics", mpath]
    say("lm: " + " ".join(cmd[1:]))
    out, wall = run_child(cmd, "lm", env, timeout=600)
    ev = read_jsonl(mpath)
    train = [e for e in ev if e.get("event") == "train"]
    if len(train) != c["steps"]:
        raise Failed(f"lm: {len(train)} steps logged, {c['steps']} asked")
    losses = [e["loss"] for e in train]
    say("lm: losses " + " ".join(f"{v:.4f}" for v in losses))
    check_losses("lm", losses, math.log(c["vocab"]), 1.5, toy)
    steps = [e for e in ev if e.get("event") == "step"]
    gaps = [b["t"] - a["t"] for a, b in zip(train[1:], train[2:])]
    warm = median(gaps[1:] or gaps)
    say(f"lm: step 0 (cold: trace + compile + run) "
        f"{steps[0]['device_ms'] / 1e3:.2f}s, warm {warm:.4f} s/step end to "
        f"end (median gap between per-step loss fetches, the host's "
        f"synthetic corpus included) = "
        f"{c['batch'] * c['seq_len'] / warm:,.0f} tok/s")
    if len(steps) > 1:
        # step 0 was fetched before step 1 was dispatched, so dispatch +
        # block_until_ready of step 1 is one device step and nothing else
        dev = (steps[1]["host_ms"] + steps[1]["sync_ms"]) / 1e3
        say(f"lm: step 1 alone, dispatch to block_until_ready: {dev:.4f}s "
            f"= {c['batch'] * c['seq_len'] / dev:,.0f} tok/s on the device")
    say(f"lm: peak HBM {hbm_peak(ev)}; child wall {wall:.1f}s")
    say("lm: " + cache_report(out))
    # the same step, lowered and compiled by a child that holds the chip
    # alone: no verb prints HLO and none should learn to
    out, wall = run_child(self_child("lm_hlo", c), "lm_hlo", env,
                          timeout=600)
    r = child_json(out)
    say(f"lm: compiled step text: tpu_custom_call x{r['tpu_custom_call']}, "
        f"while x{r['while']}; memory_analysis arg {r['arg_gb']:.2f} GB + "
        f"temp {r['temp_gb']:.2f} GB; trace+lower+compile "
        f"{r['compile_s']:.1f}s in a fresh process, "
        + ("no persistent cache in this run" if r["new_entries"] is None
           else f"{r['new_entries']} new compile-cache entries (0 = the "
           "lm verb's program was found)"))
    say("lm: the HLO child's " + cache_report(out))
    if r["platform"] == "tpu":
        if not r["tpu_custom_call"]:
            raise Failed("lm: no tpu_custom_call in the compiled step: "
                         "the flash kernel is not on the path")
        if not r["while"]:
            raise Failed("lm: no while loop in the compiled step: the "
                         "layer scan did not engage")


def phase_serve(c, env):
    prefix = os.path.join(WORK, "caffenet_snap")
    out, _ = run_child(self_child("write_prototxt", dict(
        c, dir=WORK, prefix=prefix)), "serve_prototxt", env, timeout=120)
    solver = child_json(out)["solver"]
    cmd = [sys.executable, "-m", "sparknet_tpu", "train", "--solver",
           solver, "--iterations", str(c["train_iters"])]
    say("serve: " + " ".join(cmd[1:]))
    out, wall = run_child(cmd, "serve_train", env, timeout=400)
    feed = "synthetic noise (the prototxt names no DB source)" \
        if "feeding synthetic noise" in out else (
            re.search(r"Training from .*", out) or ["unknown"])[0]
    say(f"serve: snapshot written by `train` in {wall:.1f}s; feed: {feed}")
    say("serve: train's " + cache_report(out))
    if not os.path.exists(prefix + ".latest.json"):
        raise Failed(f"serve: train left no manifest {prefix}.latest.json")

    cmd = [sys.executable, "-m", "sparknet_tpu", "serve", "--prefix",
           prefix, "--port", "0", "--max_batch", str(c["max_batch"])]
    say("serve: " + " ".join(cmd[1:]))
    t0 = time.time()
    srv = _spawn(cmd, "serve_server", env)
    try:
        url = None
        while time.time() - t0 < 400 and srv.poll() is None and not url:
            m = re.search(r"listening on (http://[\w.:]+)",
                          _tail(srv.log_path, 20000))
            url = m.group(1) if m else None
            time.sleep(0.5)
        if not url:
            raise Failed("serve: server never listened\n"
                         + _tail(srv.log_path))
        say(f"serve: listening after {time.time() - t0:.1f}s "
            f"(load + warm-up compile of every bucket) at {url}")
        out, _ = run_child(self_child("make_requests", dict(
            c, dir=WORK)), "serve_requests", env, timeout=120)
        lat = []
        for i, rows in enumerate(c["request_rows"]):
            with open(os.path.join(WORK, f"request{i}.json"), "rb") as f:
                body = f.read()
            t1 = time.time()
            req = urllib.request.Request(
                url + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                answer = resp.read()
            lat.append(time.time() - t1)
            with open(os.path.join(WORK, f"response{i}.json"), "wb") as f:
                f.write(answer)
        say("serve: /predict answered " + ", ".join(
            f"{r} row(s) in {s * 1e3:.0f} ms"
            for r, s in zip(c["request_rows"], lat))
            + " (JSON over HTTP, host clock)")
        os.killpg(srv.pid, signal.SIGTERM)
        try:
            rc = srv.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise Failed("serve: no exit 120s after SIGTERM\n"
                         + _tail(srv.log_path))
        log = _tail(srv.log_path, 20000)
        if rc != 0 or "drained cleanly" not in log:
            raise Failed(f"serve: SIGTERM gave exit {rc}, drained="
                         f"{'drained cleanly' in log}\n" + log[-3000:])
        say("serve: SIGTERM -> drained cleanly, exit 0")
    finally:
        _reap(srv)
    # the chip is free again: a direct forward of the same weights
    out, wall = run_child(self_child("direct_forward", dict(
        c, dir=WORK, prefix=prefix)), "serve_direct", env, timeout=400)
    r = child_json(out)
    say(f"serve: direct forward of the same snapshot: logits shape "
        f"{r['shape']}, max |logit| {r['max_abs']:.4f}, max |served - "
        f"direct| {r['max_diff']:.3e} (allowed {r['allowed']:.3e}); "
        f"peak HBM {r['hbm_peak']}")
    if not r["ok"]:
        raise Failed("serve: served logits are zero, non-finite or differ "
                     f"from the direct forward: {r}")


def phase_mesh(c, env, chips):
    say(f"mesh: one process, {chips} devices: DataParallelSolver and "
        f"LocalSGDSolver on make_mesh({{'data': {chips}}}) against the "
        "one-device Solver")
    out, wall = run_child(self_child("mesh", dict(c, chips=chips)), "mesh",
                          env, timeout=1000)
    for line in out.splitlines():
        if line.startswith("mesh: "):
            say(line)
    r = child_json(out)
    if not r["ok"]:
        raise Failed(f"mesh: {r}")
    say(f"mesh: child wall {wall:.1f}s; " + cache_report(out))


# ---------------------------------------------------------------- main --
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--toy", action="store_true",
                    help="CPU rehearsal at toy sizes; never ok, never 0")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--cfg", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return CHILDREN[args.child](json.loads(args.cfg))

    cfg = TOY if args.toy else FULL
    env = _child_env(args.toy, args.chips)
    for d in (WORK, LOGS):          # the metrics streams append
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    try:
        out, _ = run_child(self_child("probe", dict(
            n=cfg["sync_n"], iters=cfg["sync_iters"], toy=args.toy)),
            "probe", env, timeout=300)
        for line in out.splitlines():
            if line.startswith("probe: "):
                say(line)
        dev = child_json(out)
        if dev["platform"] != "tpu" and not args.toy:
            print(f"chip_smoke: jax found no TPU (platform "
                  f"{dev['platform']!r}): nothing to smoke, no result",
                  file=sys.stderr)
            return 1
        if dev["count"] < args.chips:
            raise Failed(f"--chips {args.chips} but jax reports "
                         f"{dev['count']} device(s)")
        if args.chips == 1:
            phase_cnn(cfg["cnn"], env, args.toy)
            phase_lm(cfg["lm"], env, args.toy)
            phase_serve(cfg["serve"], env)
        else:
            phase_mesh(cfg["mesh"], env, args.chips)
    except Failed as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        for p in list(_live):
            _reap(p)
        shutil.rmtree(WORK, ignore_errors=True)     # 240 MB snapshots
    say(f"all phases passed in {time.time() - T0:.0f}s")
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    if dev["platform"] != "tpu":
        print(json.dumps({"ok": False, "toy": True, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


# ============================ code that runs only in children ===========
# Everything below imports jax or sparknet_tpu, and is reached only
# through `--child NAME`: one process, one chip.

def _out(**kw):
    print("CHILD_JSON " + json.dumps(kw), flush=True)
    return 0


def child_probe(c):
    """Versions, devices, the native library, and what ends a timing."""
    import importlib.metadata as md
    import jax
    import jax.numpy as jnp
    from sparknet_tpu import native
    from sparknet_tpu.utils.compile_cache import configure_compile_cache

    def ver(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "absent"
    print(f"probe: python {sys.version.split()[0]}, jax {ver('jax')}, "
          f"jaxlib {ver('jaxlib')}, libtpu {ver('libtpu')}, "
          f"numpy {ver('numpy')}")
    print(f"probe: compile cache: {configure_compile_cache()} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    devs = jax.devices()
    d = devs[0]
    print("probe: devices: " + ", ".join(
        f"{d.id}:{d.platform}:{d.device_kind}" for d in devs))
    if d.platform != "tpu" and not c["toy"]:
        return _out(platform=d.platform, kind=d.device_kind,
                    count=len(devs))
    # the native library is rebuilt from pipeline.cpp here, whatever lies
    # on disk; with a compiler present, no library is a failure
    if shutil.which("g++"):
        so = native.build()
        print(f"probe: native library built from pipeline.cpp: "
              f"{os.path.basename(so)}")
    print(f"probe: native.available() = {native.available()}")
    if shutil.which("g++") and not native.available():
        raise SystemExit("g++ is here and the native library is not")

    # what ends a timing on this link: does block_until_ready wait for
    # the device, or is a value fetch needed?
    n, iters = c["n"], c["iters"]
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, iters, lambda i, a: (a @ x) * (1.0 / n), x)
    float(chain(x)[0, 0])
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    # the question is whether anything is left to wait for once
    # block_until_ready has returned
    waits = (t3 - t2) < 0.2 * (t2 - t0)
    print(f"probe: {iters} chained {n}x{n} bf16 matmuls: dispatch returned "
          f"in {(t1 - t0) * 1e3:.2f} ms, block_until_ready took "
          f"{(t2 - t1) * 1e3:.2f} ms, a value fetch after it "
          f"{(t3 - t2) * 1e3:.2f} ms -> block_until_ready "
          f"{'WAITS for the device' if waits else 'does NOT wait'}"
          f" ({2 * n**3 * iters / max(t2 - t0, 1e-9) / 1e12:.1f} TFLOP/s)")
    return _out(platform=d.platform, kind=d.device_kind, count=len(devs),
                block_until_ready_waits=bool(waits))


def child_write_prototxt(c):
    """CaffeNet + solver prototxt from zoo.caffenet. Touches no device."""
    from sparknet_tpu.models import zoo
    from sparknet_tpu.proto import Message, text_format
    net_path = os.path.join(c["dir"], "caffenet_train.prototxt")
    text_format.dump(zoo.caffenet(batch_size=c["train_batch"],
                                  num_classes=c["classes"]), net_path)
    sp = Message("SolverParameter", net=net_path, base_lr=0.001,
                 lr_policy="fixed", momentum=0.9, weight_decay=0.0005,
                 display=1, max_iter=c["train_iters"], random_seed=0,
                 snapshot_prefix=c["prefix"])
    solver_path = os.path.join(c["dir"], "caffenet_solver.prototxt")
    text_format.dump(sp, solver_path)
    return _out(solver=solver_path)


def _request_inputs(c):
    import numpy as np
    rs = np.random.RandomState(7)
    return [(rs.randn(rows, 3, 227, 227) * 40.0).astype(np.float32)
            for rows in c["request_rows"]]


def child_make_requests(c):
    """The /predict bodies, from a seed. Touches no device."""
    for i, x in enumerate(_request_inputs(c)):
        with open(os.path.join(c["dir"], f"request{i}.json"), "w") as f:
            json.dump({"data": x.tolist()}, f)
    return _out(n=len(c["request_rows"]))


def child_direct_forward(c):
    """The same weights, forwarded directly: no engine, no batcher, no
    padding. Compares with what the server answered."""
    import numpy as np
    import jax
    from sparknet_tpu.graph.compiler import CompiledNet, TEST
    from sparknet_tpu.proto import wire
    from sparknet_tpu.resilience import checkpoint
    from sparknet_tpu.serve.engine import deploy_net_param
    from sparknet_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    model_path, entry = checkpoint.load_model_only(c["prefix"])
    proto = wire.load(model_path, "NetParameter")
    deploy = deploy_net_param(proto)
    worst, max_abs, shape, ok = 0.0, 0.0, None, True
    for i, x in enumerate(_request_inputs(c)):
        net = CompiledNet(deploy.copy(), TEST,
                          feed_shapes={"data": x.shape})
        params, state = net.init(jax.random.PRNGKey(0))
        params, state = net.load_netproto(proto, params, state)
        blobs, _ = jax.jit(lambda p, s, b: net.apply(p, s, b, train=False))(
            params, state, {"data": x})
        direct = np.asarray(blobs["fc8"], np.float32)
        with open(os.path.join(c["dir"], f"response{i}.json")) as f:
            served = np.asarray(json.load(f)["outputs"]["fc8"], np.float32)
        shape = list(served.shape)
        ok &= served.shape == direct.shape == (x.shape[0], c["classes"])
        ok &= bool(np.isfinite(served).all()) and bool(np.any(served != 0))
        max_abs = max(max_abs, float(np.abs(direct).max()))
        worst = max(worst, float(np.abs(served - direct).max()))
    # bf16-pass matmuls on the MXU, bucket-padded batch vs exact batch
    allowed = 2e-2 * max_abs + 1e-6
    ok &= worst <= allowed and max_abs > 0
    ms = jax.devices()[0].memory_stats() or {}
    peak = ms.get("peak_bytes_in_use")
    return _out(ok=bool(ok), shape=shape, max_abs=max_abs, max_diff=worst,
                allowed=allowed,
                hbm_peak=f"{peak / 2**30:.2f} GiB" if peak
                else "not reported by this backend")


def child_lm_hlo(c):
    """The lm verb's step, built the way cmd_lm builds it, lowered and
    compiled the way Solver.compiled_memory_stats does."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.models import zoo
    from sparknet_tpu.proto import Message
    from sparknet_tpu.solver.solver import Solver
    from sparknet_tpu.utils.compile_cache import configure_compile_cache
    cache = configure_compile_cache()
    sp = Message("SolverParameter", base_lr=3e-4, lr_policy="fixed",
                 display=1, type="Adam", random_seed=0, snapshot=0)
    net = zoo.transformer_lm(
        num_layers=c["layers"], moe_experts=0, moe_aux_weight=0.01,
        vocab_size=c["vocab"], seq_len=c["seq_len"], batch_size=c["batch"],
        d_model=c["d_model"], num_heads=c["heads"], flash=True)
    solver = Solver(sp, net_param=net, dtype=jnp.float32,
                    compute_dtype=jnp.bfloat16)
    toks = np.zeros((c["batch"], c["seq_len"]), np.int32)
    batch = {"data": jnp.asarray(toks), "label": jnp.asarray(toks)}
    def entries():
        return len(os.listdir(cache)) if os.path.isdir(cache) else 0
    before = entries() if cache else None
    t0 = time.perf_counter()
    compiled = solver._memory_step_fn(batch).lower(
        *solver._memory_step_args(batch)).compile()
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    return _out(platform=jax.devices()[0].platform,
                tpu_custom_call=text.count("tpu_custom_call"),
                **{"while": len(re.findall(r"\bwhile\(", text))},
                arg_gb=ma.argument_size_in_bytes / 1e9,
                temp_gb=ma.temp_size_in_bytes / 1e9, compile_s=dt,
                new_entries=entries() - before if cache else None)


def child_mesh(c):
    """Four devices, one process. CaffeNet without its two Dropout layers:
    each shard draws its own dropout stream by design, so only the
    dropout-free net can EQUAL the one-device run."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.models import zoo
    from sparknet_tpu.proto import Message
    from sparknet_tpu.solver.solver import Solver
    from sparknet_tpu.parallel import (make_mesh, DataParallelSolver,
                                       LocalSGDSolver)
    from sparknet_tpu.parallel.data_parallel import shard_batch
    from sparknet_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    n = c["chips"]
    devs = jax.devices()[:n]
    if len(devs) != n:
        raise SystemExit(f"{len(devs)} devices, {n} wanted")
    per, G, K = c["per_chip"], c["per_chip"] * n, c["classes"]
    tau, ok = c["tau"], True
    print(f"mesh: devices {[f'{d.id}:{d.device_kind}' for d in devs]}; "
          f"SPARKNET_OVERLAP={os.environ.get('SPARKNET_OVERLAP', 'unset (default on)')}")

    def caffenet(batch):
        net = zoo.caffenet(batch_size=batch, num_classes=K)
        kept = [lp for lp in net.layer if lp.type != "Dropout"]
        net.layer.clear()
        for lp in kept:
            net.layer.append(lp)
        return net

    def sp():
        return Message("SolverParameter", base_lr=0.01, momentum=0.9,
                       weight_decay=0.0005, lr_policy="fixed", display=0,
                       random_seed=0)

    rs = np.random.RandomState(0)
    nb = max(c["steps"], tau * c["rounds"])
    data = (rs.randn(nb, G, 3, 227, 227) * 40.0).astype(np.float32)
    label = rs.randint(0, K, (nb, G)).astype(np.int32)
    mesh = make_mesh({"data": n}, devices=devs)

    def distinct(arr):
        return len({s.device for s in arr.addressable_shards})

    def hlo_counts(text):
        return {k: len(re.findall(r"\b" + k + r"(-start)?\(", text))
                for k in ("all-reduce", "while")}

    def close(a, b, tol=2e-2):
        return abs(a - b) <= tol * max(abs(a), abs(b), 1e-6)

    # -- one device: Solver on the global batch ---------------------------
    one = Solver(sp(), net_param=caffenet(G))
    ref = [float(one.train_step({"data": data[i], "label": label[i]}))
           for i in range(c["steps"])]
    print("mesh: one-device Solver b%d losses   %s"
          % (G, " ".join(f"{v:.5f}" for v in ref)))
    del one

    # -- DataParallelSolver: per-step gradient all-reduce -----------------
    dp = DataParallelSolver(sp(), mesh=mesh, net_param=caffenet(G))
    t = []
    got = []
    for i in range(c["steps"]):
        t0 = time.perf_counter()
        got.append(float(dp.train_step({"data": data[i],
                                        "label": label[i]})))
        t.append(time.perf_counter() - t0)
    print("mesh: DataParallelSolver %dx%d losses %s"
          % (n, per, " ".join(f"{v:.5f}" for v in got)))
    print("mesh: dp step seconds (first two compile): "
          + " ".join(f"{s:.3f}" for s in t))
    b0 = {"data": data[0], "label": label[0]}
    text = dp._memory_step_fn(b0).lower(
        *dp._memory_step_args(b0)).compile().as_text()
    cnt = hlo_counts(text)
    w = dp.params["fc6"][0]
    placed = shard_batch(b0, mesh, "data")["data"]
    shard_rows = placed.addressable_shards[0].data.shape[0]
    print(f"mesh: dp compiled step: all-reduce x{cnt['all-reduce']}; "
          f"fc6 weight replicas on {distinct(w)} distinct devices; batch "
          f"shards of {shard_rows} rows on {distinct(placed)} distinct "
          "devices")
    dp_ok = all(close(a, b) for a, b in zip(got, ref)) \
        and cnt["all-reduce"] > 0 and distinct(w) == n \
        and distinct(placed) == n and shard_rows == per
    print(f"mesh: dp agrees with one device within 2e-2 rel: "
          f"{all(close(a, b) for a, b in zip(got, ref))}")
    ok &= dp_ok
    del dp

    # -- LocalSGDSolver: tau local steps, then average --------------------
    # one-device reference of round 0: each worker's tau steps run alone
    # on a one-device mesh from the same start, then the host averages
    def rounds_of(i):
        return {"data": data[i * tau:(i + 1) * tau],
                "label": label[i * tau:(i + 1) * tau]}

    ls = LocalSGDSolver(sp(), mesh=mesh, tau=tau, net_param=caffenet(per))
    init = np.asarray(ls.params["fc8"][0], np.float32)
    t, got = [], []
    for r in range(c["rounds"]):
        t0 = time.perf_counter()
        got.append(float(ls.train_round(rounds_of(r))))
        t.append(time.perf_counter() - t0)
        if r == 0:
            after0 = np.asarray(ls.params["fc8"][0], np.float32)
    print("mesh: LocalSGDSolver %dx%d tau=%d round losses %s"
          % (n, per, tau, " ".join(f"{v:.5f}" for v in got)))
    print("mesh: local-sgd round seconds (first two compile): "
          + " ".join(f"{s:.3f}" for s in t))
    dev_b = shard_batch(rounds_of(0), mesh, "data", batch_dim=1)
    text = ls._jit_round.lower(
        ls.params, ls.state, ls.history, dev_b,
        jnp.asarray(ls.iter, jnp.int32), ls.rng, ls._alive_mask(),
        ls._staleness_lag()).compile().as_text()
    cnt = hlo_counts(text)
    w = ls.params["fc6"][0]
    print(f"mesh: local-sgd compiled round: all-reduce x{cnt['all-reduce']},"
          f" while x{cnt['while']} (the scanned tau loop); fc6 weight on "
          f"{distinct(w)} distinct devices; batch shards on "
          f"{distinct(dev_b['data'])} distinct devices")
    del ls
    mesh1 = make_mesh({"data": 1}, devices=devs[:1])
    losses, fc8 = [], []
    for wk in range(n):
        s1 = LocalSGDSolver(sp(), mesh=mesh1, tau=tau,
                            net_param=caffenet(per))
        sl = slice(wk * per, (wk + 1) * per)
        losses.append(float(s1.train_round(
            {"data": data[:tau, sl], "label": label[:tau, sl]})))
        fc8.append(np.asarray(s1.params["fc8"][0], np.float32))
        del s1
    ref_loss = float(np.mean(losses))
    ref_fc8 = np.mean(fc8, axis=0)
    upd = float(np.abs(after0 - init).max())
    diff = float(np.abs(after0 - ref_fc8).max())
    print(f"mesh: one-device reference of round 0 ({n} workers run one "
          f"after another, averaged on the host): loss {ref_loss:.5f} vs "
          f"{got[0]:.5f}; fc8 weights moved by at most {upd:.4e} in the "
          f"round, max |mesh - reference| after it {diff:.3e}")
    ls_ok = close(got[0], ref_loss) and 0 < upd and diff <= 5e-2 * upd \
        and all(math.isfinite(v) for v in got) \
        and cnt["all-reduce"] > 0 and distinct(w) == n \
        and distinct(dev_b["data"]) == n
    ok &= ls_ok
    ms = devs[0].memory_stats() or {}
    peak = ms.get("peak_bytes_in_use")
    print("mesh: peak HBM on device 0: "
          + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))
    return _out(ok=bool(ok), dp_ok=bool(dp_ok), ls_ok=bool(ls_ok))


CHILDREN = {"probe": child_probe, "write_prototxt": child_write_prototxt,
            "make_requests": child_make_requests,
            "direct_forward": child_direct_forward,
            "lm_hlo": child_lm_hlo, "mesh": child_mesh}

if __name__ == "__main__":
    sys.exit(main())
