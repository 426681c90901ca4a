"""What a run of one cell is made of: finding the cell's files by name, the
device, the timed object with seeded weights, its first three steps (which
the correctness check reads), the window, and the plain reference. `run.py`
strings them into one run; `control.py` and the tests reuse the parts.
"""

import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_SECONDS = 3.0                 # traced part of a --trace 1 window
WARM_UNITS = 2                      # whole sync units run before the window
CHECKED_STEPS = 3                   # steps the reference follows


def say(*a):
    print(*a, flush=True)


class Marks:
    """Where a run's seconds go: named marks on the host's clock, printed
    on an earlier line so that set-up can be read phase by phase."""

    def __init__(self):
        self.last = time.perf_counter()
        self.spans = []

    def mark(self, name):
        now = time.perf_counter()
        self.spans.append((name, now - self.last))
        self.last = now

    def __str__(self):
        return ", ".join(f"{n} {s:.2f}" for n, s in self.spans)


MARKS = Marks()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_path(path):
    mod, attr = path.split(":")
    return getattr(importlib.import_module(mod), attr)


def load_reference(config, batch):
    """What `reference/<name>.py` hands the harness for `config` at `batch`
    (`reference.plain.LayerList` describes it): the module's own
    `build(config, batch)`, or its layer list through `LayerList`."""
    mod = importlib.import_module(f"reference.{config['reference']}")
    if hasattr(mod, "build"):
        return mod.build(config, batch)
    import flops
    from reference import plain
    return plain.LayerList(*flops.reference_net(config, batch),
                           config["solver"])


class Cell:
    """The files of one workload, found by the names in the BENCHMARK.json
    beside `here`: the benchmark's own directory, or one laid out like it
    (the tests' fixtures), whose files are found first and which may use
    the benchmark's own by name."""

    def __init__(self, name, rehearse=False, here=HERE):
        if here not in sys.path:
            sys.path.insert(0, here)
        import reference           # the one directory here with an __init__
        if os.path.join(here, "reference") not in reference.__path__:
            reference.__path__.insert(0, os.path.join(here, "reference"))
        self.here = here
        self.bench = load_json(os.path.dirname(here), "BENCHMARK.json")
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             "BENCHMARK.json")
        self.name = name
        self.file = load_json(here, "workloads", f"{name}.json")
        for key in ("config", "traffic", "chips"):
            if self.file[key] != entry[key]:
                raise SystemExit(f"benchmark: {name}: {key} differs between "
                                 "BENCHMARK.json and the cell file")
        self.config = load_json(here, "configs", f"{entry['config']}.json")
        self.traffic = load_json(here, "traffic",
                                 f"{entry['traffic']}.json")
        self.chips = int(entry["chips"])
        self.toy = self.file.get("toy", {}) if rehearse else {}
        self.batch = int(self.toy.get("batch", self.traffic["batch"]))
        self.sync_every = int(self.traffic["sync_every"])
        self.builder_args = dict(self.config.get("builder_args", {}),
                                 **self.toy.get("builder_args", {}))
        self.num_classes = self.builder_args.get("num_classes", 1000)
        self.solver_cfg = self.config["solver"]
        # the configuration's limits, unless the cell's traffic moves them
        self.limits = dict(self.config["check"]["limits"],
                           **self.file.get("check_limits", {}))
        self.limits.update(self.toy.get("check_limits", {}))

        self.sized_config = dict(self.config, builder_args=self.builder_args)
        self.ref = load_reference(self.sized_config, self.batch)
        self.specs = self.ref.specs
        self.data_shape = tuple(self.ref.inputs[0][1])

    def flops_per_sample(self):
        import flops
        return flops.train_flops_per_sample(self.sized_config)


def find_device(chips, rehearse=False):
    """(devices, peaks row) this run measures, or exit: no fallback to
    another backend, and no device without published peaks."""
    import jax
    import flops
    devs = jax.devices()        # the accelerator's runtime starts here
    MARKS.mark("device")
    if rehearse:
        return devs[:chips], {"bf16_flops": float("nan")}
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (jax found {devs[0].platform}: "
                         f"{devs[0].device_kind}); no result")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, jax "
                         f"found {len(devs)}; no result")
    peak = flops.peak_for(devs[0].device_kind,
                          load_json(HERE, "peaks.json"))
    if peak is None:
        raise SystemExit(f"benchmark: device kind {devs[0].device_kind!r} "
                         "is not in benchmark/peaks.json; no result")
    return devs[:chips], peak


def configure_cache():
    """The program's own rule for the compile cache (a fixed directory in
    the checkout, or JAX_COMPILATION_CACHE_DIR), and small programs —
    weights, norms — cached too, so that a second run compiles nothing."""
    import jax
    from sparknet_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    if cache_dir:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def memory_peak(devices):
    """Peak bytes on the fullest chip. On this backend `peak_bytes_in_use`
    counts live buffers only; what the compiled step needs besides (its
    temporaries) the runtime holds as `bytes_reserved`. For CaffeNet b1536
    the two add up to 1.25 + 4.33 GB against XLA's own memory analysis of
    5.34 GB for the step (my chip run and sandbox compile, PR 24)."""
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0))
                   + int(ms.get("peak_bytes_reserved", 0)))
    return peak


class Timed:
    """The timed object: the program's solver with the benchmark's seeded
    weights, and the cell's feed. One of these is built per process; the
    check's three steps and the window drive the same one."""

    def __init__(self, cell, seed):
        from sparknet_tpu.proto import Message
        self.cell = cell
        net = by_path(cell.config["builder"])(batch_size=cell.batch,
                                              **cell.builder_args)
        # the configuration's solver block is the SolverParameter's fields
        # (type, rates, moments, decay), less what only its reference reads
        fields = {k: v for k, v in cell.solver_cfg.items()
                  if k not in ("weight_mults", "bias_mults", "note")}
        sp = Message("SolverParameter", display=0,
                     random_seed=seed % (2 ** 31 - 1), **fields)
        cls = by_path(cell.file.get("solver",
                                    "sparknet_tpu.solver.solver:Solver"))
        # arguments of the solver's constructor; a `*dtype` is named
        import jax.numpy as jnp
        args = {k: getattr(jnp, v) if k.endswith("dtype") else v
                for k, v in cell.config.get("solver_args", {}).items()}
        self.solver = cls(sp, net_param=net, log_fn=None, **args)
        MARKS.mark("solver")
        self.feed = None
        self.feed_wait, self.dispatch, self.losses = [], [], []
        self.tracing = False
        self.reseed(seed)

    def reseed(self, seed):
        """Seeded weights, zero momentum, the run's step stream, a new
        feed. `run.py` calls this once; `control.py` once per seed."""
        import jax
        import jax.numpy as jnp
        import weights
        solver, cell = self.solver, self.cell
        self.w0 = weights.make_weights(cell.specs, seed)
        if set(self.w0) != set(solver.params):
            raise SystemExit(
                "benchmark: the reference's weight blobs "
                f"{sorted(set(self.w0) ^ set(solver.params))} do not match "
                "the program's")
        for name, blobs in self.w0.items():
            mine = solver.params[name]
            if [b.shape for b in blobs] != [m.shape for m in mine]:
                raise SystemExit(f"benchmark: blob shapes of {name} differ")
            # copies: the program donates its weights to every step
            solver.params[name] = [
                jax.device_put(jnp.array(b, dtype=m.dtype, copy=True),
                               m.sharding) for b, m in zip(blobs, mine)]
        solver.history = jax.tree_util.tree_map(jnp.zeros_like,
                                                solver.history)
        MARKS.mark("weights")
        # the reference follows this stream
        solver.rng = weights.seed_key(seed, weights.STEPS)
        solver.iter, solver._it_dev = 0, None
        if self.feed is not None:
            self.feed.close()
        mod = importlib.import_module(f"feeds.{cell.traffic['feed']}")
        self.feed = mod.build(traffic=cell.traffic, config=cell.sized_config,
                              seed=seed, solver=solver,
                              data_shape=cell.data_shape,
                              num_classes=cell.num_classes)
        del self.feed_wait[:], self.dispatch[:], self.losses[:]
        MARKS.mark("feed")

    # -- the window's own call and feed ---------------------------------
    def one_step(self):
        import jax
        if self.tracing:
            with jax.profiler.TraceAnnotation("bench.feed_next"):
                t0 = time.perf_counter()
                b = next(self.feed)
                t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.train_step"):
                loss = self.solver.train_step(b)
                t2 = time.perf_counter()
        else:
            t0 = time.perf_counter()
            b = next(self.feed)
            t1 = time.perf_counter()
            loss = self.solver.train_step(b)
            t2 = time.perf_counter()
        self.feed_wait.append(t1 - t0)
        self.dispatch.append(t2 - t1)
        self.losses.append(loss)
        return loss

    def one_unit(self):
        import jax
        for _ in range(self.cell.sync_every):
            loss = self.one_step()
        if self.tracing:
            with jax.profiler.TraceAnnotation("bench.sync"):
                return float(loss)
        return float(loss)

    def checked_steps(self):
        """The first three steps, through one_step. -> the program's side
        of the check, and the seconds until the first step's result."""
        import jax
        import check
        cell, solver = self.cell, self.solver
        got = {"losses": []}
        t = time.perf_counter()
        got["losses"].append(float(self.one_step()))
        first_step_s = time.perf_counter() - t
        MARKS.mark("step1")
        # the program keeps SGD's momentum, and Adam's first moment, as
        # history[layer][blob][0]
        slot0 = {n: [h[0] for h in blobs]
                 for n, blobs in solver.history.items()}
        # kept on the host so that the window's memory stays the program's
        got["grads"] = jax.device_get(check.first_gradients(
            slot0, self.w0, cell.specs, cell.solver_cfg))
        del slot0
        for _ in range(CHECKED_STEPS - 1):
            got["losses"].append(float(self.one_step()))
        got["dparams"] = jax.device_get(
            check.leaves(solver.params, cell.specs, minus=self.w0))
        self.w0 = None
        MARKS.mark("steps2-3+readings")
        return got, first_step_s

    def programs(self):
        """How many programs the step's jit has compiled so far."""
        fn = getattr(self.solver, "_jit_train", None)
        size = getattr(fn, "_cache_size", None)
        return size() if size else None

    def window(self, seconds, trace_dir=None):
        """Sync units back to back for `seconds`. -> (starts, ends) on the
        host's clock. With `trace_dir`, the profiler runs for
        TRACE_SECONDS from the second unit on."""
        import jax
        starts, ends, traced = [], [], None
        t_open = time.perf_counter()
        try:
            while True:
                now = time.perf_counter()
                if now - t_open >= seconds and (traced or not trace_dir):
                    break               # a traced window holds a traced unit
                if trace_dir and traced is None and starts:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0   # spans, not every call
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                    self.tracing, traced = True, time.perf_counter()
                if self.tracing:
                    with jax.profiler.TraceAnnotation("bench.unit"):
                        starts.append(time.perf_counter())
                        self.one_unit()
                        ends.append(time.perf_counter())
                    if ends[-1] - traced >= TRACE_SECONDS:
                        jax.profiler.stop_trace()
                        self.tracing = False
                else:
                    starts.append(time.perf_counter())
                    self.one_unit()
                    ends.append(time.perf_counter())
        finally:
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False
        return starts, ends

    def reference_inputs(self):
        return [self.feed.reference_inputs(i) for i in range(CHECKED_STEPS)]

    def free(self):
        """Close the feed and drop the program's state from the device."""
        self.feed.close()
        s = self.solver
        s.params = s.history = s.state = None
        self.losses = []


def run_reference(cell, seed, inputs, control=False):
    """The plain reference through the same three steps: the seed's weights,
    the window's first three inputs, the run's dropout stream; float32 at
    `highest`. `control` makes it the lower-precision control instead: the
    configuration's `check.control` names the types one step below the
    ones it states, for the blobs and for the stored weights."""
    import jax
    import jax.numpy as jnp
    import check
    import weights
    block = cell.toy.get("reference_block_rows",
                         cell.config["check"].get("reference_block_rows"))
    low = cell.config["check"]["control"] if control else {}
    step = cell.ref.make_step(
        cell.solver_cfg, block_rows=block,
        quant=getattr(jnp, low["activations"]) if low else None,
        masters=getattr(jnp, low["masters"]) if low else None)
    MARKS.mark("reference build")
    with jax.default_matmul_precision("highest"):
        w0 = weights.make_weights(cell.specs, seed)
        params, history = w0, None
        out = {"losses": []}
        key = weights.seed_key(seed, weights.STEPS)
        for i, (data, labels) in enumerate(inputs):
            key, sub = jax.random.split(key)
            params, history, loss, grads = step(params, history, data,
                                                labels, sub)
            out["losses"].append(float(loss))
            if i == 0:
                out["grads"] = check.leaves(grads, cell.specs)
            del grads
            MARKS.mark(f"reference step{i + 1}")
        out["dparams"] = check.leaves(params, cell.specs, minus=w0)
    return out


def settle():
    """Last act of set-up: collect garbage once and move everything that is
    alive (the traced programs' jaxprs above all) out of the collector's
    reach, so that no full collection walks it inside the window. Cheap
    (0.03 s) and not the cure it was hoped to be: single units that stall
    for 0.1 to 5 s came with and without it (my chip runs, PR 24), so they
    are the machine's."""
    import gc
    gc.collect()
    gc.freeze()


def count_failed(step_losses, programs_before, programs_after):
    """Steps of the window that failed: a loss that is not finite, and
    every step once the step's jit compiled inside the window."""
    if programs_before != programs_after:
        return len(step_losses)
    return sum(1 for x in step_losses if not math.isfinite(float(x)))
