"""Observability subsystem: tracing, step accounting, comms metering.

The SparkNet paper's central result is a communication/compute tradeoff
(tau local steps vs. broadcast/collect cost), but the reference had no
structured way to measure it — loss and timing went to glog and ad-hoc
prints (SURVEY.md section 5). This package is the measurement layer every
perf PR reports against:

  trace.py      the one span system: an always-on ring, profiler
                annotations on the device trace's clock, JSONL `span`
                events, jax's compile events, Chrome trace_event export;
                and the steady-state jax.profiler toggle
  stepstats.py  host-dispatch vs device-wall step accounting, recompile
                detection, p50/p95/p99 step-time histograms
  comms.py      bytes moved per sync round (ring-allreduce cost model,
                mapped to the paper's broadcast/collect model), plus
                host->device feed byte counters
  divergence.py per-sync-round worker-weight divergence measured
                on-device before the averaging pmean (the paper's tau
                drift, plus a gradient-noise-scale proxy)
  health.py     rolling anomaly detectors over the round signals —
                stragglers, loss skew, divergence trends — emitting
                structured ``health`` alarms that can arm recovery
  memstats.py   live-array/HBM/compile-cache/rss sampling so step-time
                regressions decompose into recompile vs memory pressure
  report.py     `sparknet report`: aggregate a metrics JSONL into a
                human-readable run report + machine-readable JSON
  monitor.py    `sparknet monitor`: tail a live metrics JSONL and
                render an in-place terminal summary of the run

Everything writes through one utils.metrics.MetricsLogger, so a single
JSONL stream carries spans, steps, comms, recompiles, watchdog barks,
prefetch gauges, and the training curve together.
"""

from .trace import (Tracer, JaxProfiler, chrome_from_spans, export_chrome,
                    default_tracer)
from .stepstats import StepAccounting, percentiles, device_memory
from .comms import (CommsMeter, tree_bytes, ring_allreduce_bytes,
                    broadcast_collect_bytes, all_to_all_bytes)
from .divergence import DivergenceMeter, consensus_stats, tree_sq_dist
from .health import HealthMonitor
from .memstats import MemoryMonitor

__all__ = [
    "Tracer", "JaxProfiler", "chrome_from_spans", "export_chrome",
    "default_tracer",
    "StepAccounting", "percentiles", "device_memory",
    "CommsMeter", "tree_bytes", "ring_allreduce_bytes",
    "broadcast_collect_bytes", "all_to_all_bytes",
    "DivergenceMeter", "consensus_stats", "tree_sq_dist",
    "HealthMonitor", "MemoryMonitor",
]
