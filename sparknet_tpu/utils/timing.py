"""Timing — the reference util/benchmark.cpp Timer/CPUTimer, plus a
step-rate tracker.

Reference Timer used CUDA events for device-accurate timing; on TPU the
analog is waiting for the device before reading the clock. A value fetch
(transfer of a scalar) and block_until_ready both do: chip_smoke.py's
probe times the two on every run and prints which waited.
"""

import time

import numpy as np


class Timer:
    """Start/Stop/MilliSeconds like benchmark.cpp:26-142."""

    def __init__(self):
        self._start = None
        self._elapsed = 0.0

    def start(self):
        self._start = time.perf_counter()
        return self

    def stop(self, sync=None):
        """sync: an optional jax array to fetch (device barrier)."""
        if sync is not None:
            np.asarray(sync).ravel()[:1]
        if self._start is not None:
            self._elapsed += time.perf_counter() - self._start
            self._start = None
        return self

    def milliseconds(self):
        return self._elapsed * 1e3

    def seconds(self):
        return self._elapsed


class StepTimer:
    """Rolling images/sec + step-time stats for the training loop."""

    def __init__(self, window=20):
        self.window = window
        self.times = []
        self._last = None

    def tick(self, batch_size=None):
        now = time.perf_counter()
        if self._last is not None:
            self.times.append((now - self._last, batch_size or 0))
            if len(self.times) > self.window:
                self.times.pop(0)
        self._last = now

    def step_ms(self):
        if not self.times:
            return float("nan")
        return float(np.mean([t for t, _ in self.times])) * 1e3

    def images_per_sec(self):
        ts = [(t, b) for t, b in self.times if b]
        if not ts:
            return float("nan")
        return sum(b for _, b in ts) / sum(t for t, _ in ts)
