"""The harness's own host spans: wall time per name on the host clock,
and the same span in the profiler's trace when one is being taken.
Spans may run on several threads at once; each adds its own time."""
from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict

import jax


class Spans:
    def __init__(self):
        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.seconds[name] += dt
                self.count[name] += 1
