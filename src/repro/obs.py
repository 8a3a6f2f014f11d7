"""The program's own spans and counters, on the profiler's clock.

* :func:`span` — a named host span: it enters
  ``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows it
  on the same host timeline (and clock) as the device's ``XLA Ops``
  line, and times the block with ``time.perf_counter``.
* :func:`count` — adds to a named counter.
* :class:`Tally` — the totals of the spans and counters entered on the
  current thread while it is open (``with Tally() as t: ...``).
  Tallies nest, and never see another thread's spans: four executions
  on four threads keep four separate accounts.
* :func:`record_execution` / :func:`executions` — a bounded registry of
  one record per campaign ``execute()``, in completion order.
* A ``gc.callbacks`` hook logs every generation-1 and generation-2
  collection as a ``gc`` span and into a pause log
  (:func:`gc_pause_s`); generation-0 collections are ignored.

Always on: with no profiler session running, a span's enter and exit
cost about 2 us (measured on a TPU v5e host), and a campaign execution
enters a few dozen.

Names used by the program:

* campaign host stages, one span each per bucket
  (:mod:`repro.core.experiment`, :mod:`repro.core.campaign`):
  :data:`CAMPAIGN_STAGES`, plus ``campaign.plan`` around ``plan()``;
* campaign counters: :data:`H2D_BYTES`, :data:`FUSED_OUT_BUCKETS`;
* service tick stages (:mod:`repro.serving.anomaly.service`):
  :data:`SERVE_STAGES`;
* device stages, ``jax.named_scope`` inside the compiled cores (HLO
  op metadata only): :data:`SINGLE_SCOPES`, :data:`MULTI_SCOPES`,
  :data:`SERVE_SCOPES`.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import defaultdict, deque
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

CAMPAIGN_STAGES = ("campaign.build", "campaign.resolve", "campaign.dispatch",
                   "campaign.fetch", "campaign.post")
H2D_BYTES = "campaign.h2d_bytes"
#: 1 for each campaign bucket whose core runs the fused output-layer
#: kernel (:func:`repro.kernels.ops.recon_out_layer`), 0 for the others
FUSED_OUT_BUCKETS = "campaign.fused_out_buckets"
SERVE_STAGES = ("serve.tick", "serve.group", "serve.pack", "serve.dispatch",
                "serve.fetch", "serve.reassemble")
SINGLE_SCOPES = ("alive_mask", "local_delta", "combine", "iso_fallback",
                 "test_scoring", "final_scoring")
MULTI_SCOPES = ("alive_mask", "assign", "local_grad", "combine",
                "test_scoring", "final_scoring")
SERVE_SCOPES = ("score_bucket",)
#: the registry keeps the newest this many execution records
MAX_EXECUTIONS = 4096
#: and the pause log the newest this many collections
MAX_PAUSES = 65536

_executions_lock = threading.Lock()
_executions: deque = deque(maxlen=MAX_EXECUTIONS)
_local = threading.local()
#: (start, end) perf_counter times of gen-1/2 collections; appended by
#: the gc hook without a lock (a deque append is atomic), read by
#: copying it whole
_pauses: deque = deque(maxlen=MAX_PAUSES)


def _tallies() -> List["Tally"]:
    stack = getattr(_local, "tallies", None)
    if stack is None:
        stack = _local.tallies = []
    return stack


class Tally:
    """Seconds per span name and counter totals of what this thread
    enters between ``__enter__`` and ``__exit__``; ``start`` / ``end``
    are the ``perf_counter`` times of both."""

    def __init__(self):
        self.seconds: Dict[str, float] = defaultdict(float)
        self.counters: Dict[str, float] = defaultdict(float)
        self.start = self.end = 0.0

    def __enter__(self) -> "Tally":
        _tallies().append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        _tallies().remove(self)


class span:
    """``with span(name): ...`` — a profiler host span and a timed
    block, added to every open :class:`Tally` of this thread."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        for t in _tallies():
            t.seconds[self.name] += dt


def count(name: str, n: float) -> None:
    """Add ``n`` to counter ``name`` in this thread's open tallies."""
    for t in _tallies():
        t.counters[name] += n


def to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, counting the bytes a host array puts
    on the device under :data:`H2D_BYTES`."""
    arr = jnp.asarray(x, dtype)
    if not isinstance(x, jax.Array):
        count(H2D_BYTES, arr.nbytes)
    return arr


# ---------------------------------------------------------------------------
# GC pauses
# ---------------------------------------------------------------------------
def _gc_hook(phase: str, info: dict) -> None:
    if info["generation"] == 0:
        return
    if phase == "start":
        ann = jax.profiler.TraceAnnotation("gc")
        ann.__enter__()
        _local.gc = (time.perf_counter(), ann)
    else:
        started = getattr(_local, "gc", None)
        if started is None:     # the hook was installed mid-collection
            return
        _local.gc = None
        t0, ann = started
        _pauses.append((t0, time.perf_counter()))
        ann.__exit__(None, None, None)


def gc_pauses() -> Tuple[Tuple[float, float], ...]:
    """The logged (start, end) ``perf_counter`` times of generation-1
    and generation-2 collections, oldest first."""
    return tuple(_pauses)


def gc_pause_s(lo: float, hi: float) -> float:
    """Seconds of collection pause inside ``[lo, hi]``.  A pause stops
    every thread, so it counts against everything running then."""
    total = 0.0
    for s, e in reversed(gc_pauses()):
        if e <= lo:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


if _gc_hook not in gc.callbacks:
    gc.callbacks.append(_gc_hook)


# ---------------------------------------------------------------------------
# Per-execution registry
# ---------------------------------------------------------------------------
def record_execution(tally: Tally, **fields) -> dict:
    """Append one execution's record, built from its closed ``tally``:
    ``wall_s``, the seconds of each of :data:`CAMPAIGN_STAGES` (as
    ``build_s``, ``resolve_s``, ...), ``h2d_bytes``,
    ``fused_out_buckets``, ``gc_s`` (the collection pause inside the
    execution) and ``fields``."""
    gc_s = gc_pause_s(tally.start, tally.end)
    rec = {"start": tally.start, "end": tally.end,
           "wall_s": tally.end - tally.start,
           "h2d_bytes": tally.counters[H2D_BYTES],
           "fused_out_buckets": tally.counters[FUSED_OUT_BUCKETS],
           "gc_s": gc_s}
    for name in CAMPAIGN_STAGES:
        rec[name.split(".", 1)[1] + "_s"] = tally.seconds[name]
    rec.update(fields)
    with _executions_lock:
        _executions.append(rec)
    return rec


def executions() -> List[dict]:
    """The newest :data:`MAX_EXECUTIONS` execution records, in the
    order the executions completed."""
    with _executions_lock:
        return list(_executions)
