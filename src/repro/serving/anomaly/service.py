"""Batched anomaly-scoring service with inference-time failover.

The live half of the paper's failure-tolerance story: clients stream
traffic windows, the service coalesces them into fixed-size batch
buckets (each bucket ONE pre-compiled entry point,
:mod:`repro.serving.anomaly.engine`), routes every window to its
cluster-head model, and — reusing :func:`~repro.core.failure.
trace_alive_mask` semantics at inference time — fails over
ResiliNet-style to the client's isolated model while its head is dead,
failing back on recovery.

Mechanics, SHARK-Engine ``BatchGenerateService`` style:

* **buckets** — ``ServiceConfig.bucket_sizes`` (default 1/8/64), each
  compiled ahead of time at construction; a warm persistent cache
  (:mod:`repro.core.compilecache`) means a FRESH process stands up the
  whole bucket set with zero traces and zero XLA compiles.
* **work queue** — :meth:`AnomalyService.submit` enqueues
  ``(client, window)`` FIFO; :meth:`AnomalyService.tick` drains it,
  grouping each drained chunk by ROUTED MODEL ROW and packing every
  group into the smallest bucket that fits (padding the remainder).
  Results reassemble in submission order, so a client's windows are
  never reordered and no submitted window is ever dropped.  The
  per-row grouping is the throughput move: a bucket with uniform
  weights lowers to the same big GEMMs as a direct
  ``anomaly_scores`` call (see :mod:`~repro.serving.anomaly.engine`).
* **liveness** — one :class:`~repro.core.failure.FailureTrace` (or a
  sampled :class:`~repro.core.processes.FailureProcess`) drives the
  per-tick alive mask (precomputed over the horizon, so a tick costs
  no eager device ops); the service tick IS the trace epoch.
* **routing** — head alive: bank row 0 (the global model); head dead:
  row ``client + 1`` (the isolated model).  Row selection is a gather
  inside the compiled core, so failover scores are bit-identical to
  scoring the isolated model directly.

:meth:`AnomalyService.report` summarises a served stream: windows and
dispatches, p50/p99 latency, failover/failback counts and — when
submissions carry labels — per-regime AUROC (windows served by a head
vs. windows served by an isolated fallback).  Each tick's host stages
run in the ``serve.*`` spans of :mod:`repro.obs`.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.failure import Failure, NO_FAILURE, PAD_EPOCH, as_trace
from repro.core.processes import FailureProcess, process_seed
from repro.serving.anomaly import engine
from repro.serving.anomaly.bank import ModelBank
from repro.training.metrics import auroc


@dataclass(frozen=True)
class ServiceConfig:
    """Static service shape: everything here is shape-only (it lands in
    the compiled buckets' abstract-argument signatures, never in the
    program), classified as such in ``plancheck.cachekey``."""

    bucket_sizes: Tuple[int, ...] = (1, 8, 64)   # one executable each
    window: int = 16                             # rows per traffic window

    def __post_init__(self):
        assert self.bucket_sizes, "at least one batch bucket"
        assert all(b > 0 for b in self.bucket_sizes), self.bucket_sizes
        assert self.window > 0, self.window


class ScoredWindow(NamedTuple):
    """One scored traffic window, as returned by :meth:`tick`."""
    client: int
    seq: int                 # per-client submission sequence number
    epoch: int               # service tick it was scored at
    scores: np.ndarray       # (window,) per-row anomaly scores
    served_by: str           # "head" | "isolated"
    latency_s: float         # submit -> scored wall clock


@dataclass
class ServiceReport:
    """Summary of a served stream (the serving-side companion of the
    campaign's AUROC tables)."""
    windows: int             # windows scored
    dropped: int             # submitted but never scored (always 0)
    batches: int             # compiled-bucket dispatches
    p50_ms: float            # per-window submit->scored latency
    p99_ms: float
    failovers: int           # head->isolated transitions
    failbacks: int           # isolated->head transitions
    auroc_head: float        # AUROC of head-served windows (nan: no labels)
    auroc_isolated: float    # AUROC of failover-served windows
    bucket_batches: Dict[int, int] = field(default_factory=dict)

    def describe(self) -> str:
        return (f"{self.windows} windows in {self.batches} batches "
                f"(p50 {self.p50_ms:.2f} ms, p99 {self.p99_ms:.2f} ms), "
                f"{self.failovers} failovers / {self.failbacks} "
                f"failbacks, AUROC head={self.auroc_head:.3f} "
                f"isolated={self.auroc_isolated:.3f}, "
                f"dropped={self.dropped}")


class _Request(NamedTuple):
    client: int
    seq: int
    window: np.ndarray
    labels: Optional[np.ndarray]
    t_submit: float


class AnomalyService:
    """Batched failover scoring service over a trained
    :class:`~repro.serving.anomaly.bank.ModelBank`.

    ``failure`` may be ``None`` (nothing ever fails), any legacy
    :class:`FailureSpec` / explicit :class:`FailureTrace`, or a
    :class:`FailureProcess` — sampled once at construction with the
    same SHA-256-derived seeding as campaign trace grids, so a service
    stood up twice replays the identical outage."""

    def __init__(self, bank: ModelBank,
                 config: ServiceConfig = ServiceConfig(),
                 failure: Union[None, Failure, FailureProcess] = None,
                 sample_seed: int = 0, horizon: int = 256):
        self.bank = bank
        self.config = config
        topo = bank.topology
        if failure is None:
            self._trace = as_trace(NO_FAILURE, topo)
        elif isinstance(failure, FailureProcess):
            rng = np.random.default_rng(
                process_seed(sample_seed, failure, 0))
            self._trace = failure.sample(rng, topo, horizon)
        else:
            self._trace = as_trace(failure, topo)
        self._heads = np.asarray(topo.heads)
        self._cluster_of = np.asarray(topo.device_cluster_array())
        # liveness precomputed over the horizon in ONE device call:
        # a tick then indexes a host table instead of dispatching eager
        # trace_alive_mask ops (measured ~1 ms/tick, comparable to a
        # whole 64-bucket dispatch)
        ep = np.asarray(self._trace.epochs)
        real = ep[ep < PAD_EPOCH]
        n_epochs = int(max(horizon, (int(real.max()) + 2) if real.size
                           else 1))
        self._alive_table = engine.alive_table(self._trace,
                                               topo.num_devices, n_epochs)
        self._buckets = tuple(sorted(set(config.bucket_sizes)))
        self._pending: deque = deque()
        self._seq: Dict[int, int] = {}
        self._mode: Dict[int, str] = {}       # last served_by per client
        self.epoch = 0
        self.timeline: List[Tuple[int, int, str]] = []
        # counters the report aggregates
        self._submitted = 0
        self._scored = 0
        self._batches = 0
        self._bucket_batches: Dict[int, int] = {b: 0 for b in self._buckets}
        self._failovers = 0
        self._failbacks = 0
        self._latencies: List[float] = []
        self._regime_scores: Dict[str, list] = {"head": [], "isolated": []}
        self._regime_labels: Dict[str, list] = {"head": [], "isolated": []}
        # pre-compile every bucket (memory -> disk -> compile)
        self._compiled: Dict[int, object] = {}
        self.compile_sources: Dict[int, str] = {}
        param_avals = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
            bank.row_params)
        for bs in self._buckets:
            avals = (param_avals,
                     jax.ShapeDtypeStruct((), jnp.int32),
                     jax.ShapeDtypeStruct((bs, config.window,
                                           bank.input_dim), jnp.float32))
            compiled, times = engine.score_executable(bank.detector, avals)
            self._compiled[bs] = compiled
            self.compile_sources[bs] = times.source

    # ------------------------------------------------------------------
    # client side
    # ------------------------------------------------------------------
    def submit(self, client: int, window: np.ndarray,
               labels: Optional[np.ndarray] = None) -> int:
        """Enqueue one traffic window for ``client``; returns the
        client's submission sequence number.  ``window`` is
        ``(config.window, input_dim)`` float32; optional ``labels``
        (one per row, 1 = anomalous) feed the per-regime AUROC."""
        window = np.asarray(window, np.float32)
        expect = (self.config.window, self.bank.input_dim)
        assert window.shape == expect, (window.shape, expect)
        seq = self._seq.get(client, 0)
        self._seq[client] = seq + 1
        self._pending.append(_Request(
            int(client), seq, window,
            None if labels is None else np.asarray(labels),
            time.perf_counter()))
        self._submitted += 1
        return seq

    @property
    def pending(self) -> int:
        return len(self._pending)

    # ------------------------------------------------------------------
    # service side
    # ------------------------------------------------------------------
    def alive_mask(self, epoch: Optional[int] = None) -> np.ndarray:
        """(N,) liveness at a service tick (default: the current one).
        Liveness is a step function of the epoch, so epochs past the
        precomputed table clamp to its last (post-final-event) row."""
        e = self.epoch if epoch is None else epoch
        return self._alive_table[min(e, len(self._alive_table) - 1)]

    def _pick_bucket(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def tick(self) -> List[ScoredWindow]:
        """Drain the queue at the current epoch, then advance it.

        Every pending window is scored (zero drops): the queue is
        consumed FIFO in chunks of at most the largest bucket; each
        chunk is grouped by ROUTED BANK ROW and every group dispatched
        through the smallest bucket that fits (remainder rows padded
        with zero windows — padding is sliced off before results are
        reassembled in submission order)."""
        with obs.span("serve.tick"):
            alive = self.alive_mask()
            out: List[ScoredWindow] = []
            while self._pending:
                self._tick_chunk(alive, out)
            self.epoch += 1
        return out

    def _tick_chunk(self, alive: np.ndarray, out: List[ScoredWindow]
                    ) -> None:
        """Score one chunk of at most the largest bucket, one span per
        stage (:data:`repro.obs.SERVE_STAGES`): every group's bucket is
        packed, then all are dispatched, then all fetched."""
        with obs.span("serve.group"):
            n = min(len(self._pending), self._buckets[-1])
            chunk = [self._pending.popleft() for _ in range(n)]
            modes: List[str] = []
            groups: Dict[int, List[int]] = {}
            for i, r in enumerate(chunk):
                head = int(self._heads[self._cluster_of[r.client]])
                failover = alive[head] <= 0.0
                modes.append("isolated" if failover else "head")
                row = self.bank.row_index(r.client, failover)
                groups.setdefault(row, []).append(i)
        with obs.span("serve.pack"):
            packed = []
            for row, members in groups.items():
                bs = self._pick_bucket(len(members))
                x = np.zeros((bs, self.config.window,
                              self.bank.input_dim), np.float32)
                for j, i in enumerate(members):
                    x[j] = chunk[i].window
                packed.append((bs, row, members, x))
        with obs.span("serve.dispatch"):
            pending = [self._compiled[bs](self.bank.row_params,
                                          np.int32(row), x)
                       for bs, row, _, x in packed]
        with obs.span("serve.fetch"):
            got = [np.asarray(g) for g in pending]
        with obs.span("serve.reassemble"):
            scores = np.empty((n, self.config.window), np.float32)
            for (bs, _, members, _), g in zip(packed, got):
                scores[np.asarray(members)] = g[:len(members)]
                self._batches += 1
                self._bucket_batches[bs] += 1
            t1 = time.perf_counter()
            for i, (r, mode) in enumerate(zip(chunk, modes)):
                prev = self._mode.get(r.client, "head")
                if mode != prev:
                    if mode == "isolated":
                        self._failovers += 1
                        self.timeline.append((self.epoch, r.client,
                                              "failover"))
                    else:
                        self._failbacks += 1
                        self.timeline.append((self.epoch, r.client,
                                              "failback"))
                self._mode[r.client] = mode
                self._scored += 1
                self._latencies.append(t1 - r.t_submit)
                if r.labels is not None:
                    self._regime_scores[mode].append(scores[i])
                    self._regime_labels[mode].append(r.labels)
                out.append(ScoredWindow(r.client, r.seq, self.epoch,
                                        scores[i], mode, t1 - r.t_submit))

    def run(self, epochs: int) -> List[ScoredWindow]:
        """Tick ``epochs`` times (anything queued between ticks by the
        caller is scored on the next one)."""
        out: List[ScoredWindow] = []
        for _ in range(epochs):
            out.extend(self.tick())
        return out

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def _regime_auroc(self, regime: str) -> float:
        if not self._regime_scores[regime]:
            return float("nan")
        s = np.concatenate([np.ravel(v)
                            for v in self._regime_scores[regime]])
        y = np.concatenate([np.ravel(v)
                            for v in self._regime_labels[regime]])
        return auroc(s, y)

    def report(self) -> ServiceReport:
        lat = np.asarray(self._latencies) * 1e3 if self._latencies \
            else np.zeros((1,))
        return ServiceReport(
            windows=self._scored,
            dropped=self._submitted - self._scored - len(self._pending),
            batches=self._batches,
            p50_ms=float(np.percentile(lat, 50)),
            p99_ms=float(np.percentile(lat, 99)),
            failovers=self._failovers,
            failbacks=self._failbacks,
            auroc_head=self._regime_auroc("head"),
            auroc_isolated=self._regime_auroc("isolated"),
            bucket_batches=dict(self._bucket_batches))
