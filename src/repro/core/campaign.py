"""Batched Monte-Carlo failure-campaign engine.

The paper's robustness claims ("a range of realistic settings that
consider client as well as server failure") need *scenario diversity*:
grids of failure traces x seeds, not one hand-picked event per run.
This module sweeps such grids at hardware speed: for one static
``SimConfig`` (scheme, k, rounds, ...) every (trace, seed) scenario in
the batch runs through ONE ``jit(vmap(core))`` executable — the core is
the exact same round-loop :func:`repro.core.simulate.run_simulation`
uses, so per-scenario results match the single-shot simulator
(``tests/test_campaign.py`` asserts equality and the single compile).

Typical use::

    traces = [FailureTrace.none(),
              FailureTrace.from_spec(FailureSpec(10, "server"), topo)]
    res = run_campaign(ae_cfg, dx, counts, test_x, test_y,
                       SimConfig(scheme="tolfl", num_clusters=5),
                       traces, seeds=range(8))
    res.summary()["auroc_used_mean"]

The multi-model baselines (FedGroup / IFCA / FeSEM) get the same
treatment: :func:`run_multimodel_campaign` vmaps the pure core from
:mod:`repro.core.baselines` over a stacked (trace x seed) grid, so the
paper's Table III-V comparison columns also cost one compile per cell.

Execution scales along three independent axes (:class:`ExecPlan`):

* **compile amortisation** — data arrays are ARGUMENTS of an
  lru-cached jitted batched core, never closed over, so repeated
  campaigns on the same shapes (the benchmarks' inner loops) reuse the
  compiled executable instead of re-tracing per campaign;
* **scenario sharding** — ``ExecPlan(shard=True)`` pads the
  (trace x seed) batch to a device-divisible size and dispatches it
  through a ``shard_map`` over the local-device "scenario" mesh axis
  (:func:`repro.sharding.scenario_shard_map`), so B scenarios run on D
  devices in B/D time;
* **host chunking** — ``ExecPlan(chunk_size=c)`` slices the batch into
  same-shape chunks (the last one padded, padding stripped after), so
  arbitrarily large grids run in bounded device memory with ONE compile.

Different schemes / k imply different topologies, but topology is just
ARRAYS to the core (:func:`repro.core.simulate._build_core_arrays`), so
a (scheme x k) grid needs neither one compile per cell NOR one dispatch
per cell — :func:`sweep_grid` stacks the padded per-cell cluster arrays
(head indices, ``device_cluster_array``, ``head_valid`` — padded to the
per-kind max k) along the scenario axis and runs ALL cells of one
iso-tracking kind through a SINGLE ``jit(vmap)`` over the flattened
(cell x trace x seed) batch: all sbt/tolfl cells in one dispatch, all
fl cells in another (the fl fallback branch costs extra per-round
compute, so non-fl cells never pay for it).  Multi-model cells fuse the
same way per scheme: the model axis pads to the grid's max M with a
``model_valid`` mask, so (ifca, 2) and (ifca, 3) share one executable
and one dispatch.  Padded cluster/model slots are exact no-ops in the
combine algebra, so results match the per-cell paths bit-for-bit
(``fuse=False`` restores one dispatch per cell, ``pad_k=False`` the
one-compile-per-cell static build; both pinned equal by tests).

As of the declarative experiment layer
(:mod:`repro.core.experiment` / :mod:`repro.api`) this module is the
MECHANISM — cached executables, batched dispatch, result types — and
the entry points above are thin shims that lower their arguments to an
``ExperimentSpec`` and run ``plan(spec) -> execute(plan)``.  New code
should declare specs; the shims stay for back-compat and are pinned
bit-identical by ``tests/test_experiment.py``.
"""
from __future__ import annotations

import functools
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.baselines import MultiModelConfig, _build_multimodel_core
from repro.core.failure import Failure, FailureTrace
from repro.core.simulate import SimConfig, _build_core, _build_core_arrays
from repro.models.detector import ModelLike, canonical_model_key
from repro.sharding import scenario_shard_map
from repro.training.metrics import auroc_batch

#: incremented each time a batched campaign core is (re)traced — lets
#: tests assert that a whole campaign costs exactly one compile.  AOT
#: lowering (``aot_executable``) traces on worker threads, so the
#: increment is lock-guarded.
TRACE_COUNT = 0
_TRACE_LOCK = threading.Lock()

#: schemes dispatched to the multi-model engine by :func:`sweep_grid`
MULTI_SCHEMES = ("fedgroup", "ifca", "fesem")


@dataclass(frozen=True)
class ExecPlan:
    """How a campaign batch is executed (results never change with it).

    shard
        Split the scenario axis across the local JAX devices via
        ``shard_map`` (the batch is padded up to a device-divisible
        size; padding is stripped from the results).  On a single-device
        host sharding warns and degrades to the plain jitted path.
    chunk_size
        Host-side chunking: at most this many scenarios are resident on
        the devices at once; every chunk has the same padded shape so
        the whole campaign still costs one compile.  ``None`` runs the
        batch in one shot.
    devices
        Cap on the number of local devices used when sharding
        (default: all of ``jax.local_device_count()``).
    aot
        Ahead-of-time compilation: ``execute()`` lowers and compiles
        every dispatch bucket (``jit(...).lower().compile()``) on a
        thread pool at plan-finalise time, overlapping XLA with the
        host-side data/trace array builds, and dispatches through the
        compiled executables.  Results are bit-identical to the jit
        path (pinned by ``tests/test_aot.py``); combined with the
        persistent disk cache (:mod:`repro.core.compilecache`) a warm
        re-run in a fresh process skips XLA entirely.

    Invalid values raise ``ValueError`` at construction (they used to
    surface as shape errors deep inside ``_run_batched``).
    """
    shard: bool = False
    chunk_size: Optional[int] = None
    devices: Optional[int] = None
    aot: bool = False

    def __post_init__(self):
        if self.chunk_size is not None and self.chunk_size <= 0:
            raise ValueError(
                f"ExecPlan.chunk_size must be a positive number of "
                f"scenarios (or None for one-shot), got "
                f"{self.chunk_size}")
        if self.devices is not None and self.devices <= 0:
            raise ValueError(
                f"ExecPlan.devices must be a positive device count "
                f"(or None for all local devices), got {self.devices}")

    def num_devices(self) -> int:
        n = jax.local_device_count()
        return min(self.devices, n) if self.devices else n

    def resolved_devices(self, warn: bool = True) -> Optional[int]:
        """Shard width actually used: ``None`` when not sharding — and
        when ``shard=True`` finds only one local device, in which case
        it warns and degrades to the (identical-result) unsharded path
        instead of paying shard_map overhead for nothing."""
        if not self.shard:
            return None
        n = self.num_devices()
        if n <= 1:
            if warn:
                warnings.warn(
                    "ExecPlan(shard=True) found a single local device; "
                    "degrading to the unsharded path (results are "
                    "identical). Set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N to fake "
                    "a multi-device host.", UserWarning, stacklevel=2)
            return None
        return n


def mean_ci95(vals: np.ndarray) -> Tuple[float, float, float]:
    """(mean, sample std, normal-approx 95% CI half-width) over seeds.

    Uses the SAMPLE standard deviation (ddof=1): campaigns estimate the
    spread of a seed population from few draws, and the ddof=0
    population formula reports over-tight intervals for small B.  A
    single scenario has no spread estimate: std 0, CI half-width nan."""
    b = len(vals)
    mean = float(np.mean(vals))
    if b <= 1:
        return mean, 0.0, float("nan")
    std = float(np.std(vals, ddof=1))
    return mean, std, 1.96 * std / np.sqrt(b)


@dataclass
class CampaignResult:
    """Stacked per-scenario results of one batched campaign.

    Scenario b is (trace ``trace_index[b]``, seed ``seed[b]``); arrays
    are aligned on that leading axis."""
    cfg: SimConfig
    trace_index: np.ndarray        # (B,) int — index into the trace list
    seed: np.ndarray               # (B,) int
    auroc_used: np.ndarray         # (B,) paper-reported AUROC
    final_auroc: np.ndarray        # (B,) global-model AUROC
    iso_auroc: np.ndarray          # (B,) isolated-mean AUROC (nan if n/a)
    iso_active: np.ndarray         # (B,) bool — FL fallback engaged
    loss_curves: np.ndarray        # (B, rounds) REPORTED loss: global,
    #                                but FL server-dead rounds carry the
    #                                isolated mean (Fig 4 semantics)
    iso_loss_curves: np.ndarray    # (B, rounds)
    rounds_to_loss: np.ndarray     # (B,) float, nan when never reached

    @property
    def num_scenarios(self) -> int:
        return len(self.auroc_used)

    def select(self, trace_index: int) -> np.ndarray:
        """auroc_used of every scenario using trace ``trace_index``."""
        return self.auroc_used[self.trace_index == trace_index]

    def summary(self) -> Dict[str, float]:
        """Mean / sample std / normal-approx 95% CI of the reported
        AUROC plus mean rounds-to-loss (over scenarios that reached the
        target)."""
        mean, std, half = mean_ci95(self.auroc_used)
        r2l = self.rounds_to_loss[np.isfinite(self.rounds_to_loss)]
        return {
            "num_scenarios": float(self.num_scenarios),
            "auroc_used_mean": mean,
            "auroc_used_std": std,
            "auroc_used_ci95_lo": mean - half,
            "auroc_used_ci95_hi": mean + half,
            "rounds_to_loss_mean": (float(np.mean(r2l)) if len(r2l)
                                    else float("nan")),
        }


@dataclass
class MultiCampaignResult:
    """Stacked per-scenario results of one batched multi-model campaign.

    Scenario b is (trace ``trace_index[b]``, seed ``seed[b]``)."""
    cfg: MultiModelConfig
    trace_index: np.ndarray        # (B,) int — index into the trace list
    seed: np.ndarray               # (B,) int
    best_auroc: np.ndarray         # (B,) the paper's * column
    multi_auroc: np.ndarray        # (B,) the paper's dagger column
    loss_curves: np.ndarray        # (B, rounds) per-sample-min test loss
    assignments: np.ndarray        # (B, N) final device -> model maps

    @property
    def num_scenarios(self) -> int:
        return len(self.best_auroc)

    def select(self, trace_index: int, column: str = "best") -> np.ndarray:
        """best/multi AUROC of every scenario using ``trace_index``."""
        vals = {"best": self.best_auroc, "multi": self.multi_auroc}[column]
        return vals[self.trace_index == trace_index]

    def summary(self) -> Dict[str, float]:
        out: Dict[str, float] = {"num_scenarios": float(self.num_scenarios)}
        for column in ("best", "multi"):
            vals = {"best": self.best_auroc,
                    "multi": self.multi_auroc}[column]
            mean, std, half = mean_ci95(vals)
            out[f"{column}_auroc_mean"] = mean
            out[f"{column}_auroc_std"] = std
            out[f"{column}_auroc_ci95_lo"] = mean - half
            out[f"{column}_auroc_ci95_hi"] = mean + half
        return out


def _scenario_grid(num_traces: int, seeds: Sequence[int]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Full cross product: trace-major, seed-minor."""
    seeds = np.asarray(list(seeds), np.int32)
    trace_idx = np.repeat(np.arange(num_traces, dtype=np.int32),
                          len(seeds))
    seed_arr = np.tile(seeds, num_traces)
    return trace_idx, seed_arr


# ---------------------------------------------------------------------------
# Cached batched executables.  Data/topology arrays are ARGUMENTS (broadcast
# over the vmap), never closed over: the jit lives in an lru_cache keyed on
# the static config, so repeated campaigns with the same shapes reuse the
# compiled executable instead of re-tracing per campaign.
# ---------------------------------------------------------------------------
def _exe_key(kind: str, model: ModelLike, cfg, k_pad, ndev,
             track_iso: bool, fused: bool) -> tuple:
    """Canonical executable-cache key.

    Everything that changes the lowered program is in here — the
    detector-model spec, the static config (scheme/k-normalised by the
    caller on padded paths), the cluster-axis pad, the shard width, the
    iso-tracking kind and the fused/broadcast operand split — and
    NOTHING else: redundant degrees of freedom are normalised away so
    two spellings of the same configuration can never compile twice
    (``functools.lru_cache`` would otherwise key ``f(a, b)`` and
    ``f(a, b=...)`` differently, and a ``track_iso`` flag disagreeing
    with ``cfg.scheme`` on the static path would duplicate an identical
    program).  ``model`` canonicalises through
    :func:`repro.models.detector.canonical_model_key`: autoencoder
    specs key on the raw :class:`AutoencoderConfig` — whichever
    spelling the caller used — so pre-detector cache keys and
    persistent-cache fingerprints are bit-identical; other bodies key
    on their frozen spec.  Shapes/dtypes are deliberately NOT part of
    this key: the jit path retraces per shape inside one entry, and the
    AOT path extends the key with the abstract-argument signature
    (``aot_executable``).  The faulty-update engine variants
    (``FaultySimConfig`` / ``FaultyMultiModelConfig``) key through
    ``cfg`` by CLASS IDENTITY — dataclass ``__eq__``/``repr`` include
    the class — so faulty cores get distinct entries and fingerprints
    while plain-config keys stay bit-identical.  Pinned by
    ``tests/test_cache_semantics.py`` and ``tests/test_detector.py``."""
    model = canonical_model_key(model)
    if kind == "multi":
        assert k_pad is None, "multi-model cells pad M via cfg.num_models"
        track_iso = False          # the multi core has no iso branch
    elif k_pad is None:
        # static build: _build_core derives the iso branch from
        # cfg.scheme and there is no fused-static path — a divergent
        # flag would alias a second identical executable
        track_iso = cfg.scheme == "fl"
        fused = False
    return (kind, model, cfg, k_pad, ndev, bool(track_iso), bool(fused))


def _executable(kind: str, model: ModelLike, cfg, k_pad, ndev,
                track_iso: bool = False, fused: bool = False):
    """Batched scenario executable (see :func:`_build_executable`); the
    lru key is the canonical :func:`_exe_key`, never the raw call
    spelling."""
    return _build_executable(*_exe_key(kind, model, cfg, k_pad, ndev,
                                       track_iso, fused))


@functools.lru_cache(maxsize=64)
def _build_executable(kind: str, model: ModelLike, cfg, k_pad,
                      ndev, track_iso: bool, fused: bool):
    """Batched scenario executable.

    kind
        "single" (SimConfig core) or "multi" (MultiModelConfig core).
    k_pad
        None -> topology closed over statically (the single-campaign
        path); int -> topology enters as dynamic arrays padded to
        ``k_pad`` — the compile-amortised sweep path.  The ``cfg`` key
        is then scheme/k-normalised by the caller so every sweep cell of
        the same ``track_iso`` kind hits the SAME cache entry
        (``track_iso`` stays in the key: the fl fallback branch costs
        extra per-round compute, so non-fl cells must not pay for it —
        one executable per kind, not per cell).
    fused
        The cell-varying operands (padded cluster arrays for "single",
        the ``model_valid`` mask for "multi") move from the broadcast
        group into the MAPPED group, so one dispatch sweeps a flattened
        (cell x trace x seed) scenario axis where every row carries its
        own topology — the whole-grid fused path.
    ndev
        None -> plain ``jit``; int -> ``jit(shard_map(...))`` over an
        (ndev,)-device "scenario" mesh, batch axis sharded, broadcasts
        replicated (:func:`repro.sharding.scenario_shard_map`).
    """
    if kind == "multi":
        core = _build_multimodel_core(model, cfg)
        n_bcast, n_mapped = (4, 3) if fused else (5, 2)
    elif k_pad is None:
        core = _build_core(model, cfg, score_history=False)
        n_bcast, n_mapped = 4, 2
    else:
        core = _build_core_arrays(model, cfg, cfg.num_devices, k_pad,
                                  track_iso=track_iso,
                                  score_history=False)
        n_bcast, n_mapped = (4, 5) if fused else (7, 2)

    def scenario(*args):
        global TRACE_COUNT
        with _TRACE_LOCK:         # AOT lowers on worker threads
            TRACE_COUNT += 1      # runs at trace time only: 1 per compile
        return core(*args)

    vm = jax.vmap(scenario,
                  in_axes=(None,) * n_bcast + (0,) * n_mapped)
    if ndev is None:
        return jax.jit(vm)
    return jax.jit(scenario_shard_map(vm, ndev, n_bcast, n_mapped))


# ---------------------------------------------------------------------------
# AOT entry path.  Same canonical key-space as the jit path, extended by
# the abstract-argument signature (a compiled executable is pinned to
# exact shapes/dtypes, unlike a jit that retraces per shape).  Because
# ``aot_executable`` lowers THROUGH the lru-cached jit wrapper, the
# trace cache is shared: an AOT compile followed by a jit call on the
# same shapes re-traces nothing, and vice versa — the warm in-process
# path is untouched and bit-identical.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AotTimes:
    """Wall-clock of one :func:`aot_executable` resolution.  ``source``
    is where the executable came from: ``"memory"`` (in-process AOT
    cache, both times 0), ``"disk"`` (deserialised from the persistent
    executable cache — ``compile_s`` is the load time and NOTHING was
    traced or XLA-compiled) or ``"compiled"`` (lowered + compiled this
    call)."""
    lower_s: float = 0.0
    compile_s: float = 0.0
    source: str = "compiled"

    @property
    def cached(self) -> bool:
        return self.source == "memory"


_AOT_CACHE: Dict[tuple, Any] = {}
_AOT_LOCK = threading.Lock()

#: AOT bucket compiles pass this explicit (default-valued, so codegen
#: is unchanged) option override: it lands in the compile-options hash,
#: giving AOT compiles a module-cache key DISJOINT from the jit path's.
#: On jax/jaxlib 0.9.0 an XLA:CPU executable that the persistent module
#: cache served does not survive re-serialisation (the reloaded payload
#: fails with "Function ... not found"), so an AOT compile must never be
#: satisfied by a module entry a previous jit-path run wrote — the key
#: split guarantees a genuine, whole-serialisable compile without
#: touching global config (the host's utility jits keep caching normally
#: while the worker pool compiles).  On a TPU v5e the same round trip
#: loads and runs; the split is kept on every backend so the engine has
#: one path, at the price of separate module-cache entries.
_AOT_COMPILER_OPTIONS = {"xla_embed_ir_in_executable": False}


def _avals_signature(abstract_args) -> tuple:
    """Hashable (treedef, leaf shape/dtype) signature of an abstract
    argument tuple — what distinguishes compiled executables within one
    jit cache entry."""
    leaves, treedef = jax.tree.flatten(abstract_args)
    return (str(treedef),
            tuple((tuple(l.shape), jnp.dtype(l.dtype).name)
                  for l in leaves))


def _lowering_config() -> tuple:
    """Global settings that change the lowered program without changing
    the avals — the default matmul precision (``float32`` on a TPU is a
    six-pass program, the default one bfloat16 pass) — so executables
    compiled under different settings never share a cache entry."""
    return (("matmul_precision",
             str(jax.config.jax_default_matmul_precision)),)


def aot_executable(kind: str, model: ModelLike, cfg, k_pad, ndev,
                   track_iso: bool, fused: bool, abstract_args
                   ) -> Tuple[Any, AotTimes]:
    """Lower + compile the batched core for ``abstract_args`` (a tuple
    of ``jax.ShapeDtypeStruct`` pytrees matching the concrete call) and
    cache the compiled executable under canonical key + aval signature.

    Returns ``(compiled, AotTimes)``; ``compiled(*concrete_args)`` is
    bit-identical to calling the jitted executable (it IS the same
    lowering — pinned by ``tests/test_aot.py``).  Thread-safe: the
    experiment layer compiles buckets on a worker pool while the host
    builds data arrays."""
    from repro.core import compilecache as _cc
    key = _exe_key(kind, model, cfg, k_pad, ndev, track_iso, fused)
    full_key = key + _avals_signature(abstract_args) + _lowering_config()
    with _AOT_LOCK:
        hit = _AOT_CACHE.get(full_key)
    if hit is not None:
        return hit, AotTimes(source="memory")
    # persistent executable cache: a warm fresh process deserialises
    # the whole compiled executable — no trace, no lower, no XLA
    fp = _cc.exe_fingerprint(full_key)
    t0 = time.perf_counter()
    loaded = _cc.load_executable(fp)
    if loaded is not None:
        with _AOT_LOCK:
            loaded = _AOT_CACHE.setdefault(full_key, loaded)
        return loaded, AotTimes(compile_s=time.perf_counter() - t0,
                                source="disk")
    jitted = _build_executable(*key)
    t0 = time.perf_counter()
    lowered = jitted.lower(*abstract_args)
    t1 = time.perf_counter()
    compiled = lowered.compile(compiler_options=_AOT_COMPILER_OPTIONS)
    t2 = time.perf_counter()
    _cc.store_executable(fp, compiled)
    with _AOT_LOCK:
        # a racing thread may have compiled the same key; keep the first
        compiled = _AOT_CACHE.setdefault(full_key, compiled)
    return compiled, AotTimes(lower_s=t1 - t0, compile_s=t2 - t1)


def clear_executable_caches() -> None:
    """Drop every in-process executable cache (jit lru, AOT compiled,
    the single-shot simulator's core cache) — cold-start benchmarking
    and cache-semantics tests use this to force genuine re-compiles.
    The persistent disk cache (:mod:`repro.core.compilecache`) is NOT
    touched; clear its directory to simulate a truly cold machine."""
    from repro.core import baselines as _bl
    from repro.core import simulate as _sim
    _build_executable.cache_clear()
    with _AOT_LOCK:
        _AOT_CACHE.clear()
    _sim._jitted_core_cached.cache_clear()
    _bl._jitted_multimodel_core_cached.cache_clear()
    jax.clear_caches()


def _run_batched(batched_call, bcast_args, mapped, plan: Optional[ExecPlan],
                 aot_resolve=None):
    """Dispatch a stacked scenario batch through ``batched_call`` with
    host-side chunking and batch padding per ``plan``; returns the
    outputs pytree as numpy arrays with the padding stripped.  Its host
    work runs in the ``campaign.*`` stage spans (:mod:`repro.obs`).

    ``mapped`` is a tuple of pytrees sharing the scenario leading axis —
    (traces, seeds) for a single campaign, plus the stacked per-cell
    topology/model-mask operands on the fused sweep path.

    ``aot_resolve`` (the AOT path) maps the per-chunk abstract-argument
    tuple — derived here from the CONCRETE arrays, so it is exact by
    construction — to a compiled executable that replaces
    ``batched_call`` (every chunk shares one padded shape, so one
    executable serves them all)."""
    plan = plan or ExecPlan()
    B = int(jax.tree.leaves(mapped)[0].shape[0])
    chunk = min(plan.chunk_size or B, B)
    ndev = plan.resolved_devices(warn=False)  # entry points warn once
    if ndev:
        chunk = -(-chunk // ndev) * ndev      # device-divisible chunks
    n_chunks = -(-B // chunk)
    b_pad = n_chunks * chunk
    if b_pad != B:
        # pad by repeating scenario 0 — any valid scenario works, the
        # rows are stripped below before post-processing
        with obs.span("campaign.build"):
            sel = np.concatenate([np.arange(B),
                                  np.zeros(b_pad - B, np.int64)])
            mapped = jax.tree.map(lambda x: x[sel], mapped)
    if aot_resolve is not None:
        with obs.span("campaign.resolve"):
            sds = jax.ShapeDtypeStruct
            avals = tuple(
                jax.tree.map(lambda x: sds(x.shape, x.dtype), a)
                for a in bcast_args) + tuple(
                jax.tree.map(
                    lambda x: sds((chunk,) + x.shape[1:], x.dtype), m)
                for m in mapped)
            batched_call = aot_resolve(avals)
    outs = []
    for c in range(n_chunks):
        sl = slice(c * chunk, (c + 1) * chunk)
        with obs.span("campaign.dispatch"):
            out = batched_call(*bcast_args,
                               *jax.tree.map(lambda x: x[sl], mapped))
        # materialise on the host per chunk: device memory stays bounded
        # by chunk_size however large the grid is
        with obs.span("campaign.fetch"):
            outs.append(jax.tree.map(np.asarray, out))
    if n_chunks == 1 and b_pad == B:
        return outs[0]
    with obs.span("campaign.post"):
        full = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)
        return jax.tree.map(lambda x: x[:B], full)


def _padded_topology_arrays(topo, k_pad: int):
    """(cluster_ids, heads, head_valid) with the cluster axis padded to
    ``k_pad``: padding head slots point at device 0 but are masked
    invalid, and no device maps to a padded cluster."""
    assert k_pad >= topo.num_clusters, (k_pad, topo.num_clusters)
    heads = np.zeros(k_pad, np.int32)
    heads[:topo.num_clusters] = topo.heads
    head_valid = np.zeros(k_pad, np.float32)
    head_valid[:topo.num_clusters] = 1.0
    return (obs.to_device(topo.device_cluster_array()),
            obs.to_device(heads), obs.to_device(head_valid))


def run_campaign(model: ModelLike, device_x: np.ndarray,
                 device_counts: np.ndarray, test_x: np.ndarray,
                 test_y: np.ndarray, cfg: SimConfig,
                 traces: Sequence[Failure], seeds: Sequence[int],
                 target_loss: Optional[float] = None,
                 exec_plan: Optional[ExecPlan] = None,
                 pad_k: Optional[int] = None) -> CampaignResult:
    """Run every (trace x seed) scenario through one compiled executable.

    ``traces`` may mix legacy :class:`FailureSpec`s and
    :class:`FailureTrace`s; all are normalised to traces and stacked.
    ``cfg.seed`` is ignored — seeds come from the grid.  ``exec_plan``
    chooses scenario sharding / host chunking (results are unchanged);
    ``pad_k`` (int >= cfg's cluster count) routes through the padded-k
    core so campaigns with different (scheme, k) share one executable —
    :func:`sweep_grid`'s per-cell path sets it to the grid's per-kind
    max k.  "batch" centralises the data (different array shapes), so
    it always builds statically and ``pad_k`` is ignored.

    A thin shim over the declarative pipeline
    (:mod:`repro.core.experiment`): one-cell spec, per-cell dispatch."""
    from repro.core import experiment as X
    spec = X.ExperimentSpec(
        data=X.DataSpec(model=model, device_x=device_x,
                        device_counts=device_counts, test_x=test_x,
                        test_y=test_y),
        base=cfg,
        cells=(X.CellSpec(scheme=cfg.scheme, k=cfg.num_clusters, cfg=cfg,
                          traces=traces),),
        seeds=X.SeedSpec(tuple(seeds)), exec_plan=exec_plan,
        target_loss=target_loss, fuse=False,
        pad_k=(pad_k is not None), k_pad=pad_k)
    return X.run_experiment(spec).results[0]


def _post_process(cfg, out, trace_idx, seed_arr, test_y, target_loss
                  ) -> CampaignResult:
    fields = _post_process_arrays(cfg.scheme == "fl", out, test_y,
                                  target_loss)
    return CampaignResult(cfg=cfg, trace_index=trace_idx, seed=seed_arr,
                          **fields)


def _post_process_arrays(track_iso: bool, out, test_y, target_loss
                         ) -> Dict[str, np.ndarray]:
    """Scenario-aligned result arrays of a stacked :class:`SimOutputs`
    batch (ONE ``auroc_batch`` sweep over the whole batch, however many
    sweep cells were flattened into it) — everything
    :class:`CampaignResult` stores except the grid bookkeeping."""
    losses = np.asarray(out.losses)                    # (B, R)
    iso_losses = np.asarray(out.iso_losses)
    finals = np.asarray(out.final_scores)              # (B, T)
    iso_scores = np.asarray(out.iso_final_scores)      # (B, N, T')
    final_alive = np.asarray(out.final_alive)          # (B, N)
    dead_rounds = np.asarray(out.server_dead_rounds) > 0   # (B, R)
    server_dead = np.asarray(out.server_dead) > 0      # (B,)
    B = losses.shape[0]

    test_y = np.asarray(test_y)
    final_auroc = auroc_batch(finals, test_y)
    iso_auroc = np.full(B, np.nan)
    iso_active = np.zeros(B, bool)
    if track_iso:
        # Fig 4 semantics (matching run_simulation): server-dead rounds
        # report the isolated-mean loss, not the frozen global model's
        losses = np.where(dead_rounds, iso_losses, losses)
        iso_active = server_dead.copy()
        hit = np.flatnonzero(iso_active)
        if len(hit) and iso_scores.shape[-1]:
            n_dev = iso_scores.shape[1]
            per_dev = auroc_batch(
                iso_scores[hit].reshape(len(hit) * n_dev, -1),
                test_y).reshape(len(hit), n_dev)
            alive = (final_alive[hit] > 0)
            denom = alive.sum(axis=1)
            num = np.where(alive, per_dev, 0.0).sum(axis=1)
            iso_auroc[hit] = np.where(denom > 0,
                                      num / np.maximum(denom, 1),
                                      np.nan)
    auroc_used = np.where(iso_active, iso_auroc, final_auroc)

    r2l = np.full(B, np.nan)
    if target_loss is not None:
        reached = losses <= target_loss                # (B, R)
        any_hit = reached.any(axis=1)
        first = reached.argmax(axis=1) + 1.0
        r2l = np.where(any_hit, first, np.nan)

    return dict(auroc_used=auroc_used, final_auroc=final_auroc,
                iso_auroc=iso_auroc, iso_active=iso_active,
                loss_curves=losses, iso_loss_curves=iso_losses,
                rounds_to_loss=r2l)


def run_multimodel_campaign(model: ModelLike,
                            device_x: np.ndarray,
                            device_counts: np.ndarray, test_x: np.ndarray,
                            test_y: np.ndarray, cfg: MultiModelConfig,
                            traces: Sequence[Failure],
                            seeds: Sequence[int],
                            exec_plan: Optional[ExecPlan] = None
                            ) -> MultiCampaignResult:
    """Every (trace x seed) scenario of a multi-model baseline in one
    jitted, vmapped call — the multi-model twin of :func:`run_campaign`
    (same cached-executable / sharding / chunking machinery).

    ``traces`` may mix legacy :class:`FailureSpec`s and
    :class:`FailureTrace`s; specs are normalised with the BASELINE
    default targets (see :func:`as_multimodel_trace`).  The client/group
    trace split happens in-graph inside the core, so one compiled
    executable covers the whole grid.  ``cfg.seed`` is ignored — seeds
    come from the grid.

    A thin shim over the declarative pipeline
    (:mod:`repro.core.experiment`): one-cell spec, per-cell dispatch."""
    from repro.core import experiment as X
    spec = X.ExperimentSpec(
        data=X.DataSpec(model=model, device_x=device_x,
                        device_counts=device_counts, test_x=test_x,
                        test_y=test_y),
        base=SimConfig(num_devices=cfg.num_devices),
        cells=(X.CellSpec(scheme=cfg.scheme, k=cfg.num_models, cfg=cfg,
                          traces=traces),),
        seeds=X.SeedSpec(tuple(seeds)), exec_plan=exec_plan, fuse=False)
    return X.run_experiment(spec).results[0]


def _multi_metrics(finals: np.ndarray, test_y,
                   model_valid: Optional[np.ndarray] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(best, multi) AUROC columns of stacked (B, M, T) final scores in
    TWO ``auroc_batch`` sweeps total, however many sweep cells were
    flattened into the batch.  ``model_valid`` (B, M) masks padded model
    slots (fused padded-M cells): they never win ``best`` and stay out
    of the per-sample-min ``multi`` score."""
    B, M = finals.shape[0], finals.shape[1]
    test_y = np.asarray(test_y)
    per_model = auroc_batch(finals.reshape(B * M, -1),
                            test_y).reshape(B, M)
    if model_valid is None:
        return per_model.max(axis=1), auroc_batch(finals.min(axis=1),
                                                  test_y)
    live = model_valid > 0
    best = np.where(live, per_model, -np.inf).max(axis=1)
    min_scores = np.where(live[:, :, None], finals, np.inf).min(axis=1)
    return best, auroc_batch(min_scores, test_y)


def _single_trace_key(traces: Sequence[Failure], topo) -> tuple:
    """How a trace list resolves against a topology: pure
    :class:`FailureTrace` lists are topology-independent (one stacked
    batch serves every sweep cell), legacy specs default their targets
    from the heads / cluster-0 layout."""
    if all(isinstance(t, FailureTrace) for t in traces):
        return ()
    return (tuple(topo.heads), tuple(topo.clusters[0]))


def run_fused_campaigns(model: ModelLike, device_x: np.ndarray,
                        device_counts: np.ndarray, test_x: np.ndarray,
                        test_y: np.ndarray,
                        cells: Sequence[Tuple[SimConfig,
                                              Sequence[Failure]]],
                        seeds: Sequence[int],
                        target_loss: Optional[float] = None,
                        exec_plan: Optional[ExecPlan] = None,
                        k_pad: Optional[int] = None
                        ) -> List[CampaignResult]:
    """Many single-model campaign cells, fused into ONE dispatch per
    iso-tracking kind.

    ``cells`` pairs each :class:`SimConfig` with its trace list (lists
    may differ per cell — e.g. per-topology sampled grids — and may be
    the same object, in which case padding/stacking happens once, not
    per cell).  Cells whose static config agrees on everything but
    (scheme, num_clusters) are grouped by iso-tracking kind; each
    group's cluster arrays are padded to ``k_pad`` (default: the group
    max k), stacked per cell, repeated along the per-cell scenario
    batch, and the whole flattened (cell x trace x seed) axis runs
    through a single ``jit(vmap)`` — sharded/chunked as one batch by
    ``exec_plan`` — instead of one dispatch per cell.  Padded slots are
    exact no-ops, so per-cell results match :func:`run_campaign`.
    Post-processing is likewise vectorised: one ``auroc_batch`` sweep
    per group, sliced back into per-cell :class:`CampaignResult`\\ s
    (aligned with ``cells``).

    "batch" cells centralise the data (different array shapes) and are
    rejected — run them through :func:`run_campaign`.

    A thin shim over the declarative pipeline
    (:mod:`repro.core.experiment`): per-cell explicit trace lists,
    fused buckets grouped by :func:`plan`.
    """
    if not cells:
        return []
    for cfg, _ in cells:
        if cfg.scheme == "batch":
            raise ValueError("'batch' cells centralise the data onto one "
                             "device (different array shapes); run them "
                             "via run_campaign")
    from repro.core import experiment as X
    spec = X.ExperimentSpec(
        data=X.DataSpec(model=model, device_x=device_x,
                        device_counts=device_counts, test_x=test_x,
                        test_y=test_y),
        base=cells[0][0],
        cells=tuple(X.CellSpec(scheme=cfg.scheme, k=cfg.num_clusters,
                               cfg=cfg, traces=traces)
                    for cfg, traces in cells),
        seeds=X.SeedSpec(tuple(seeds)), exec_plan=exec_plan,
        target_loss=target_loss, k_pad=k_pad)
    return X.run_experiment(spec).results


def run_fused_multimodel_campaigns(model: ModelLike,
                                   device_x: np.ndarray,
                                   device_counts: np.ndarray,
                                   test_x: np.ndarray, test_y: np.ndarray,
                                   cells: Sequence[Tuple[MultiModelConfig,
                                                         Sequence[Failure]]],
                                   seeds: Sequence[int],
                                   exec_plan: Optional[ExecPlan] = None,
                                   pad_m: Optional[int] = None
                                   ) -> List[MultiCampaignResult]:
    """Many multi-model baseline cells, fused into ONE dispatch per
    scheme — the multi-model twin of :func:`run_fused_campaigns`.

    Cells whose static config agrees on everything but ``num_models``
    are grouped (the assignment rule is structural, so fedgroup / ifca /
    fesem cells always compile separately); each group's model axis is
    padded to ``pad_m`` (default: the group max M) with a
    ``model_valid`` mask stacked along the flattened (cell x trace x
    seed) axis, so cells with DIFFERENT model counts share one compiled
    executable and one dispatch.  Padded model slots are exact no-ops
    (never assigned, never aggregated, masked out of the loss/metrics),
    so per-cell results match :func:`run_multimodel_campaign`.

    A thin shim over the declarative pipeline
    (:mod:`repro.core.experiment`)."""
    if not cells:
        return []
    from repro.core import experiment as X
    spec = X.ExperimentSpec(
        data=X.DataSpec(model=model, device_x=device_x,
                        device_counts=device_counts, test_x=test_x,
                        test_y=test_y),
        base=SimConfig(num_devices=cells[0][0].num_devices),
        cells=tuple(X.CellSpec(scheme=cfg.scheme, k=cfg.num_models,
                               cfg=cfg, traces=traces)
                    for cfg, traces in cells),
        seeds=X.SeedSpec(tuple(seeds)), exec_plan=exec_plan,
        m_pad=pad_m)
    return X.run_experiment(spec).results


def sweep_grid(model: ModelLike, device_x: np.ndarray,
               device_counts: np.ndarray, test_x: np.ndarray,
               test_y: np.ndarray, base: SimConfig,
               scheme_ks: Sequence[Tuple[str, int]],
               traces: Sequence[Failure], seeds: Sequence[int],
               target_loss: Optional[float] = None,
               exec_plan: Optional[ExecPlan] = None,
               pad_k: bool = True, fuse: bool = True
               ) -> Dict[Tuple[str, int], CampaignResult]:
    """(scheme x k) grid of batched campaigns — fused by default: the
    whole grid runs in ONE dispatch per iso-tracking kind (plus one per
    multi-model scheme), not one per cell.

    Single-model schemes (fl/sbt/tolfl) interpret k as the cluster
    count.  With the default ``fuse`` their padded cluster arrays
    (heads, ``device_cluster_array``, ``head_valid`` — padded to the
    grid's max k) become VMAPPED operands stacked along the flattened
    (cell x trace x seed) scenario axis, so all cells of one
    iso-tracking kind share a single ``jit(vmap)`` dispatch as well as a
    single executable: all sbt/tolfl cells go in one call, all fl cells
    in another (their isolated-fallback branch is extra compute non-fl
    cells must not pay).  Trace arrays are built once per grid and the
    post-processing ``auroc_batch`` sweeps the stacked axis once per
    kind (:func:`run_fused_campaigns`).  Padded cluster slots are exact
    no-ops in the combine algebra, so results match the per-cell paths
    bit-for-bit: ``fuse=False`` restores one dispatch per cell (sharing
    executables via broadcast padded arrays — the PR 3 behaviour), and
    ``pad_k=False`` additionally restores the one-compile-per-cell
    static build (both pinned equal by tests).  "batch" cells
    centralise the data onto one device (different array shapes), so
    they always dispatch per cell.

    Multi-model baselines (:data:`MULTI_SCHEMES`) interpret k as the
    model count M; their cells return :class:`MultiCampaignResult`, and
    legacy specs in ``traces`` resolve to the baseline default targets.
    Under ``fuse`` every multi cell is padded to the grid's max M with a
    ``model_valid`` mask, so same-scheme cells with different M also
    share one executable and one dispatch
    (:func:`run_fused_multimodel_campaigns`); ``fuse=False`` dispatches
    each through :func:`run_multimodel_campaign`.  Every cell covers
    the full (trace x seed) scenario batch under ``exec_plan``.

    A thin shim over the declarative pipeline: the grid IS an
    :class:`repro.core.experiment.ExperimentSpec` (one
    :class:`CellSpec` per (scheme, k), configs derived from ``base``),
    and bucketing / padding decisions live in
    :func:`repro.core.experiment.plan`."""
    from repro.core import experiment as X
    spec = X.ExperimentSpec(
        data=X.DataSpec(model=model, device_x=device_x,
                        device_counts=device_counts, test_x=test_x,
                        test_y=test_y),
        base=base,
        cells=tuple(X.CellSpec(scheme=s, k=k) for s, k in scheme_ks),
        traces=X.TraceSpec(traces=tuple(traces)),
        seeds=X.SeedSpec(tuple(seeds)), exec_plan=exec_plan,
        target_loss=target_loss, fuse=fuse, pad_k=pad_k)
    res = X.run_experiment(spec)
    return dict(zip(tuple(scheme_ks), res.results))
