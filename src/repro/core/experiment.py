"""Declarative experiment layer: one spec -> plan -> execute pipeline.

The paper's headline results (Figs. 4-6, Tables III-V) are grids over
{scheme, k, failure pattern, seed}.  The campaign engine can sweep such
grids at hardware speed (:mod:`repro.core.campaign`), but its entry
points grew organically — six functions with 8-12 positional arguments,
and every benchmark re-implemented data prep and cell bookkeeping.
This module is the single choke point instead:

* an :class:`ExperimentSpec` *declares* a study — dataset
  (:class:`DataSpec`), a grid of (scheme, k/M) cells
  (:class:`CellSpec`), the failure conditions (:class:`TraceSpec`:
  explicit traces and/or sampled failure-rate grids), the seed
  population (:class:`SeedSpec`) and the execution policy
  (:class:`repro.core.campaign.ExecPlan`);
* :func:`plan` *lowers* the spec to an :class:`ExecutionPlan` — groups
  cells into fused iso-tracking dispatch buckets, chooses per-kind
  pad-k / pad-M, resolves per-topology trace sampling, and computes the
  shard / chunk geometry.  Planning never builds or dispatches a
  compiled executable (``campaign.TRACE_COUNT`` stays put), so plans
  are printable (:meth:`ExecutionPlan.describe`) and unit-testable for
  free;
* :func:`execute` runs the plan through the batched campaign machinery
  — one ``jit(vmap)`` dispatch per bucket — and returns an
  :class:`ExperimentResult`: per-scenario arrays keyed by (cell, trace,
  seed) with ``.summary()`` / ``.per_cell()`` / ``.to_rows()``.

The legacy entry points (``run_campaign``, ``run_multimodel_campaign``,
``sweep_grid``, ``run_fused_campaigns``,
``run_fused_multimodel_campaigns``) are thin shims over this pipeline
(bit-identical results — the shims build the same executable-cache keys
and stacked operands, pinned by ``tests/test_experiment.py``), and
``run_simulation`` is the scalar core the pipeline vmaps.  The coming
multi-host work (``jax.distributed`` process meshes) extends exactly
one seam: the bucket geometry that :func:`plan` computes.

Typical use::

    from repro.api import (CellSpec, DataSpec, ExperimentSpec, SeedSpec,
                           TraceSpec, execute, plan)

    spec = ExperimentSpec(
        data=DataSpec(model=detector, device_x=dx, device_counts=counts,
                      test_x=tx, test_y=ty),
        base=SimConfig(num_devices=10, rounds=40, lr=1e-3),
        cells=(CellSpec("tolfl", 5), CellSpec("fl", 1),
               CellSpec("ifca", 3)),
        traces=TraceSpec(traces=(NO_FAILURE, FailureSpec(20, "server")),
                         p_grid=(0.1, 0.3), traces_per_p=4),
        seeds=SeedSpec.range(3))
    p = plan(spec)            # pure; inspect p.describe() before running
    res = execute(p)          # one fused dispatch per bucket
    res.per_cell()[("tolfl", 5)].summary()["auroc_used_mean"]
"""
from __future__ import annotations

import dataclasses
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.autoencoder_paper import AutoencoderConfig
from repro.core import campaign as _c
from repro.core import compilecache
from repro.core.baselines import (FaultyMultiModelConfig, MultiModelConfig,
                                  as_multimodel_trace,
                                  prepare_multimodel_arrays)
from repro.core.campaign import (MULTI_SCHEMES, CampaignResult, ExecPlan,
                                 MultiCampaignResult)
from repro.core.failure import (Failure, FailureSpec, FailureTrace, as_trace,
                                concat_traces, sample_rate_grid,
                                stack_traces)
from repro.core.processes import ProcessGrid, sample_process_grids
from repro.core.simulate import FaultySimConfig, SimConfig, _prepare_arrays
from repro.core.topology import Topology
from repro.models.detector import (AutoencoderDetector, DetectorModel,
                                   ModelLike, as_detector)

#: single-model schemes the simulator core understands
SINGLE_SCHEMES = ("batch", "fl", "sbt", "tolfl")


# ---------------------------------------------------------------------------
# Spec dataclasses (the declarative surface)
# ---------------------------------------------------------------------------
#: fired at most once per process; tests reset it to re-pin the warning
_AE_CFG_WARNED = False


@dataclass(frozen=True, eq=False)
class DataSpec:
    """Dataset + federated partition + detector body of one experiment.

    ``model`` is the detector spec the campaign trains — any
    :class:`repro.models.detector.DetectorModel` (or a raw
    :class:`AutoencoderConfig`, which normalises to the paper's
    :class:`~repro.models.detector.AutoencoderDetector`).  ``device_x``
    is the (N, n_max, D) padded per-device tensor, ``device_counts``
    the (N,) true sample counts — exactly the arrays
    :func:`repro.data.federated.pad_devices` returns.  ``name`` is
    cosmetic (it tags :meth:`ExperimentResult.to_rows`).

    ``ae_cfg`` is the deprecated pre-detector spelling of ``model``:
    constructing with it still works (one ``DeprecationWarning`` per
    process), and reading it back returns the underlying
    :class:`AutoencoderConfig` for autoencoder specs (None otherwise)
    so legacy call sites keep functioning."""
    model: Optional[ModelLike] = None
    device_x: Optional[np.ndarray] = None
    device_counts: Optional[np.ndarray] = None
    test_x: Optional[np.ndarray] = None
    test_y: Optional[np.ndarray] = None
    name: str = ""
    ae_cfg: Optional[AutoencoderConfig] = None

    def __post_init__(self):
        global _AE_CFG_WARNED
        model = self.model
        if model is None:
            if self.ae_cfg is None:
                raise TypeError(
                    "DataSpec needs a detector spec: pass model= (a "
                    "DetectorModel or AutoencoderConfig)")
            if not _AE_CFG_WARNED:
                warnings.warn(
                    "DataSpec(ae_cfg=...) is deprecated; pass model= "
                    "(any repro.models.detector.DetectorModel — a raw "
                    "AutoencoderConfig still normalises to the paper "
                    "autoencoder)", DeprecationWarning, stacklevel=3)
                _AE_CFG_WARNED = True
            model = self.ae_cfg
        det = as_detector(model)
        object.__setattr__(self, "model", det)
        object.__setattr__(
            self, "ae_cfg",
            det.cfg if isinstance(det, AutoencoderDetector) else None)


@dataclass(frozen=True, eq=False)
class CellSpec:
    """One grid cell: a scheme plus its k (clusters) or M (models).

    ``overrides`` are (field, value) pairs applied on top of the config
    derived from the experiment's base :class:`SimConfig` — e.g.
    ``(("lr", 1e-4),)``.  ``traces`` (optional) replaces the
    experiment-level explicit trace list for THIS cell only (sampled
    grids from the :class:`TraceSpec` still apply) — the escape hatch
    for ragged grids like "batch has no client-failure column".
    ``cfg`` (optional) bypasses derivation entirely with a fully-formed
    :class:`SimConfig` / :class:`MultiModelConfig` — the legacy shims
    use it; spec authors should not need it.

    Single-model schemes (batch / fl / sbt / tolfl) read k as the
    cluster count; multi-model schemes (fedgroup / ifca / fesem) read
    it as the model count M and inherit the single-model cells' TOTAL
    local-step budget (base.rounds x base.local_epochs) so grid columns
    compare equal work."""
    scheme: str
    k: int = 1
    overrides: Tuple[Tuple[str, Any], ...] = ()
    traces: Optional[Sequence[Failure]] = None
    label: Optional[str] = None
    cfg: Optional[Union[SimConfig, MultiModelConfig]] = None

    @property
    def kind(self) -> str:
        """"single" or "multi" — which engine runs this cell."""
        scheme = (self.cfg.scheme if self.cfg is not None else self.scheme)
        if scheme in MULTI_SCHEMES:
            return "multi"
        if scheme in SINGLE_SCHEMES:
            return "single"
        raise ValueError(
            f"unknown scheme {scheme!r}: single-model schemes are "
            f"{SINGLE_SCHEMES}, multi-model baselines {MULTI_SCHEMES}")

    def resolve(self, base: SimConfig
                ) -> Union[SimConfig, MultiModelConfig]:
        """The cell's full config, derived from ``base`` (or ``cfg``)."""
        if self.cfg is not None:
            return self.cfg
        if self.kind == "multi":
            cfg: Any = MultiModelConfig(
                scheme=self.scheme, num_devices=base.num_devices,
                num_models=self.k,
                rounds=base.rounds * base.local_epochs,
                lr=base.lr, dropout=base.dropout)
        else:
            cfg = dataclasses.replace(base, scheme=self.scheme,
                                      num_clusters=self.k)
        if self.overrides:
            cfg = dataclasses.replace(cfg, **dict(self.overrides))
        return cfg

    def key(self) -> Any:
        """Result-dict key: the label if given, else (scheme, k)."""
        if self.label is not None:
            return self.label
        if self.cfg is not None:
            k = (self.cfg.num_models if self.kind == "multi"
                 else self.cfg.num_clusters)
            return (self.cfg.scheme, k)
        return (self.scheme, self.k)


def cell(scheme: str, k: int = 1, traces: Optional[Sequence[Failure]] = None,
         label: Optional[str] = None, **overrides) -> CellSpec:
    """Sugar: ``cell("tolfl", 5, lr=1e-4)`` ==
    ``CellSpec("tolfl", 5, overrides=(("lr", 1e-4),))``."""
    return CellSpec(scheme, k, tuple(sorted(overrides.items())), traces,
                    label)


@dataclass(frozen=True, eq=False)
class TraceSpec:
    """Failure conditions of an experiment.

    Three composable parts:

    * ``traces`` — explicit conditions (legacy ``FailureSpec``s or
      ``FailureTrace``s), shared by every cell (a cell may override its
      list via :attr:`CellSpec.traces`).
    * ``p_grid`` — sampled failure-rate grids: for each rate p,
      ``traces_per_p`` multi-event failure-and-recovery scenarios are
      drawn via :func:`repro.core.failure.sample_rate_grid` against
      EACH CELL'S OWN topology (a tolfl head is a plain client under
      fl; multi-model baselines sample against the FL topology, device
      0 = the aggregator).  Draws are deduplicated per cell and the
      explicit traces join the dedup as the grid's base conditions, so
      an all-none draw aliases the no-failure condition instead of
      retraining it.  ``plan()`` records the draw -> trace-index map in
      :attr:`CellPlan.draws`.

    * ``processes`` — generative failure models
      (:class:`repro.core.processes.FailureProcess`): each
      :class:`~repro.core.processes.ProcessGrid` draws ``n_samples``
      scenarios of its process against each cell's own topology,
      deduplicated into the same trace pool; ``plan()`` records
      ``{grid index: [trace index per draw]}`` in
      :attr:`CellPlan.process_draws`.  A process with
      ``needs_faulty_engine`` (e.g.
      :class:`~repro.core.processes.FaultyUpdateProcess`) switches
      every cell onto the faulty-aware engine variant.

    When ``p_grid`` or ``processes`` is non-empty the explicit entries
    are normalised to traces at the slot budget (``max_events``,
    default 2N or the largest process default — enough for every device
    to fail AND recover); a "client" ``FailureSpec`` is dropped for
    batch cells (batch centralises the data: there are no clients),
    recorded as ``None`` in :attr:`CellPlan.explicit_index`.  Without
    sampling, explicit entries pass through to the engine verbatim —
    bit-compatible with the legacy entry points.

    Reproducibility: ALL sampling (rate grids and process grids)
    derives from ``sample_seed`` alone — ``plan()`` builds fresh
    generators from it (per cell for ``p_grid``; per (process, draw)
    via :func:`repro.core.processes.process_seed`), so equal specs
    lower to bit-identical trace grids in any process or session."""
    traces: Tuple[Failure, ...] = ()
    p_grid: Tuple[float, ...] = ()
    traces_per_p: int = 4
    recover_prob: float = 0.5
    sample_seed: int = 0
    max_events: Optional[int] = None
    processes: Tuple[ProcessGrid, ...] = ()

    @staticmethod
    def explicit(*traces: Failure) -> "TraceSpec":
        return TraceSpec(traces=tuple(traces))

    @staticmethod
    def sampled(p_grid: Sequence[float], traces_per_p: int = 4,
                base: Sequence[Failure] = (), recover_prob: float = 0.5,
                sample_seed: int = 0,
                max_events: Optional[int] = None) -> "TraceSpec":
        return TraceSpec(traces=tuple(base), p_grid=tuple(p_grid),
                         traces_per_p=traces_per_p,
                         recover_prob=recover_prob,
                         sample_seed=sample_seed, max_events=max_events)

    @staticmethod
    def generated(*grids: ProcessGrid, base: Sequence[Failure] = (),
                  sample_seed: int = 0,
                  max_events: Optional[int] = None) -> "TraceSpec":
        """A spec of generative failure-process grids (plus optional
        explicit base conditions)."""
        return TraceSpec(traces=tuple(base), processes=tuple(grids),
                         sample_seed=sample_seed, max_events=max_events)


@dataclass(frozen=True)
class SeedSpec:
    """The seed population every (cell, trace) pair crosses with."""
    seeds: Tuple[int, ...] = (0,)

    @staticmethod
    def range(n: int, start: int = 0) -> "SeedSpec":
        return SeedSpec(tuple(builtins_range(start, start + n)))


builtins_range = range


@dataclass(frozen=True, eq=False)
class ExperimentSpec:
    """A whole study, declaratively: see the module docstring example.

    ``base`` seeds every cell's config derivation
    (:meth:`CellSpec.resolve`); ``fuse`` / ``pad_k`` / ``k_pad`` /
    ``m_pad`` mirror the legacy ``sweep_grid`` execution knobs (the
    defaults — fuse with per-kind max pads — are what you want; the
    per-cell paths exist for parity pinning)."""
    data: DataSpec
    base: SimConfig
    cells: Tuple[CellSpec, ...]
    traces: TraceSpec = TraceSpec()
    seeds: SeedSpec = SeedSpec()
    exec_plan: Optional[ExecPlan] = None
    target_loss: Optional[float] = None
    fuse: bool = True
    pad_k: bool = True
    k_pad: Optional[int] = None      # explicit pad-k override (all buckets)
    m_pad: Optional[int] = None      # explicit pad-M override (all buckets)


# ---------------------------------------------------------------------------
# The lowered plan
# ---------------------------------------------------------------------------
@dataclass
class CellPlan:
    """One cell, resolved: full config + its trace list and draw map."""
    index: int
    spec: CellSpec
    cfg: Union[SimConfig, MultiModelConfig]
    kind: str                       # "single" | "multi"
    traces: Sequence[Failure]       # resolved per-cell trace list
    explicit_index: Dict[int, Optional[int]]   # explicit pos -> trace idx
    draws: Dict[float, List[int]]   # rate p -> one trace idx per draw
    num_scenarios: int              # len(traces) * len(seeds)
    #: process-grid index -> one trace idx per draw (TraceSpec.processes)
    process_draws: Dict[int, List[int]] = field(default_factory=dict)

    @property
    def key(self) -> Any:
        return self.spec.key()


@dataclass
class BucketPlan:
    """One dispatch bucket: the cells that share a compiled executable
    AND (when ``fused``) a single stacked ``jit(vmap)`` dispatch."""
    index: int
    kind: str                       # "single" | "multi"
    fused: bool
    cell_indices: List[int]
    key_cfg: Union[SimConfig, MultiModelConfig]   # executable-cache key
    track_iso: bool = False         # single: the fl fallback branch
    k_pad: Optional[int] = None     # single: padded cluster-axis length
    m_pad: Optional[int] = None     # multi fused: padded model-axis length
    num_scenarios: int = 0          # flattened (cell x trace x seed) B
    chunk: int = 0                  # scenarios resident per dispatch
    num_chunks: int = 0
    padded_scenarios: int = 0       # B rounded up to chunk * num_chunks
    devices: Optional[int] = None   # shard width (None = unsharded)

    def describe(self) -> str:
        mode = ("fused" if self.fused else
                "per-cell" if (self.k_pad or self.m_pad) else "static")
        pads = []
        if self.k_pad is not None:
            pads.append(f"pad_k={self.k_pad}")
        if self.m_pad is not None:
            pads.append(f"pad_m={self.m_pad}")
        if self.track_iso:
            pads.append("iso")
        geom = f"B={self.num_scenarios}"
        if self.padded_scenarios != self.num_scenarios:
            geom += f"(pad {self.padded_scenarios})"
        geom += f" chunks={self.num_chunks}x{self.chunk}"
        if self.devices:
            geom += f" shard={self.devices}dev"
        return (f"bucket {self.index}: {self.kind} {mode} "
                f"[{' '.join(pads) or '-'}] cells={self.cell_indices} "
                f"{geom}")


@dataclass
class ExecutionPlan:
    """What :func:`execute` will run — computed without building or
    dispatching any executable, so it is printable and testable for
    free (``plan()`` unit tests assert ``campaign.TRACE_COUNT`` never
    moves)."""
    spec: ExperimentSpec
    cells: List[CellPlan]
    buckets: List[BucketPlan]
    #: cached plancheck report (populated by static_report / check=True)
    report: Optional[object] = None

    @property
    def num_scenarios(self) -> int:
        return sum(c.num_scenarios for c in self.cells)

    @property
    def num_dispatch_buckets(self) -> int:
        return len(self.buckets)

    def cell(self, key) -> CellPlan:
        for c in self.cells:
            if c.key == key:
                return c
        raise KeyError(key)

    def static_report(self, budgets: bool = True):
        """Run the plan-time static analyzer (plancheck pass 1) over
        every bucket and cache the :class:`~repro.analysis.plancheck.
        findings.Report`.  Lowers (traces) each bucket exactly like a
        first compile would — zero execution, and the trace is shared
        with a later :func:`execute` of the same plan."""
        if self.report is None:
            from repro.analysis.plancheck import Report, check_plan
            self.report = Report(findings=check_plan(
                self, budgets=budgets))
        return self.report

    def describe(self) -> str:
        seeds = self.spec.seeds.seeds
        lines = [f"ExperimentPlan: {len(self.cells)} cells x "
                 f"{len(seeds)} seeds -> {self.num_scenarios} scenarios "
                 f"in {len(self.buckets)} dispatch buckets"]
        for c in self.cells:
            lines.append(f"  cell {c.index} {c.key}: {len(c.traces)} "
                         f"traces, {c.num_scenarios} scenarios")
        lines.extend("  " + b.describe() for b in self.buckets)
        if self.report is not None:
            status = ("clean" if self.report.clean else
                      f"{len(self.report.findings)} finding(s)")
            lines.append(f"  static analysis: {status}")
            lines.extend("    " + f.describe().replace("\n", "\n    ")
                         for f in self.report.findings)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# plan(): spec -> ExecutionPlan
# ---------------------------------------------------------------------------
def _resolve_cell_traces(spec: ExperimentSpec, cspec: CellSpec,
                         cfg, kind: str, shared_explicit: Sequence[Failure]):
    """(traces, explicit_index, draws, process_draws) of one cell per
    the TraceSpec."""
    ts = spec.traces
    explicit = (list(cspec.traces) if cspec.traces is not None
                else shared_explicit)
    if not ts.p_grid and not ts.processes:
        # verbatim pass-through: no normalisation, no dedup — the
        # legacy-compatible path every shim rides
        return explicit, {j: j for j in range(len(explicit))}, {}, {}

    if kind == "single":
        topo = cfg.topology()
        n = topo.num_devices
        rounds = cfg.rounds
    else:
        # baselines have no cluster heads: sample against the FL
        # topology (device 0 = the aggregator -> server events)
        topo = Topology(cfg.num_devices, 1)
        n = cfg.num_devices
        rounds = cfg.rounds
    # one cell-wide slot budget so every trace in the pool stacks: the
    # rate-grid default (2N) or the largest process default, whichever
    # is bigger (markov churn wants 4N for repeated outages)
    max_events = ts.max_events or max(
        [2 * n] + [pg.process.default_max_events(topo)
                   for pg in ts.processes])

    base_traces: List[FailureTrace] = []
    explicit_index: Dict[int, Optional[int]] = {}
    for j, f in enumerate(explicit):
        if (kind == "single" and cfg.scheme == "batch"
                and isinstance(f, FailureSpec) and f.kind == "client"):
            # batch centralises the data: there are no clients to fail
            explicit_index[j] = None
            continue
        if kind == "multi":
            t = as_multimodel_trace(f, n, max_events)
        else:
            t = as_trace(f, topo, max_events)
        explicit_index[j] = len(base_traces)
        base_traces.append(t)

    rng = np.random.default_rng(ts.sample_seed)
    traces, draws = sample_rate_grid(rng, topo, ts.p_grid, rounds,
                                     ts.traces_per_p,
                                     max_events=max_events,
                                     recover_prob=ts.recover_prob,
                                     base_traces=base_traces)
    process_draws = sample_process_grids(ts.processes, topo, rounds,
                                         ts.sample_seed, max_events,
                                         traces)
    return traces, explicit_index, draws, process_draws


def _faulty_variant(cfg):
    """The faulty-update engine twin of a resolved cell config
    (idempotent).  Subclass swap, not a field: plain-config reprs —
    and therefore every existing executable-cache key and persisted
    fingerprint — stay bit-identical, while the faulty cores key and
    bucket separately by class identity (``dataclasses.replace`` in
    the bucket grouping below preserves the subclass)."""
    if isinstance(cfg, (FaultySimConfig, FaultyMultiModelConfig)):
        return cfg
    cls = (FaultyMultiModelConfig if isinstance(cfg, MultiModelConfig)
           else FaultySimConfig)
    return cls(**{f.name: getattr(cfg, f.name)
                  for f in dataclasses.fields(cfg)})


def _geometry(bucket: BucketPlan, exec_plan: Optional[ExecPlan]) -> None:
    """Fill the bucket's shard / chunk geometry (mirrors
    ``campaign._run_batched`` arithmetic)."""
    plan_ = exec_plan or ExecPlan()
    B = bucket.num_scenarios
    chunk = min(plan_.chunk_size or B, B)
    # planning never warns: the shard-degradation warning fires exactly
    # once per execute() (the old bucket.index == 0 gate fired per
    # plan() call — zero times for entry points that bypassed plan(),
    # twice for plan()+execute())
    ndev = plan_.resolved_devices(warn=False)
    if ndev:
        chunk = -(-chunk // ndev) * ndev
    bucket.devices = ndev
    bucket.chunk = chunk
    bucket.num_chunks = -(-B // chunk)
    bucket.padded_scenarios = bucket.num_chunks * chunk


def plan(spec: ExperimentSpec, check: bool = False) -> ExecutionPlan:
    """Lower a spec to dispatch buckets — pure host-side work, inside
    the span ``campaign.plan``.

    Raises ``ValueError`` up front for empty grids, unknown schemes and
    invalid :class:`ExecPlan` values (the legacy paths failed deep
    inside ``_run_batched``).

    ``check=True`` additionally runs the plan-time static analyzer
    (:mod:`repro.analysis.plancheck`) over every bucket — this traces
    each bucket's executable (no execution; the trace is shared with a
    later compile) and attaches the report, which ``describe()`` then
    renders as a per-bucket static-analysis section."""
    with obs.span("campaign.plan"):
        return _plan(spec, check)


def _plan(spec: ExperimentSpec, check: bool) -> ExecutionPlan:
    if not spec.cells:
        raise ValueError("empty experiment: need >= 1 cell")
    if len(spec.seeds.seeds) == 0:
        raise ValueError("empty campaign: need >=1 trace and >=1 seed")

    shared_explicit = list(spec.traces.traces)
    needs_faulty = any(pg.process.needs_faulty_engine
                       for pg in spec.traces.processes)
    cells: List[CellPlan] = []
    for i, cspec in enumerate(spec.cells):
        kind = cspec.kind            # validates the scheme
        cfg = cspec.resolve(spec.base)
        if needs_faulty:
            cfg = _faulty_variant(cfg)
        traces, explicit_index, draws, process_draws = _resolve_cell_traces(
            spec, cspec, cfg, kind, shared_explicit)
        if len(traces) == 0:
            raise ValueError("empty campaign: need >=1 trace and "
                             ">=1 seed")
        cells.append(CellPlan(
            index=i, spec=cspec, cfg=cfg, kind=kind, traces=traces,
            explicit_index=explicit_index, draws=draws,
            num_scenarios=len(traces) * len(spec.seeds.seeds),
            process_draws=process_draws))

    buckets: List[BucketPlan] = []
    fused_mode = spec.fuse and spec.pad_k

    def add(bucket: BucketPlan) -> None:
        bucket.index = len(buckets)
        bucket.num_scenarios = sum(cells[i].num_scenarios
                                   for i in bucket.cell_indices)
        _geometry(bucket, spec.exec_plan)
        buckets.append(bucket)

    singles = [c for c in cells
               if c.kind == "single" and c.cfg.scheme != "batch"]
    multis = [c for c in cells if c.kind == "multi"]
    batches = [c for c in cells
               if c.kind == "single" and c.cfg.scheme == "batch"]

    if fused_mode:
        groups: Dict[Tuple[SimConfig, bool], List[int]] = {}
        for c in singles:
            key_cfg = dataclasses.replace(c.cfg, seed=0, scheme="tolfl",
                                          num_clusters=1)
            groups.setdefault((key_cfg, c.cfg.scheme == "fl"),
                              []).append(c.index)
        for (key_cfg, track_iso), idxs in groups.items():
            kp = spec.k_pad or max(
                cells[i].cfg.topology().num_clusters for i in idxs)
            add(BucketPlan(index=0, kind="single", fused=True,
                           cell_indices=idxs, key_cfg=key_cfg,
                           track_iso=track_iso, k_pad=kp))
        mgroups: Dict[MultiModelConfig, List[int]] = {}
        for c in multis:
            key_cfg = dataclasses.replace(c.cfg, seed=0, num_models=0)
            mgroups.setdefault(key_cfg, []).append(c.index)
        for key_cfg, idxs in mgroups.items():
            mp = spec.m_pad or max(cells[i].cfg.num_models for i in idxs)
            add(BucketPlan(index=0, kind="multi", fused=True,
                           cell_indices=idxs, key_cfg=key_cfg, m_pad=mp))
    else:
        # per-cell dispatch: pad cluster arrays to the PER-KIND max k
        # (each iso-tracking kind owns its executable either way)
        k_kind: Dict[bool, int] = {}
        if spec.pad_k:
            for c in singles:
                kind_key = (c.cfg.scheme == "fl")
                k_kind[kind_key] = max(k_kind.get(kind_key, 1),
                                       c.cfg.topology().num_clusters)
        for c in singles:
            kp = (spec.k_pad or k_kind.get(c.cfg.scheme == "fl")
                  if spec.pad_k else None)
            if kp is None:
                key_cfg = dataclasses.replace(c.cfg, seed=0)
            else:
                key_cfg = dataclasses.replace(c.cfg, seed=0,
                                              scheme="tolfl",
                                              num_clusters=1)
            add(BucketPlan(index=0, kind="single", fused=False,
                           cell_indices=[c.index], key_cfg=key_cfg,
                           track_iso=(c.cfg.scheme == "fl"), k_pad=kp))
        for c in multis:
            add(BucketPlan(index=0, kind="multi", fused=False,
                           cell_indices=[c.index],
                           key_cfg=dataclasses.replace(c.cfg, seed=0)))
    # "batch" cells centralise the data onto one device, so their array
    # SHAPES differ from every other cell: they can neither fuse nor
    # share the padded executable (whose cache key carries the
    # uncentralised device count) — k_pad is deliberately ignored and
    # each batch cell is its own static single-cell dispatch
    for c in batches:
        add(BucketPlan(index=0, kind="single", fused=False,
                       cell_indices=[c.index],
                       key_cfg=dataclasses.replace(c.cfg, seed=0)))

    out = ExecutionPlan(spec=spec, cells=cells, buckets=buckets)
    if check:
        out.static_report()
    return out


# ---------------------------------------------------------------------------
# execute(): ExecutionPlan -> ExperimentResult
# ---------------------------------------------------------------------------
@dataclass
class BucketCompileStats:
    """Compile/dispatch accounting of one bucket of an executed plan.

    ``lower_s`` / ``compile_s`` are wall times of the AOT lowering and
    XLA compile (0 on the jit path and on in-process AOT cache hits);
    ``execute_s`` is the bucket's whole host time, and the five stage
    times that sum to it (each from its ``campaign.<stage>`` span,
    :mod:`repro.obs`): ``build_s`` (host arrays, trace stacking,
    padding and the host-to-device transfers), ``resolve_s`` (waiting
    on the plan-time compile and resolving the executable),
    ``dispatch_s`` (the executable calls and chunk slicing),
    ``fetch_s`` (waiting for the device and copying the outputs back)
    and ``post_s`` (AUROC post-processing and result slicing).
    ``cache`` is
    ``""`` (jit path), ``"memory"`` (in-process AOT cache already held
    the executable), ``"disk"`` (deserialised whole from the persistent
    executable cache — no trace, no XLA; ``compile_s`` is the load
    time) or ``"compiled"`` (lower+compile ran this execute).
    ``aval_match``
    records whether the plan-time shape prediction matched the concrete
    arguments (a mismatch falls back to a synchronous compile — correct
    but un-overlapped; pinned True by ``tests/test_aot.py``)."""
    bucket: int
    kind: str
    fused: bool
    aot: bool
    lower_s: float = 0.0
    compile_s: float = 0.0
    execute_s: float = 0.0
    build_s: float = 0.0
    resolve_s: float = 0.0
    dispatch_s: float = 0.0
    fetch_s: float = 0.0
    post_s: float = 0.0
    cache: str = ""
    aval_match: Optional[bool] = None


@dataclass
class CompileReport:
    """Where one ``execute()`` spent its compile budget.

    ``traces`` is the ``campaign.TRACE_COUNT`` delta across the whole
    execute (0 on a warm process); ``xla`` is the
    :func:`repro.core.compilecache.xla_compile_stats` delta —
    ``xla['misses']`` counts ACTUAL XLA compiles, so a warm-disk re-run
    of a spec in a fresh process shows ``misses == 0`` with
    ``hits > 0``.  ``cache_dir`` is the persistent cache directory in
    use (None when disabled)."""
    aot: bool
    buckets: List[BucketCompileStats]
    traces: int = 0
    xla: Dict[str, int] = field(default_factory=dict)
    cache_dir: Optional[str] = None

    @property
    def lower_s(self) -> float:
        return sum(b.lower_s for b in self.buckets)

    @property
    def compile_s(self) -> float:
        return sum(b.compile_s for b in self.buckets)

    @property
    def execute_s(self) -> float:
        return sum(b.execute_s for b in self.buckets)

    def describe(self) -> str:
        lines = [f"CompileReport: aot={self.aot} traces={self.traces} "
                 f"xla={self.xla or '{}'} cache_dir={self.cache_dir}"]
        for b in self.buckets:
            lines.append(
                f"  bucket {b.bucket} ({b.kind}"
                f"{' fused' if b.fused else ''}): "
                f"lower={b.lower_s:.3f}s compile={b.compile_s:.3f}s "
                f"execute={b.execute_s:.3f}s (build={b.build_s:.3f}s "
                f"resolve={b.resolve_s:.3f}s dispatch={b.dispatch_s:.3f}s "
                f"fetch={b.fetch_s:.3f}s post={b.post_s:.3f}s)"
                + (f" cache={b.cache}" if b.cache else "")
                + (f" aval_match={b.aval_match}"
                   if b.aval_match is not None else ""))
        return "\n".join(lines)


def _bucket_exe_args(data: DataSpec, bucket: BucketPlan) -> tuple:
    """``(kind, model, cfg, k_pad, ndev, track_iso, fused)`` — the
    executable-cache key parts the bucket's ``_exec_*`` helper will
    resolve (fused multi buckets compile at the PADDED model count)."""
    if bucket.kind == "multi":
        cfg = (dataclasses.replace(bucket.key_cfg,
                                   num_models=bucket.m_pad)
               if bucket.fused else bucket.key_cfg)
        return ("multi", data.model, cfg, None, bucket.devices, False,
                bucket.fused)
    return ("single", data.model, bucket.key_cfg, bucket.k_pad,
            bucket.devices, bucket.track_iso, bucket.fused)


def _bucket_avals(data: DataSpec, bucket: BucketPlan,
                  cells: Sequence[CellPlan]) -> tuple:
    """Predicted abstract arguments of one bucket's batched call — the
    exact ``ShapeDtypeStruct`` tuple ``_run_batched`` will derive from
    the concrete arrays, computed from the plan alone so AOT compiles
    can start BEFORE the host builds data/trace arrays.  Mirrors the
    ``_exec_*`` arg builders: ``_prepare_arrays`` shapes (batch cells
    centralise onto one device), per-chunk mapped operands at
    ``bucket.chunk``, trace leaves from one normalised representative
    trace (``stack_traces``/``concat_traces`` assert the batch is
    uniform, so cell 0's first trace is authoritative)."""
    sds = jax.ShapeDtypeStruct
    canon = jax.dtypes.canonicalize_dtype
    f32, i32 = canon(np.float32), canon(np.int32)
    c0 = cells[0]
    cfg0 = c0.cfg
    chunk = bucket.chunk
    dxa = np.asarray(data.device_x)
    if bucket.kind == "single" and cfg0.scheme == "batch":
        n_dev, n_max = 1, int(np.sum(np.asarray(data.device_counts)))
    else:
        n_dev, n_max = int(dxa.shape[0]), int(dxa.shape[1])
    txa = np.asarray(data.test_x)
    bcast = (sds((n_dev, n_max, int(dxa.shape[2])), canon(dxa.dtype)),
             sds((n_dev,), f32),
             sds((n_dev, n_max), f32),
             sds(tuple(txa.shape), canon(txa.dtype)))

    if bucket.kind == "multi":
        t0 = as_multimodel_trace(c0.traces[0], cfg0.num_devices)
    else:
        t0 = as_trace(c0.traces[0], cfg0.topology())
    traces = jax.tree.map(
        lambda x: sds((chunk,) + tuple(x.shape), canon(x.dtype)), t0)
    seeds = sds((chunk,), i32)

    if bucket.kind == "multi":
        if bucket.fused:
            return bcast + (sds((chunk, bucket.m_pad), f32), traces,
                            seeds)
        return bcast + (sds((cfg0.num_models,), f32), traces, seeds)
    if bucket.fused:
        kp = bucket.k_pad
        return bcast + (sds((chunk, n_dev), i32), sds((chunk, kp), i32),
                        sds((chunk, kp), f32), traces, seeds)
    if bucket.k_pad is not None:
        kp = bucket.k_pad
        bcast = bcast + (sds((n_dev,), i32), sds((kp,), i32),
                        sds((kp,), f32))
    return bcast + (traces, seeds)


@dataclass
class ExperimentResult:
    """Per-cell campaign results of one executed plan, in cell order.

    ``results[i]`` is the :class:`CampaignResult` /
    :class:`MultiCampaignResult` of ``plan.cells[i]`` — every scenario
    keyed by (cell, trace index, seed).  ``compile_report`` accounts
    for where the execute spent its compile budget
    (:class:`CompileReport`)."""
    plan: ExecutionPlan
    results: List[Union[CampaignResult, MultiCampaignResult]]
    compile_report: Optional[CompileReport] = None

    @property
    def num_scenarios(self) -> int:
        return sum(r.num_scenarios for r in self.results)

    def per_cell(self) -> Dict[Any, Union[CampaignResult,
                                          MultiCampaignResult]]:
        """{cell key: result} — keys from :meth:`CellSpec.key`."""
        return {c.key: r for c, r in zip(self.plan.cells, self.results)}

    def __getitem__(self, key):
        return self.per_cell()[key]

    def summary(self) -> Dict[Any, Dict[str, float]]:
        """{cell key: that cell's summary dict} (see the result types).

        Cells planned from ``TraceSpec.processes`` additionally carry
        per-process-family keys ``E[auroc] <family>[<grid idx>]`` (the
        Monte-Carlo mean over that grid's draws x seeds); process-free
        cells are untouched."""
        out = {}
        for c, r in zip(self.plan.cells, self.results):
            s = dict(r.summary())
            if c.process_draws:
                procs = self.plan.spec.traces.processes
                for gi, aurocs in self._cell_process(c, r).items():
                    fam = procs[gi].process.family
                    s[f"E[auroc] {fam}[{gi}]"] = float(np.mean(aurocs))
            out[c.key] = s
        return out

    @staticmethod
    def _cell_process(c: CellPlan, r) -> Dict[int, np.ndarray]:
        """{grid index: per-(draw x seed) AUROCs} of one cell."""
        sel = (r.select if isinstance(r, CampaignResult)
               else (lambda i: r.select(i, "best")))
        return {gi: np.concatenate([np.asarray(sel(i)) for i in idxs])
                for gi, idxs in c.process_draws.items()}

    def per_process(self) -> Dict[Any, Dict[int, np.ndarray]]:
        """{cell key: {process-grid index: AUROC per draw x seed}} —
        the per-process-family axis of the result.  Duplicated draws
        repeat their deduplicated trace's values, so means equal the
        undeduplicated Monte-Carlo estimate (same contract as
        ``CellPlan.draws``).  Multi-model cells report their "best"
        (starred) AUROC."""
        return {c.key: self._cell_process(c, r)
                for c, r in zip(self.plan.cells, self.results)
                if c.process_draws}

    def process_summary(self) -> Dict[Any, Dict[str, float]]:
        """{cell key: {"<family>[<grid idx>]": E[AUROC]}} — the
        flattened per-family expected-performance table."""
        procs = self.plan.spec.traces.processes
        return {key: {f"{procs[gi].process.family}[{gi}]":
                      float(np.mean(aurocs))
                      for gi, aurocs in cell.items()}
                for key, cell in self.per_process().items()}

    def to_rows(self) -> List[Dict[str, Any]]:
        """One tidy dict per scenario — the benches' CSV fodder."""
        rows: List[Dict[str, Any]] = []
        name = self.plan.spec.data.name
        for c, r in zip(self.plan.cells, self.results):
            for b in builtins_range(r.num_scenarios):
                row: Dict[str, Any] = {
                    "dataset": name, "cell": c.key,
                    "scheme": r.cfg.scheme,
                    "trace": int(r.trace_index[b]),
                    "seed": int(r.seed[b]),
                }
                if isinstance(r, CampaignResult):
                    row.update(k=r.cfg.num_clusters,
                               auroc=float(r.auroc_used[b]),
                               final_auroc=float(r.final_auroc[b]),
                               iso_active=bool(r.iso_active[b]),
                               rounds_to_loss=float(r.rounds_to_loss[b]))
                else:
                    row.update(k=r.cfg.num_models,
                               auroc=float(r.best_auroc[b]),
                               multi_auroc=float(r.multi_auroc[b]))
                rows.append(row)
        return rows


def _exec_single_cell(data: DataSpec, cfg: SimConfig,
                      traces: Sequence[Failure], seeds: Sequence[int],
                      target_loss, exec_plan, pad_k: Optional[int],
                      aot_resolve=None) -> CampaignResult:
    """One single-model cell, unfused (the legacy ``run_campaign``
    body): topology closed over statically (``pad_k=None``) or entering
    as broadcast padded arrays (``pad_k=int``)."""
    topo = cfg.topology()
    with obs.span("campaign.build"):
        norm = [as_trace(t, topo) for t in traces]
        trace_idx, seed_arr = _c._scenario_grid(len(norm), seeds)
        if len(trace_idx) == 0:
            raise ValueError("empty campaign: need >=1 trace and >=1 seed")
        stacked = stack_traces(norm)
        batch_traces = jax.tree.map(lambda x: x[trace_idx], stacked)

        dx, counts, valid = _prepare_arrays(cfg, data.device_x,
                                            data.device_counts)
        tx = obs.to_device(data.test_x)
        assert dx.shape[0] == topo.num_devices, (dx.shape,
                                                 topo.num_devices)

        track_iso = (cfg.scheme == "fl")
        if pad_k is None:
            key_cfg = dataclasses.replace(cfg, seed=0)
            bcast = (dx, counts, valid, tx)
        else:
            # scheme / num_clusters are normalised OUT of the cache key:
            # the padded core reads the topology from the arrays, so
            # every single-model sweep cell of the same track_iso kind
            # resolves to the same executable
            key_cfg = dataclasses.replace(cfg, seed=0, scheme="tolfl",
                                          num_clusters=1)
            bcast = (dx, counts, valid, tx) + _c._padded_topology_arrays(
                topo, pad_k)
        mapped = (batch_traces, obs.to_device(seed_arr))
    with obs.span("campaign.resolve"):
        ndev = exec_plan.resolved_devices(warn=False) if exec_plan else None
        batched = _c._executable("single", data.model, key_cfg, pad_k,
                                 ndev, track_iso)
    out = _c._run_batched(batched, bcast, mapped, exec_plan,
                          aot_resolve=aot_resolve)
    with obs.span("campaign.post"):
        return _c._post_process(cfg, out, trace_idx, seed_arr,
                                data.test_y, target_loss)


def _exec_multi_cell(data: DataSpec, cfg: MultiModelConfig,
                     traces: Sequence[Failure], seeds: Sequence[int],
                     exec_plan, aot_resolve=None) -> MultiCampaignResult:
    """One multi-model cell, unfused (the legacy
    ``run_multimodel_campaign`` body)."""
    with obs.span("campaign.build"):
        norm = [as_multimodel_trace(t, cfg.num_devices) for t in traces]
        trace_idx, seed_arr = _c._scenario_grid(len(norm), seeds)
        if len(trace_idx) == 0:
            raise ValueError("empty campaign: need >=1 trace and >=1 seed")
        stacked = stack_traces(norm)
        batch_traces = jax.tree.map(lambda x: x[trace_idx], stacked)

        dx, counts, valid = prepare_multimodel_arrays(data.device_x,
                                                      data.device_counts)
        tx = obs.to_device(data.test_x)
        assert dx.shape[0] == cfg.num_devices, (dx.shape, cfg.num_devices)
        model_valid = jnp.ones((cfg.num_models,), jnp.float32)
        mapped = (batch_traces, obs.to_device(seed_arr))
    with obs.span("campaign.resolve"):
        key_cfg = dataclasses.replace(cfg, seed=0)
        ndev = exec_plan.resolved_devices(warn=False) if exec_plan else None
        batched = _c._executable("multi", data.model, key_cfg, None, ndev)
    out = _c._run_batched(batched, (dx, counts, valid, tx, model_valid),
                          mapped, exec_plan, aot_resolve=aot_resolve)

    with obs.span("campaign.post"):
        best, multi = _c._multi_metrics(np.asarray(out.final_scores),
                                        data.test_y)
        return MultiCampaignResult(
            cfg=cfg, trace_index=trace_idx, seed=seed_arr, best_auroc=best,
            multi_auroc=multi, loss_curves=np.asarray(out.losses),
            assignments=np.asarray(out.assignments))


def _stacked_scenarios(cells, seeds, trace_cache, trace_key_fn, norm_fn):
    """Per-cell (stacked traces, trace_idx, seed_arr, b) with the
    stacked batch shared between cells that pass the same trace list
    (one stacking per distinct resolution, not per cell)."""
    metas = []
    for cfg, traces in cells:
        ck = trace_key_fn(cfg, traces)
        if ck not in trace_cache:
            norm = norm_fn(cfg, traces)
            trace_idx, seed_arr = _c._scenario_grid(len(norm), seeds)
            if len(trace_idx) == 0:
                raise ValueError("empty campaign: need >=1 trace and "
                                 ">=1 seed")
            stacked = stack_traces(norm)
            trace_cache[ck] = (
                jax.tree.map(lambda x: x[trace_idx], stacked),
                trace_idx, seed_arr)
        batch_traces, trace_idx, seed_arr = trace_cache[ck]
        metas.append((cfg, batch_traces, trace_idx, seed_arr,
                      len(seed_arr)))
    return metas


def _exec_fused_single_group(data: DataSpec, cells, seeds, target_loss,
                             exec_plan, kp: int, key_cfg,
                             track_iso: bool, trace_cache,
                             aot_resolve=None) -> List[CampaignResult]:
    """One fused single-model bucket (the legacy ``run_fused_campaigns``
    group body): every cell's padded cluster arrays stacked as VMAPPED
    operands along the flattened (cell x trace x seed) axis — ONE
    dispatch for the whole bucket."""
    def trace_key(cfg, traces):
        return (tuple(id(t) for t in traces),
                _c._single_trace_key(traces, cfg.topology()))

    def norm(cfg, traces):
        return [as_trace(t, cfg.topology()) for t in traces]

    with obs.span("campaign.build"):
        dx, counts, valid = _prepare_arrays(cells[0][0], data.device_x,
                                            data.device_counts)
        tx = obs.to_device(data.test_x)
        metas = _stacked_scenarios(cells, seeds, trace_cache, trace_key,
                                   norm)
        cids_l, heads_l, hv_l, tr_l, seeds_l = [], [], [], [], []
        for cfg, batch_traces, trace_idx, seed_arr, b in metas:
            topo = cfg.topology()
            assert dx.shape[0] == topo.num_devices, (dx.shape,
                                                     topo.num_devices)
            cids, heads, hvalid = _c._padded_topology_arrays(topo, kp)
            cids_l.append(jnp.broadcast_to(cids, (b,) + cids.shape))
            heads_l.append(jnp.broadcast_to(heads, (b,) + heads.shape))
            hv_l.append(jnp.broadcast_to(hvalid, (b,) + hvalid.shape))
            tr_l.append(batch_traces)
            seeds_l.append(seed_arr)

        mapped = (jnp.concatenate(cids_l), jnp.concatenate(heads_l),
                  jnp.concatenate(hv_l), concat_traces(tr_l),
                  obs.to_device(np.concatenate(seeds_l)))
    with obs.span("campaign.resolve"):
        ndev = exec_plan.resolved_devices(warn=False) if exec_plan else None
        batched = _c._executable("single", data.model, key_cfg, kp, ndev,
                                 track_iso, fused=True)
    out = _c._run_batched(batched, (dx, counts, valid, tx), mapped,
                          exec_plan, aot_resolve=aot_resolve)
    with obs.span("campaign.post"):
        fields = _c._post_process_arrays(track_iso, out, data.test_y,
                                         target_loss)
        results, off = [], 0
        for cfg, _, trace_idx, seed_arr, b in metas:
            cell_fields = {name: arr[off:off + b]
                           for name, arr in fields.items()}
            results.append(CampaignResult(cfg=cfg, trace_index=trace_idx,
                                          seed=seed_arr, **cell_fields))
            off += b
        return results


def _exec_fused_multi_group(data: DataSpec, cells, seeds, exec_plan,
                            mp: int, key_cfg, trace_cache,
                            aot_resolve=None) -> List[MultiCampaignResult]:
    """One fused multi-model bucket (the legacy
    ``run_fused_multimodel_campaigns`` group body): cells with
    DIFFERENT model counts share one executable via the padded-M
    ``model_valid`` mask."""
    def trace_key(cfg, traces):
        return (tuple(id(t) for t in traces), cfg.num_devices)

    def norm(cfg, traces):
        return [as_multimodel_trace(t, cfg.num_devices) for t in traces]

    with obs.span("campaign.build"):
        dx, counts, valid = prepare_multimodel_arrays(data.device_x,
                                                      data.device_counts)
        tx = obs.to_device(data.test_x)
        metas = _stacked_scenarios(cells, seeds, trace_cache, trace_key,
                                   norm)
        mv_l, tr_l, seeds_l = [], [], []
        for cfg, batch_traces, trace_idx, seed_arr, b in metas:
            assert dx.shape[0] == cfg.num_devices, (dx.shape,
                                                    cfg.num_devices)
            assert mp >= cfg.num_models, (mp, cfg.num_models)
            mv = np.zeros((mp,), np.float32)
            mv[:cfg.num_models] = 1.0
            mv_l.append(jnp.broadcast_to(obs.to_device(mv), (b, mp)))
            tr_l.append(batch_traces)
            seeds_l.append(seed_arr)

        mapped = (jnp.concatenate(mv_l), concat_traces(tr_l),
                  obs.to_device(np.concatenate(seeds_l)))
    with obs.span("campaign.resolve"):
        ndev = exec_plan.resolved_devices(warn=False) if exec_plan else None
        exe_cfg = dataclasses.replace(key_cfg, num_models=mp)
        batched = _c._executable("multi", data.model, exe_cfg, None, ndev,
                                 fused=True)
    out = _c._run_batched(batched, (dx, counts, valid, tx), mapped,
                          exec_plan, aot_resolve=aot_resolve)
    with obs.span("campaign.post"):
        model_valid = np.asarray(mapped[0])
        best, multi = _c._multi_metrics(np.asarray(out.final_scores),
                                        data.test_y, model_valid)
        losses = np.asarray(out.losses)
        assigns = np.asarray(out.assignments)
        results, off = [], 0
        for cfg, _, trace_idx, seed_arr, b in metas:
            sl = slice(off, off + b)
            results.append(MultiCampaignResult(
                cfg=cfg, trace_index=trace_idx, seed=seed_arr,
                best_auroc=best[sl], multi_auroc=multi[sl],
                loss_curves=losses[sl], assignments=assigns[sl]))
            off += b
        return results


def _make_resolver(stats: BucketCompileStats, key_args: tuple, future):
    """Per-bucket AOT executable resolver ``_run_batched`` calls with
    the concrete abstract-argument tuple.  Waits for the speculative
    plan-time compile (``future``), then resolves through the AOT cache:
    a hit means the prediction matched (the usual case — the compile
    overlapped the host-side array builds); a miss means the prediction
    drifted from the real shapes and we compile synchronously — slower
    but still bit-identical."""
    def resolve(avals):
        spec_times = None
        if future is not None:
            _, spec_times = future.result()
        compiled, times = _c.aot_executable(*key_args, avals)
        stats.aval_match = bool(times.cached and spec_times is not None)
        if times.cached and spec_times is not None:
            # the speculative compile did the work; report ITS times
            # and source (compiled / disk) rather than the memory hit
            times = spec_times
        stats.lower_s, stats.compile_s = times.lower_s, times.compile_s
        stats.cache = times.source
        return compiled
    return resolve


def _backend() -> str:
    """The platform the bucket cores are lowered for."""
    return jax.default_backend()


def _fuses_output_layer(model: ModelLike) -> bool:
    """Whether a bucket core of ``model`` runs the fused output-layer
    kernel here: the model takes it, and the cores are lowered for a
    TPU."""
    return as_detector(model).fuses_output_layer() and _backend() == "tpu"


def execute(plan_: ExecutionPlan) -> ExperimentResult:
    """Run every bucket of a lowered plan; results align with
    ``plan_.cells`` (and with the spec's cell order).

    Wires the persistent XLA disk cache
    (:func:`repro.core.compilecache.ensure_persistent_cache`) and, when
    the spec's :class:`ExecPlan` sets ``aot=True``, launches every
    bucket's lower+compile on a thread pool BEFORE dispatching —
    ``plan()`` already knows each bucket's shapes
    (:func:`_bucket_avals`), so XLA overlaps the host-side data/trace
    array builds and the buckets dispatch through compiled executables.
    Either path attaches a :class:`CompileReport` and appends one record
    to :func:`repro.obs.executions`, which counts the buckets that run
    the fused output-layer kernel (``fused_out_buckets``)."""
    spec = plan_.spec
    data, seeds = spec.data, spec.seeds.seeds
    exec_plan, target_loss = spec.exec_plan, spec.target_loss
    compilecache.ensure_persistent_cache()
    # exactly ONE shard-degradation warning per execute(), whatever the
    # entry point: plan() and every helper pass warn=False
    if exec_plan is not None:
        exec_plan.resolved_devices(warn=True)

    use_aot = bool(exec_plan is not None and exec_plan.aot)
    stats = [BucketCompileStats(bucket=b.index, kind=b.kind,
                                fused=b.fused, aot=use_aot)
             for b in plan_.buckets]
    traces0 = _c.TRACE_COUNT
    xla0 = compilecache.xla_compile_stats()
    fused_out = float(_fuses_output_layer(data.model))

    pool = futures = None
    if use_aot:
        pool = ThreadPoolExecutor(
            max_workers=min(4, len(plan_.buckets)),
            thread_name_prefix="aot-compile")
        futures = {
            b.index: pool.submit(
                _c.aot_executable, *_bucket_exe_args(data, b),
                _bucket_avals(data, b,
                              [plan_.cells[i] for i in b.cell_indices]))
            for b in plan_.buckets}

    results: List[Optional[Any]] = [None] * len(plan_.cells)
    trace_cache: dict = {}   # one stacked batch per distinct resolution
    try:
        with obs.Tally() as whole:
            for bucket in plan_.buckets:
                cells = [plan_.cells[i] for i in bucket.cell_indices]
                pairs = [(c.cfg, c.traces) for c in cells]
                st = stats[bucket.index]
                resolve = (_make_resolver(st, _bucket_exe_args(data, bucket),
                                          futures[bucket.index])
                           if use_aot else None)
                with obs.Tally() as tally:
                    obs.count(obs.FUSED_OUT_BUCKETS, fused_out)
                    if bucket.kind == "single" and bucket.fused:
                        rs = _exec_fused_single_group(
                            data, pairs, seeds, target_loss, exec_plan,
                            bucket.k_pad, bucket.key_cfg, bucket.track_iso,
                            trace_cache, aot_resolve=resolve)
                    elif bucket.kind == "multi" and bucket.fused:
                        rs = _exec_fused_multi_group(
                            data, pairs, seeds, exec_plan, bucket.m_pad,
                            bucket.key_cfg, trace_cache, aot_resolve=resolve)
                    elif bucket.kind == "single":
                        rs = [_exec_single_cell(data, cells[0].cfg,
                                                cells[0].traces, seeds,
                                                target_loss, exec_plan,
                                                bucket.k_pad,
                                                aot_resolve=resolve)]
                    else:
                        rs = [_exec_multi_cell(data, cells[0].cfg,
                                               cells[0].traces, seeds,
                                               exec_plan,
                                               aot_resolve=resolve)]
                st.execute_s = tally.end - tally.start
                for name in obs.CAMPAIGN_STAGES:
                    setattr(st, name.split(".", 1)[1] + "_s",
                            tally.seconds[name])
                for c, r in zip(cells, rs):
                    results[c.index] = r
    finally:
        if pool is not None:
            pool.shutdown(wait=True)

    xla1 = compilecache.xla_compile_stats()
    report = CompileReport(
        aot=use_aot, buckets=stats, traces=_c.TRACE_COUNT - traces0,
        xla={k: xla1[k] - xla0[k] for k in xla1},
        cache_dir=compilecache.persistent_cache_dir())
    obs.record_execution(whole, scenarios=plan_.num_scenarios)
    return ExperimentResult(plan=plan_, results=results,
                            compile_report=report)


def run_experiment(spec: ExperimentSpec) -> ExperimentResult:
    """``execute(plan(spec))`` — the one-call entry point."""
    return execute(plan(spec))
