"""Campaign execution-layer throughput: scenarios/sec, compiles, wall.

Tracks the perf trajectory of the batched failure-campaign engine
(``BENCH_campaign.json``, written next to the working directory so CI
artifacts pick it up):

* ``oneshot``   — a fixed 64-scenario (16 sampled traces x 4 seeds)
  Tol-FL grid in one ``jit(vmap)`` call; wall-clock includes the single
  compile, ``steady`` re-runs it on the warm executable cache (the
  compile-amortisation contract: 0 new traces).
* ``chunked``   — the same grid through host-side chunking
  (``chunk_size=16``): bounded device memory, still one compile.
* ``sweep_padded`` — an all-single-model-cells ``sweep_grid``
  ((tolfl, 5) / (tolfl, 2) / (fl, 1) / (sbt, 10), 128 scenarios) with
  padded-k topology arrays but ONE DISPATCH PER CELL (``fuse=False``):
  compiles are bounded per ISO-TRACKING KIND, not per cell — exactly
  TWO for this grid (one executable shared by the three non-fl cells,
  one for the fl cell's isolated-fallback branch).
* ``sweep_fused_cold`` / ``sweep_fused`` — the SAME 128-scenario grid
  through the fused dispatcher (stacked topology operands, one
  ``jit(vmap)`` over the flattened (cell x trace x seed) axis per
  iso-tracking kind: two dispatches total).  ``_cold`` includes the two
  fused compiles; ``sweep_fused`` re-runs on the warm executable cache —
  the engine's compile-amortised operating regime (every further grid
  on these shapes costs 0 traces) and the row the ISSUE 4 win condition
  tracks against per-cell ``steady`` throughput.
* ``spec_sweep`` — the SAME 128-scenario grid declared as an
  :class:`repro.api.ExperimentSpec` and lowered through
  ``plan -> execute``: the declarative layer rides the identical warm
  executables, so CI asserts it stays within 5% of ``sweep_fused``
  scenarios/sec (the spec layer must be overhead-free).
* ``sweep_aot_cold`` — the same grid with ``ExecPlan(aot=True)``: every
  iteration starts from EMPTY in-process caches (a fresh-process
  simulation); the bench-local persistent cache directory is cleared
  before iteration 1 only, so the first iteration is the true
  first-ever cold cost and later iterations are the "new process, warm
  disk" regime — compiled executables deserialise whole
  (``exe_hits``), zero traces, zero XLA.  Best-of-N reports that
  warm-disk regime; per-iteration compiles / XLA misses are in the
  JSON.  The ISSUE 6 win condition: >= 1.5x ``sweep_fused_cold``.
* ``sweep_diskcache_cold`` — the jit-path twin: in-process caches
  cleared per iteration, disk cleared before iteration 1.  Iterations
  after the first still pay Python tracing but XLA compiles come from
  the persistent module cache — the regime a warm-disk fresh process
  hits WITHOUT opting into AOT.
* ``sampled_max_events`` — compile+run wall of a sampled-rate grid with
  the big default slot budget (max_events = 2N): the regression guard
  for the vectorized ``trace_alive_mask`` (the unrolled fold made this
  compile O(max_events) slower).

Cold rows (``sweep_padded``, ``sweep_fused_cold``) clear the
in-process executable caches AND the bench-local disk cache before
EVERY iteration, so every rep genuinely compiles (the committed
baseline used to report best-of-reps where only rep 1 compiled).  The
whole bench runs against its own fixed subdirectory of the resolved
persistent-cache directory (``<cache>/bench-campaign``, emptied at
start) and restores the prior cache wiring on exit.

The traces are sampled at a fixed RNG seed, so the grid is identical
run-to-run and numbers are comparable across commits — provided the
exec-plan flags match: ``run(shard=..., chunk_size=...)`` (the
``benchmarks.run --shard / --chunk-size`` CLI) applies an
:class:`ExecPlan` to every campaign row so sharded / chunked variants
are reproducible one-liners, but only default-flag runs should be
committed as the baseline JSON.
"""
from __future__ import annotations

import json
import os
import shutil
import time
from typing import List, Optional

import numpy as np

from benchmarks.datasets import data_spec, prepare
from repro.api import (CellSpec, DataSpec, ExperimentSpec, SeedSpec,
                       SeqDetector, TraceSpec, run_experiment)
from repro.core import campaign, compilecache
from repro.core.campaign import ExecPlan, run_campaign, sweep_grid
from repro.core.failure import sample_rate_grid, sample_traces
from repro.core.simulate import SimConfig

GRID_TRACES = 16
GRID_SEEDS = 4
ROUNDS = 8


def _timed_campaign(label, lines, results, fn, reps: int = 1,
                    pre_iter=None):
    """Time ``fn``; multi-rep rows report the BEST wall — the `timeit`
    convention: external noise (this container's cpu budget wobbles for
    seconds at a time) only ever slows a run down, so the minimum is
    the best estimate of the true throughput.  Cold rows pass
    ``pre_iter(i)`` to clear executable caches before EVERY iteration,
    so every rep genuinely compiles; per-iteration compile counts and
    XLA cache misses land in the JSON (``compiles`` reports the count
    at the best-wall iteration — the regime the headline number
    measures)."""
    walls, compiles_iter, xla_miss_iter, exe_hits_iter = [], [], [], []
    for i in range(reps):
        if pre_iter is not None:
            pre_iter(i)
        c0 = campaign.TRACE_COUNT
        x0 = compilecache.xla_compile_stats()
        t0 = time.time()
        res = fn()
        walls.append(time.time() - t0)
        x1 = compilecache.xla_compile_stats()
        compiles_iter.append(campaign.TRACE_COUNT - c0)
        xla_miss_iter.append(x1["misses"] - x0["misses"])
        exe_hits_iter.append(x1["exe_hits"] - x0["exe_hits"])
    best = int(np.argmin(walls))
    wall = walls[best]
    compiles = compiles_iter[best]
    n = sum(r.num_scenarios for r in
            (res.values() if isinstance(res, dict) else [res]))
    results[label] = {"scenarios": n, "compiles": compiles,
                      "wall_s": round(wall, 3),
                      "scenarios_per_s": round(n / max(wall, 1e-9), 2),
                      "walls_s": [round(w, 3) for w in walls],
                      "compiles_per_iter": compiles_iter,
                      "xla_misses_per_iter": xla_miss_iter,
                      "exe_hits_per_iter": exe_hits_iter}
    lines.append(f"{label},{n},{compiles},{wall:.2f},{n / wall:.1f}")
    return res


def bench_cache_dir(name: str) -> str:
    """``<cache>/<name>``: a fixed subdirectory of the resolved
    persistent cache directory (``JAX_COMPILATION_CACHE_DIR`` or the
    checkout's ``.repro-cache``)."""
    base = compilecache.default_cache_dir()
    if base is None:
        raise RuntimeError("the cache benchmarks need JAX's persistent "
                           "compilation cache; it is switched off")
    return os.path.join(base, name)


def run(out_path: str = "BENCH_campaign.json", shard: bool = False,
        chunk_size: Optional[int] = None) -> List[str]:
    # the bench measures cold/warm-disk regimes against its own fixed
    # subdirectory of the resolved cache directory, which the cold rows
    # empty themselves, and restores the prior wiring on exit
    prev_dir = compilecache.persistent_cache_dir()
    cache_dir = bench_cache_dir("bench-campaign")
    compilecache.enable_persistent_cache(cache_dir)

    def clear_disk():
        shutil.rmtree(cache_dir, ignore_errors=True)
        os.makedirs(cache_dir, exist_ok=True)

    def cold_iter(i):
        """Fresh-process simulation: every executable cache empty."""
        campaign.clear_executable_caches()
        clear_disk()

    def diskwarm_iter(i):
        """New-process-with-warm-disk simulation: in-process caches
        empty, the persistent directory cleared before iteration 1
        only (iteration 1 populates it; later iterations ride it)."""
        campaign.clear_executable_caches()
        if i == 0:
            clear_disk()

    clear_disk()
    try:
        return _run_rows(out_path, shard, chunk_size, cold_iter,
                         diskwarm_iter)
    finally:
        if prev_dir is not None:
            compilecache.enable_persistent_cache(prev_dir)
        else:
            compilecache.disable_persistent_cache()


def _run_rows(out_path, shard, chunk_size, cold_iter, diskwarm_iter
              ) -> List[str]:
    plan = (ExecPlan(shard=shard, chunk_size=chunk_size)
            if (shard or chunk_size) else None)
    prep = prepare("commsml", seed=0, scale=0.25)
    cfg = SimConfig(scheme="tolfl", num_devices=10,
                    num_clusters=prep.clusters, rounds=ROUNDS,
                    lr=prep.lr, local_epochs=1, dropout=False)
    topo = cfg.topology()
    traces = sample_traces(np.random.default_rng(0), topo, 0.3,
                           max_events=8, rounds=ROUNDS,
                           num_traces=GRID_TRACES)
    seeds = range(GRID_SEEDS)
    args = (prep.ae_cfg, prep.device_x, prep.counts, prep.test_x,
            prep.test_y)

    lines = ["name,scenarios,compiles,wall_s,scenarios_per_s"]
    results: dict = {}

    _timed_campaign("oneshot", lines, results,
                    lambda: run_campaign(*args, cfg, traces, seeds,
                                         exec_plan=plan))
    _timed_campaign("steady", lines, results,
                    lambda: run_campaign(*args, cfg, traces, seeds,
                                         exec_plan=plan), reps=3)
    _timed_campaign("chunked", lines, results,
                    lambda: run_campaign(*args, cfg, traces, seeds,
                                         exec_plan=ExecPlan(
                                             shard=shard,
                                             chunk_size=chunk_size or 16)))
    base = SimConfig(num_devices=10, rounds=ROUNDS, lr=prep.lr,
                     dropout=False)
    grid = dict(scheme_ks=[("tolfl", 5), ("tolfl", 2),
                           ("fl", 1), ("sbt", 10)],
                traces=traces, seeds=[0, 1], exec_plan=plan)
    _timed_campaign("sweep_padded", lines, results,
                    lambda: sweep_grid(*args, base, fuse=False, **grid),
                    reps=2, pre_iter=cold_iter)
    _timed_campaign("sweep_fused_cold", lines, results,
                    lambda: sweep_grid(*args, base, **grid),
                    reps=2, pre_iter=cold_iter)
    _timed_campaign("sweep_fused", lines, results,
                    lambda: sweep_grid(*args, base, **grid), reps=3)
    # the SAME 128-scenario grid declared as an ExperimentSpec and run
    # through plan -> execute: the declarative layer must be
    # overhead-free over the fused dispatcher it lowers to (same warm
    # executables — CI asserts spec_sweep stays within 5% of
    # sweep_fused scenarios/sec)
    sweep_spec = ExperimentSpec(
        data=data_spec(prep), base=base,
        cells=tuple(CellSpec(s, k) for s, k in grid["scheme_ks"]),
        traces=TraceSpec(traces=tuple(traces)),
        seeds=SeedSpec((0, 1)), exec_plan=plan)
    _timed_campaign("spec_sweep", lines, results,
                    lambda: run_experiment(sweep_spec), reps=3)
    # a SECOND detector body (the RG-LRU windowed sequence detector)
    # through the same declarative pipeline: its executables key on the
    # frozen spec (never aliasing the autoencoder's), cold iteration
    # compiles one per iso-tracking kind, warm reps ride them for free
    seq_spec = ExperimentSpec(
        data=DataSpec(
            model=SeqDetector(input_dim=prep.device_x.shape[-1],
                              window=16, d_model=8),
            device_x=prep.device_x, device_counts=prep.counts,
            test_x=prep.test_x, test_y=prep.test_y,
            name=prep.name + "-seq"),
        base=base,
        cells=(CellSpec("tolfl", 2), CellSpec("fl", 1)),
        traces=TraceSpec(traces=tuple(traces[:4])),
        seeds=SeedSpec((0,)), exec_plan=plan)
    _timed_campaign("sweep_seq_detector", lines, results,
                    lambda: run_experiment(seq_spec), reps=2)

    # the SAME grid under ExecPlan(aot=True): iteration 1 is the true
    # first-ever cold cost (plan-time lowering overlapping the host
    # array builds + persistent-cache population), iterations 2-3
    # simulate a NEW PROCESS with a warm disk — compiled executables
    # deserialise whole: zero traces, zero XLA (the ISSUE 6 win row)
    aot_plan = ExecPlan(shard=shard, chunk_size=chunk_size, aot=True)
    aot_spec = ExperimentSpec(
        data=data_spec(prep), base=base,
        cells=tuple(CellSpec(s, k) for s, k in grid["scheme_ks"]),
        traces=TraceSpec(traces=tuple(traces)),
        seeds=SeedSpec((0, 1)), exec_plan=aot_plan)
    _timed_campaign("sweep_aot_cold", lines, results,
                    lambda: run_experiment(aot_spec), reps=3,
                    pre_iter=diskwarm_iter)
    # the jit-path twin: a warm disk serves XLA's module cache but
    # Python tracing still runs every fresh process
    _timed_campaign("sweep_diskcache_cold", lines, results,
                    lambda: sweep_grid(*args, base, **grid), reps=3,
                    pre_iter=diskwarm_iter)

    # sampled-rate grid at the big slot budget (max_events = 2N): the
    # vectorized trace_alive_mask keeps this compile O(1) in max_events
    s_traces, _ = sample_rate_grid(np.random.default_rng(1), topo,
                                   p_grid=(0.1, 0.3), rounds=ROUNDS,
                                   traces_per_p=8)
    _timed_campaign("sampled_max_events", lines, results,
                    lambda: run_campaign(*args, cfg, s_traces, [0, 1],
                                         exec_plan=plan))

    # dump BEFORE the guards: a tripped assert must still leave the row
    # data on disk for diagnosis
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    lines.append(f"# wrote {out_path}")

    assert sum(results["steady"]["compiles_per_iter"]) == 0, \
        results["steady"]
    # 4 cells, 2 compiles: non-fl cells share one executable, fl (whose
    # isolated-fallback branch is extra compute) gets its own — and the
    # cold rows re-pay it EVERY iteration (caches cleared per iter)
    assert results["sweep_padded"]["compiles_per_iter"] == [2, 2], \
        results["sweep_padded"]
    assert results["sweep_fused_cold"]["compiles_per_iter"] == [2, 2], \
        results["sweep_fused_cold"]
    assert sum(results["sweep_fused"]["compiles_per_iter"]) == 0, \
        results["sweep_fused"]
    # the declarative pipeline rides the same warm executables (0
    # compiles) and must not tax throughput more than 5%
    assert sum(results["spec_sweep"]["compiles_per_iter"]) == 0, \
        results["spec_sweep"]
    # ... comparing MEDIAN walls: best-of-3 picks each row's independent
    # noise minimum, which flakes the ratio on a 1-core container
    _med = lambda r: float(np.median(r["walls_s"]))  # noqa: E731
    assert _med(results["spec_sweep"]) <= 1.05 * _med(results["sweep_fused"]), \
        (results["spec_sweep"], results["sweep_fused"])
    # second-body row: one compile per iso-tracking kind on the cold
    # iteration, none on the warm rep (detector specs are first-class
    # executable-cache keys, not a retrace hazard)
    seq = results["sweep_seq_detector"]
    assert seq["compiles_per_iter"][0] == 2, seq
    assert seq["compiles_per_iter"][1:] == [0], seq
    # AOT row: iteration 1 traces + compiles + populates the disk;
    # warm-disk iterations deserialise whole executables — no traces,
    # no XLA compiles
    aot = results["sweep_aot_cold"]
    assert aot["compiles_per_iter"][0] == 2, aot
    assert aot["compiles_per_iter"][1:] == [0, 0], aot
    assert all(m == 0 for m in aot["xla_misses_per_iter"][1:]), aot
    assert all(h >= 2 for h in aot["exe_hits_per_iter"][1:]), aot
    # jit twin: tracing recurs every iteration; XLA comes from disk
    disk = results["sweep_diskcache_cold"]
    assert disk["compiles_per_iter"] == [2, 2, 2], disk
    assert all(m == 0 for m in disk["xla_misses_per_iter"][1:]), disk
    # the cold-compile tax is dead: a warm-disk fresh process under AOT
    # must beat the genuinely cold fused sweep by >= 1.5x
    assert (aot["scenarios_per_s"]
            >= 1.5 * results["sweep_fused_cold"]["scenarios_per_s"]), \
        (aot, results["sweep_fused_cold"])
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
