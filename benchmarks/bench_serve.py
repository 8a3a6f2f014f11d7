"""Anomaly-scoring-service throughput under failure injection.

Tracks the serving layer's perf trajectory (``BENCH_serve.json``,
committed next to ``BENCH_campaign.json``):

* ``direct_bs64`` — the lower bound the service must track: one jitted
  ``det.anomaly_scores`` call on the same rows a full 64-window bucket
  carries (``(64 * W, D)``), best-of-reps windows/sec.
* ``service_bs64`` — the warm service streaming exactly 64 windows per
  tick with nothing failing: full path (submit queue -> coalesce ->
  routed gather -> compiled bucket).  The win condition the ISSUE pins:
  within 10% of ``direct_bs64`` (``ratio_vs_direct >= 0.9``) — the
  batching/routing/queue machinery must be overhead-free at the big
  bucket.
* ``service_iid`` / ``service_markov`` / ``service_cascade`` — the same
  streaming load while a sampled :class:`FailureProcess` of each family
  drives service-time liveness: windows/sec + p50/p99 latency +
  failover/failback counts.  A dead head fans its cluster's windows out
  of the shared global bucket into one isolated-model bucket per client
  — real extra model evaluations — so these rows may run slower, but
  must stay within 2x of the clean row (and ZERO windows drop,
  asserted).

The bank trains once (a tiny Tol-FL run); every service row re-stands
the service from the in-process executable cache, so rows measure
serving, not compilation.  Like ``bench_campaign``, the whole bench
runs against a throwaway persistent-cache directory and restores the
prior wiring on exit.
"""
from __future__ import annotations

import json
import shutil
import time
from typing import List

import jax
import numpy as np

from benchmarks.bench_campaign import bench_cache_dir
from benchmarks.datasets import prepare
from repro.core import compilecache
from repro.core.processes import (ClusterCascadeProcess, IidRateProcess,
                                  MarkovChurnProcess)
from repro.core.simulate import SimConfig
from repro.serving.anomaly import (AnomalyService, ServiceConfig,
                                   train_model_bank)

WINDOW = 32          # rows per traffic window (big enough that compute,
                     # not submit-loop python, dominates a 64-bucket)
ROUNDS = 6           # bank-training rounds (the bench measures serving)
TICKS = 24           # service ticks per measured rep
REPS = 3             # best-of (timeit convention; see bench_campaign)

#: the failure injections the service rows stream under
PROCESSES = {
    "service_iid": IidRateProcess(p=0.4),
    "service_markov": MarkovChurnProcess(p_fail=0.15, p_recover=0.3),
    "service_cascade": ClusterCascadeProcess(p_head=1.0, recover_prob=1.0,
                                             recovery_lag=6),
}


def _window_pool(prep) -> np.ndarray:
    tx = np.asarray(prep.test_x, np.float32)
    n = tx.shape[0] // WINDOW
    return tx[:n * WINDOW].reshape(n, WINDOW, tx.shape[-1])


def _time_direct(bank, wins, results, lines) -> float:
    """Best-of-reps windows/sec of the raw batched score call on the
    rows one 64-window bucket carries."""
    det = bank.detector
    jitted = jax.jit(det.anomaly_scores)
    flat = np.ascontiguousarray(
        wins[np.arange(64) % wins.shape[0]].reshape(64 * WINDOW, -1))
    jitted(bank.global_params, flat).block_until_ready()   # warm
    walls = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(TICKS):
            jitted(bank.global_params, flat).block_until_ready()
        walls.append(time.perf_counter() - t0)
    wall = min(walls)
    wps = 64 * TICKS / wall
    results["direct_bs64"] = {"windows": 64 * TICKS,
                              "wall_s": round(wall, 3),
                              "windows_per_s": round(wps, 1)}
    lines.append(f"direct_bs64,{64 * TICKS},{wall:.3f},{wps:.0f},-,-,0,0")
    return wps


def _time_service(label, bank, wins, failure, results, lines,
                  direct_wps) -> None:
    """Best-of-reps sustained windows/sec of the full service path
    streaming 64 windows per tick (fresh service per rep — executables
    resolve from the in-process memory cache, so reps measure serving)."""
    best = None
    for _ in range(REPS):
        svc = AnomalyService(bank, ServiceConfig(bucket_sizes=(1, 8, 64),
                                                 window=WINDOW),
                             failure=failure, sample_seed=3,
                             horizon=TICKS)
        n_win = wins.shape[0]
        t0 = time.perf_counter()
        for t in range(TICKS):
            for j in range(64):
                c = j % bank.num_clients
                svc.submit(c, wins[(t * 64 + j) % n_win])
            svc.tick()
        wall = time.perf_counter() - t0
        rep = svc.report()
        assert rep.dropped == 0, (label, rep)
        if best is None or wall < best[0]:
            best = (wall, rep)
    wall, rep = best
    wps = rep.windows / wall
    row = {"windows": rep.windows, "wall_s": round(wall, 3),
           "windows_per_s": round(wps, 1),
           "p50_ms": round(rep.p50_ms, 3), "p99_ms": round(rep.p99_ms, 3),
           "failovers": rep.failovers, "failbacks": rep.failbacks}
    if failure is None:
        row["ratio_vs_direct"] = round(wps / direct_wps, 3)
    results[label] = row
    lines.append(f"{label},{rep.windows},{wall:.3f},{wps:.0f},"
                 f"{rep.p50_ms:.2f},{rep.p99_ms:.2f},"
                 f"{rep.failovers},{rep.failbacks}")


def run(out_path: str = "BENCH_serve.json") -> List[str]:
    # a fixed subdirectory of the resolved cache directory, emptied
    # first so the bank export and bucket builds start cold
    prev_dir = compilecache.persistent_cache_dir()
    cache_dir = bench_cache_dir("bench-serve")
    shutil.rmtree(cache_dir, ignore_errors=True)
    compilecache.enable_persistent_cache(cache_dir)
    try:
        return _run_rows(out_path)
    finally:
        if prev_dir is not None:
            compilecache.enable_persistent_cache(prev_dir)
        else:
            compilecache.disable_persistent_cache()


def _run_rows(out_path: str) -> List[str]:
    prep = prepare("commsml", seed=0, scale=0.25)
    cfg = SimConfig(scheme="tolfl", num_devices=10,
                    num_clusters=prep.clusters, rounds=ROUNDS,
                    lr=prep.lr, local_epochs=1, dropout=False)
    t0 = time.perf_counter()
    bank = train_model_bank(prep.ae_cfg, prep.device_x, prep.counts, cfg)
    train_wall = time.perf_counter() - t0
    wins = _window_pool(prep)

    lines = ["name,windows,wall_s,windows_per_s,p50_ms,p99_ms,"
             "failovers,failbacks"]
    results: dict = {"train_bank": {"wall_s": round(train_wall, 3)}}

    direct_wps = _time_direct(bank, wins, results, lines)
    _time_service("service_bs64", bank, wins, None, results, lines,
                  direct_wps)
    for label, proc in PROCESSES.items():
        _time_service(label, bank, wins, proc, results, lines, direct_wps)

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    lines.append(f"# wrote {out_path}")

    # the ISSUE win condition: warm service within 10% of the direct
    # batched score call at the 64-bucket
    ratio = results["service_bs64"]["ratio_vs_direct"]
    assert ratio >= 0.9, (ratio, results["service_bs64"],
                          results["direct_bs64"])
    # injection must actually exercise the failover path...
    assert results["service_cascade"]["failovers"] > 0, \
        results["service_cascade"]
    # ...and keep sustained throughput within 2x of the clean row.
    # Failover is REAL extra work, not routing overhead: a dead head
    # fans its cluster's windows out of the shared global bucket into
    # one isolated-model bucket PER CLIENT (distinct weights can't
    # share a dispatch), so some slowdown is the cost of the models,
    # bounded here so a host-path regression still trips.
    for label in PROCESSES:
        assert (results[label]["windows_per_s"]
                >= 0.5 * results["service_bs64"]["windows_per_s"]), \
            (label, results[label], results["service_bs64"])
    return lines


#: ``benchmarks.run --smoke`` entry point (same rows; the bench is
#: already seconds-scale, so smoke IS the committed-baseline config)
run_smoke = run


if __name__ == "__main__":
    print("\n".join(run()))
