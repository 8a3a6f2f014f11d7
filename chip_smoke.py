#!/usr/bin/env python3
"""Bring-up check: the failure campaign and the scoring service on a TPU.

Run from the root of a checkout, on a machine with a TPU::

    python3 chip_smoke.py              # one chip: campaign, service, cache
    python3 chip_smoke.py --chips 4    # four chips: sharded campaign only

Every phase runs in this one process (a chip belongs to one process).
Earlier lines report what each phase found; the last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every check passed.  Without a TPU the script exits non-zero
before any phase runs: there is no CPU fallback.

One chip, at the paper's full detector width (Comms-ML, Table VII):

* campaign: spec -> ``plan(check=True)`` -> ``execute`` with
  ``ExecPlan(aot=True)`` over tolfl k=2 / fl / sbt / ifca M=2 under no
  failure, a server failure, a client failure and a sampled iid grid,
  seeds (0, 1); then one tolfl cell of the 3072-d CIFAR10 body.  Checks:
  every AUROC finite, the failure-free Tol-FL AUROC is within
  ``CPU_AUROC_TOL`` of its CPU value, and, with the campaign re-run at
  float32 matmul precision, a few scenarios re-run through the scalar
  ``run_simulation`` agree with the batched ones;
* service: a tolfl bank at the same width behind ``AnomalyService``
  (buckets 1/8/64, window 8) while a cluster cascade kills heads.
  Checks: no window dropped, all three buckets and a failover used,
  failover and head scores agree with direct scoring of the same model,
  and every served score agrees with a NumPy float64 forward pass;
* cache: every executable compiled in this run was stored in the
  persistent cache (a silent store failure would leave every later run
  cold).  The cache directory is ``JAX_COMPILATION_CACHE_DIR`` when set,
  else ``<checkout>/.repro-cache``.

Four chips: the campaign spec, at float32 matmul precision, once with
``ExecPlan(shard=True)`` over the four devices and once unsharded on
one device of the same process; per-scenario AUROCs and test-loss
curves must agree.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.datasets import base_config, data_spec, prepare  # noqa: E402
from repro.api import (NO_FAILURE, AnomalyService,  # noqa: E402
                       CampaignResult, CellSpec, ClusterCascadeProcess,
                       ExecPlan, ExperimentSpec, FailureSpec, SeedSpec,
                       ServiceConfig, TraceSpec, execute, plan,
                       run_simulation, train_model_bank)
from repro.core import compilecache  # noqa: E402

ROUNDS = 60                 # paper-table rounds (benchmarks/datasets.py)
SEEDS = (0, 1)
SCALE = 1.0                 # prepare() sample scale: 800 per class
TICKS = 20                  # service ticks, one window per client each
WINDOW = 8                  # rows per served window
BUCKETS = (1, 8, 64)

#: Batched (vmapped) campaign scenarios against the scalar
#: ``run_simulation`` of the same scenario, and sharded against
#: unsharded: two programs of the same semantics (on the CPU they agree
#: bit for bit).  At the TPU's default precision a float32 matmul rounds
#: its operands to bfloat16, and two programs that round in different
#: places drift apart over 60 rounds of 3 local SGD steps: emulating
#: bfloat16 operands on the CPU moved this campaign's AUROCs by up to
#: 1.5e-2 (8.3e-2 for IFCA) and its test losses by up to 3.6e-2 relative
#: (1.3e-1 for IFCA), and on a TPU v5e the fl scenarios differed by up
#: to 1.8e-2 in AUROC.  A tolerance that wide would hide real faults, so
#: both sides of these comparisons run at float32 precision (six
#: bfloat16 passes on a TPU), where what is left is the order of float32
#: sums.  On a TPU v5e that left tolfl and sbt within 1e-7, and the fl
#: scenario, whose isolated models train on one device's data after the
#: server fails, within 5.2e-4 in loss and 6e-5 in AUROC; 2e-3 sits
#: between that and the bfloat16 drift above.
LOSS_RTOL = 2e-3
AUROC_ATOL = 2e-3

#: Failure-free Tol-FL AUROC (tolfl k=2, mean over seeds 0 and 1) of the
#: campaign spec below on the CPU, from
#:   JAX_PLATFORMS=cpu python3 -c "import chip_smoke as s; \
#:       print(s.failure_free_tolfl_auroc(s.run_commsml(s.ExecPlan())))"
#: with jax/jaxlib 0.9.0.
CPU_TOLFL_AUROC = 0.91018203125
CPU_AUROC_TOL = 0.02

#: Served scores against a NumPy float64 forward pass.  A float32 matmul
#: at the TPU's default precision rounds its operands to bfloat16 (8
#: significant bits, relative rounding 2**-9).  Emulating that on the
#: CPU (bfloat16 operands, float32 accumulation, all six layers) over
#: this bank's whole test set moved the scores by at most 1.75e-2
#: relative; the tolerance leaves a 1.7x margin.
SCORE_RTOL = 3e-2


def commsml_spec(exec_plan: ExecPlan) -> ExperimentSpec:
    """The paper-table Comms-ML campaign at full width (112-128-64-32
    autoencoder, N=10, k=2, anomaly classes 2 and 3, lr 1e-4, E=3)."""
    prep = prepare("commsml", seed=0, scale=SCALE)
    fail = ROUNDS // 2
    return ExperimentSpec(
        data=data_spec(prep), base=base_config(prep, ROUNDS),
        cells=(CellSpec("tolfl", 2), CellSpec("fl", 1),
               CellSpec("sbt", 10), CellSpec("ifca", 2)),
        traces=TraceSpec.sampled(
            (0.2,), traces_per_p=2, sample_seed=0,
            base=(NO_FAILURE, FailureSpec(fail, "server"),
                  FailureSpec(fail, "client"))),
        seeds=SeedSpec(SEEDS), exec_plan=exec_plan)


def run_commsml(exec_plan: ExecPlan):
    return execute(plan(commsml_spec(exec_plan), check=True))


@contextlib.contextmanager
def float32_matmuls():
    """Float32 matmul precision for everything compiled inside, set
    globally (not thread-locally) because ``execute`` compiles its
    buckets on worker threads."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "float32")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def failure_free_tolfl_auroc(res) -> float:
    c = res.plan.cell(("tolfl", 2))
    r = res[("tolfl", 2)]
    return float(np.mean(r.select(c.explicit_index[0])))


def _aurocs(r) -> dict:
    if isinstance(r, CampaignResult):
        return {"auroc_used": r.auroc_used, "final_auroc": r.final_auroc}
    return {"best_auroc": r.best_auroc, "multi_auroc": r.multi_auroc}


class Checks:
    """Collects failed checks; every phase runs to its end."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"  [{'pass' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)
        return ok


def _describe(res) -> None:
    print(res.plan.describe())
    print(res.compile_report.describe())
    for key, s in res.summary().items():
        vals = {k: round(float(v), 4) for k, v in s.items() if "auroc" in k}
        print(f"  {key}: {vals}")


def campaign_phase(check: Checks) -> list:
    """Returns the executed results (for the cache evidence)."""
    print("== campaign: Comms-ML, paper width ==")
    t0 = time.perf_counter()
    res = run_commsml(ExecPlan(aot=True))
    print(f"  wall {time.perf_counter() - t0:.1f}s (compile included)")
    _describe(res)
    for c, r in zip(res.plan.cells, res.results):
        for name, v in _aurocs(r).items():
            check(bool(np.all(np.isfinite(v))),
                  f"{c.key} {name} finite over {len(v)} scenarios")

    ref = failure_free_tolfl_auroc(res)
    check(abs(ref - CPU_TOLFL_AUROC) <= CPU_AUROC_TOL,
          f"failure-free tolfl AUROC {ref:.4f} within {CPU_AUROC_TOL} "
          f"of the CPU value {CPU_TOLFL_AUROC:.4f}")

    print("== campaign: Comms-ML at float32 matmul precision ==")
    t0 = time.perf_counter()
    with float32_matmuls():
        exact = run_commsml(ExecPlan(aot=True))
    print(f"  wall {time.perf_counter() - t0:.1f}s (compile included)")
    print(exact.compile_report.describe())
    for c, a, b in zip(res.plan.cells, res.results, exact.results):
        print(f"  {c.key}: max |AUROC default - float32| " + ", ".join(
            f"{n} {float(np.max(np.abs(v - _aurocs(b)[n]))):.3g}"
            for n, v in _aurocs(a).items()))

    # a few scenarios through the scalar simulator on the same chip
    spec = exact.plan.spec
    for key, explicit, seed in ((("tolfl", 2), 0, 0), (("fl", 1), 1, 1),
                                (("sbt", 10), 2, 0)):
        c, r = exact.plan.cell(key), exact[key]
        ti = c.explicit_index[explicit]
        b = int(np.flatnonzero((r.trace_index == ti) & (r.seed == seed))[0])
        with float32_matmuls():
            one = run_simulation(spec.data.model, spec.data.device_x,
                                 spec.data.device_counts, spec.data.test_x,
                                 spec.data.test_y,
                                 dataclasses.replace(c.cfg, seed=seed),
                                 c.traces[ti])
        d_auc = abs(float(r.auroc_used[b]) - one.auroc_used)
        d_loss = float(np.max(np.abs(r.loss_curves[b] - one.loss_curve)
                              / np.abs(one.loss_curve)))
        same = (float(r.auroc_used[b]) == one.auroc_used
                and np.array_equal(r.loss_curves[b], one.loss_curve))
        check(d_auc <= AUROC_ATOL and d_loss <= LOSS_RTOL,
              f"{key} trace {ti} seed {seed}: batched vs run_simulation "
              f"|dAUROC| {d_auc:.3g} <= {AUROC_ATOL}, loss rel "
              f"{d_loss:.3g} <= {LOSS_RTOL} (bit-identical: {same})")

    print("== campaign: CIFAR10 body (3072-d), tolfl ==")
    prep = prepare("cifar10", seed=0, scale=SCALE)
    t0 = time.perf_counter()
    wide = execute(plan(ExperimentSpec(
        data=data_spec(prep), base=base_config(prep, ROUNDS),
        cells=(CellSpec("tolfl", prep.clusters),),
        traces=TraceSpec.explicit(NO_FAILURE,
                                  FailureSpec(ROUNDS // 2, "server")),
        seeds=SeedSpec(SEEDS), exec_plan=ExecPlan(aot=True)), check=True))
    print(f"  wall {time.perf_counter() - t0:.1f}s (compile included)")
    _describe(wide)
    for name, v in _aurocs(wide.results[0]).items():
        check(bool(np.all(np.isfinite(v))),
              f"cifar10 tolfl {name} finite over {len(v)} scenarios")
    return [res, exact, wide]


def _forward64(params: dict, x: np.ndarray) -> np.ndarray:
    """Float64 autoencoder scores: ReLU hidden layers, linear output,
    squared reconstruction error per row."""
    n = len(params)
    h = x.astype(np.float64)
    for i in range(n):
        p = params[f"fc{i}"]
        h = h @ np.asarray(p["w"], np.float64) + np.asarray(p["b"],
                                                            np.float64)
        if i < n - 1:
            h = np.maximum(h, 0.0)
    return np.sum(np.square(x.astype(np.float64) - h), axis=-1)


def service_phase(check: Checks) -> dict:
    """Returns the service's bucket compile sources."""
    print("== service: tolfl bank, paper width, cluster cascade ==")
    prep = prepare("commsml", seed=0, scale=SCALE)
    cfg = base_config(prep, ROUNDS)
    t0 = time.perf_counter()
    bank = train_model_bank(prep.ae_cfg, prep.device_x, prep.counts, cfg)
    print(f"  bank trained in {time.perf_counter() - t0:.1f}s "
          f"(N={bank.num_clients}, k={cfg.num_clusters}, {ROUNDS} rounds)")
    t0 = time.perf_counter()
    svc = AnomalyService(
        bank, ServiceConfig(bucket_sizes=BUCKETS, window=WINDOW),
        failure=ClusterCascadeProcess(p_head=1.0, q=0.5, recover_prob=1.0,
                                      recovery_lag=5),
        sample_seed=1, horizon=TICKS)
    print(f"  service built in {time.perf_counter() - t0:.1f}s, bucket "
          f"compile sources: {svc.compile_sources}")

    tx = np.asarray(prep.test_x, np.float32)
    ty = np.asarray(prep.test_y)
    n_win = tx.shape[0] // WINDOW
    wins = tx[:n_win * WINDOW].reshape(n_win, WINDOW, -1)
    labs = ty[:n_win * WINDOW].reshape(n_win, WINDOW)
    scored = []
    for t in range(TICKS):
        for c in range(bank.num_clients):
            i = (t * bank.num_clients + c) % n_win
            svc.submit(c, wins[i], labels=labs[i])
        scored.extend(svc.tick())
    rep = svc.report()
    print(f"  {rep.describe()}")
    print(f"  bucket dispatches: {rep.bucket_batches}")
    print(f"  timeline: {svc.timeline}")
    submitted = TICKS * bank.num_clients
    check(rep.dropped == 0 and len(scored) == submitted,
          f"{len(scored)} of {submitted} windows scored, none dropped")
    check(all(rep.bucket_batches.get(b, 0) > 0 for b in BUCKETS),
          f"buckets {BUCKETS} all dispatched")
    check(rep.failovers >= 1, f"{rep.failovers} failovers")

    det = bank.detector
    host_rows = jax.tree.map(np.asarray, bank.row_params)
    max_rel, worst_direct, bitwise = 0.0, 0.0, {"head": True,
                                                  "isolated": True}
    for w in scored:
        i = (w.seq * bank.num_clients + w.client) % n_win
        iso = w.served_by == "isolated"
        row = bank.row_index(w.client, iso)
        params = (bank.client_iso_params(w.client) if iso
                  else bank.global_params)
        direct = np.asarray(det.anomaly_scores(params, wins[i]))
        bitwise[w.served_by] &= bool(np.array_equal(w.scores, direct))
        worst_direct = max(worst_direct, float(np.max(
            np.abs(w.scores - direct) / np.abs(direct))))
        ref = _forward64(jax.tree.map(lambda p: p[row], host_rows), wins[i])
        max_rel = max(max_rel, float(np.max(np.abs(w.scores - ref)
                                            / np.abs(ref))))
    print(f"  bit-identical to direct scoring: failover "
          f"{bitwise['isolated']}, head {bitwise['head']}")
    check(worst_direct <= SCORE_RTOL,
          f"served vs direct scoring of the same model: max rel "
          f"{worst_direct:.3g} <= {SCORE_RTOL}")
    check(max_rel <= SCORE_RTOL,
          f"served vs float64 NumPy forward: max rel {max_rel:.3g} "
          f"<= {SCORE_RTOL}")
    return svc.compile_sources


def cache_evidence(check: Checks, results: list, serving: dict) -> None:
    print("== cache ==")
    sources = [b.cache for r in results for b in r.compile_report.buckets]
    stats = compilecache.xla_compile_stats()
    compiled = (sources.count("compiled")
                + list(serving.values()).count("compiled"))
    print(f"  dir {compilecache.persistent_cache_dir()}")
    print(f"  campaign bucket sources {sources}, serving {serving}")
    print(f"  xla_compile_stats {stats}")
    check(stats["exe_stores"] >= compiled,
          f"{stats['exe_stores']} executables stored for {compiled} "
          f"compiled buckets")


def sharded_phase(check: Checks) -> None:
    print("== campaign: sharded over 4 chips vs unsharded, float32 "
          "matmul precision ==")
    sharded = ExecPlan(shard=True, aot=True)
    ndev = sharded.resolved_devices(warn=False)
    check(ndev == 4, f"ExecPlan(shard=True) resolves {ndev} devices")
    with float32_matmuls():
        t0 = time.perf_counter()
        res_sh = run_commsml(sharded)
        print(f"  sharded wall {time.perf_counter() - t0:.1f}s")
        _describe(res_sh)
        check(all(b.devices == 4 for b in res_sh.plan.buckets),
              "every bucket shards over 4 devices")
        t0 = time.perf_counter()
        res_one = run_commsml(ExecPlan(aot=True))
        print(f"  unsharded wall {time.perf_counter() - t0:.1f}s")
    for c, a, b in zip(res_sh.plan.cells, res_sh.results, res_one.results):
        exact = True
        for name, va in _aurocs(a).items():
            vb = _aurocs(b)[name]
            exact &= bool(np.array_equal(va, vb))
            d = float(np.max(np.abs(va - vb)))
            check(d <= AUROC_ATOL, f"{c.key} {name}: max |sharded - "
                  f"unsharded| {d:.3g} <= {AUROC_ATOL}")
        d = float(np.max(np.abs(a.loss_curves - b.loss_curves)
                         / np.abs(b.loss_curves)))
        exact &= bool(np.array_equal(a.loss_curves, b.loss_curves))
        check(d <= LOSS_RTOL, f"{c.key} loss curves: max rel {d:.3g} <= "
              f"{LOSS_RTOL} (bit-identical: {exact})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-unsharded campaign")
    args = ap.parse_args()

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = compilecache.enable_persistent_cache()
    print(f"device {d0.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"cache {cache_dir}")

    check = Checks()
    if args.chips == 4:
        sharded_phase(check)
    else:
        results = campaign_phase(check)
        serving = service_phase(check)
        cache_evidence(check, results, serving)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
