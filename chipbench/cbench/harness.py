"""One run of one cell: set-up, the measured window, the check.

The cell's configuration, traffic mix, detector body and per-layer
metric readers are files found by name (the configuration entry's
``file``, ``traffic/<traffic>.json`` for the cell's ``traffic``,
``bodies/<kind>.py`` for the configuration's ``model.kind``,
``metrics/<metric>.py``); the traffic file's ``kind`` names the general
generator that reads it (:mod:`cbench.campaign`, :mod:`cbench.serve`).

A body file holds everything the harness knows of one detector body:

``program_model(model)``
    what the program's ``DataSpec(model=...)`` is given;
``program_loss_class()``
    the program class whose ``loss`` the ``half_batch`` fault wraps;
``program_params(params, model)``
    reference-layout parameters as the program's pytree;
``init(key, model)``, ``train_loss(params, x, valid, key, precision,
model)``, ``scores(params, x, precision, model)``
    the plain float32 reference of the body, which imports nothing of
    the program and takes every product through
    :func:`cbench.reference.matmul`;
``bank(key, model, rows)`` (for a scoring-service cell)
    ``rows`` scoring-service models drawn from ``key``, in the
    reference's layout;
``param_count(model)``, ``train_flops_per_scenario(model, counts,
local_steps, rounds)``
    the counts ``mfu.campaign`` reads;
``kernel_counts(model, geometry)`` (optional)
    FLOPs and HBM bytes of one call of each of the body's kernels, by
    op-name prefix, for one local step of a bucket.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional

GENERATORS = {"campaign": ("cbench.campaign", "Campaign"),
              "serve": ("cbench.serve", "Serve")}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def bench_dir() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: where ``body(kind)`` looks for ``<kind>.py``
BODY_DIR = os.path.join(bench_dir(), "bodies")


def body(kind: str) -> ModuleType:
    """The body file of a model kind, loaded once per path."""
    path = os.path.join(BODY_DIR, f"{kind}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no body file for model kind {kind!r}: looked for {path}")
    return _load_body(path, kind)


@functools.lru_cache(maxsize=None)
def _load_body(path: str, kind: str) -> ModuleType:
    return _load(path, "chipbench_body_" + kind)


def body_kinds() -> List[str]:
    """The kinds that have a body file."""
    return sorted(os.path.splitext(f)[0] for f in os.listdir(BODY_DIR)
                  if f.endswith(".py"))


def _load(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_files(bench: dict, workload: str, checkout: str):
    """(workload entry, configuration, traffic) of a cell."""
    wl = find(bench["workloads"], workload, "workload")
    cfg_entry = find(bench["configs"], wl["config"], "config")
    config = load_json(os.path.join(checkout, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir(), "traffic",
                                     f"{wl['traffic']}.json"))
    return wl, config, traffic


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    return _load(os.path.join(bench_dir(), "metrics", f"{name}.py"),
                 "chipbench_metric_" + name).read


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, checkout: str,
             require_tpu: bool = True,
             config_override: Optional[Callable[[dict], dict]] = None,
             traffic_override: Optional[Callable[[dict], dict]] = None,
             log=lambda *a: print(*a, file=sys.stderr)) -> dict:
    """Run one cell once and return its result object (the last line
    a run prints)."""
    import jax
    from cbench import peaks
    from cbench.spans import Spans

    wl, config, traffic = cell_files(bench, workload, checkout)
    if config_override:
        config = config_override(config)
    if traffic_override:
        traffic = traffic_override(traffic)
    chips = int(wl["chips"])
    devs = devices_for(chips, require_tpu)
    kind = devs[0].device_kind
    device_peaks = peaks.peaks(kind) if require_tpu else None
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    from repro.core import compilecache
    compilecache.enable_persistent_cache()

    mod_name, cls_name = GENERATORS[traffic["kind"]]
    mod = __import__(mod_name, fromlist=[cls_name])
    spans = Spans()
    gen = getattr(mod, cls_name)(config, traffic, seed, chips, spans,
                                 seconds)
    gen.warm_up()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    compiles0 = compilecache.xla_compile_stats()["requests"]
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = gen.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = compilecache.xla_compile_stats()["requests"] - compiles0
    peak = memory_peak(devs)
    log(f"window {win['seconds']:.3f} s, {win['scenarios']} done, "
        f"{compiles} compile requests inside it")

    reduced = None
    if trace:
        from cbench import tracereduce
        try:
            rec = tracereduce.load_xplane(
                trace_dir, getattr(mod, "SPAN_NAMES"))
            reduced = tracereduce.reduce_trace(
                rec, kernels=getattr(gen, "kernel_prefixes", ()))
        except tracereduce.NoDevicePlane:
            if require_tpu:
                raise
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    gen.free()
    t_check = time.perf_counter()
    numbers = gen.compare()
    log(f"check {time.perf_counter() - t_check:.3f} s")
    limits = traffic["limits"]
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in numbers.items() if name in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics: Dict[str, dict] = {}
    if not trace:
        for m in bench["end_to_end"]:
            if not applies(m, workload):
                continue
            v = setup_s if m["name"] == "setup_s" else win["e2e"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = {"kind": traffic["kind"], "counters": win["counters"],
               "trace": reduced, "window_s": win["seconds"],
               "chips": chips, "peaks": device_peaks,
               "precision": config["matmul_precision"]}
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": win["scenarios"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["info"] = dict(win["counters"], window_s=win["seconds"],
                       window_compile_requests=compiles, setup_s=setup_s,
                       not_compared={n: v for n, v in numbers.items()
                                     if n not in limits})
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return out
