"""AOT execution-path contracts (kill-the-cold-compile-tax PR).

What is proven:

* **bit-identical parity** — ``ExecPlan(aot=True)`` routes every bucket
  through plan-time ``lower().compile()`` executables and produces the
  SAME result arrays as the jit path (it is the same lowering), with
  exactly one trace per bucket cold and ZERO traces warm
  (``cache == "memory"``); the speculative plan-time aval predictions
  match the concrete arrays (``aval_match``).
* **disk-warm in-process** — after :func:`clear_executable_caches` the
  AOT path deserialises whole executables from the persistent cache
  (``cache == "disk"``, zero traces) and the results stay identical.
* **warn-once** — ``ExecPlan(shard=True)`` on a single-device host
  emits its degrade warning EXACTLY once per ``execute()``, and never
  during ``plan()`` (it used to fire zero or twice depending on the
  entry point).
* **cross-process disk cache** — a second process running the same spec
  against the same ``JAX_COMPILATION_CACHE_DIR`` reports zero XLA
  compiles, zero traces and a bit-identical result digest.
"""
import dataclasses
import json
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro.api import (AutoencoderConfig, CellSpec, DataSpec, ExecPlan,
                       ExperimentSpec, SeedSpec, SimConfig, TraceSpec,
                       execute, plan, run_experiment)
from repro.core import campaign
from repro.core.failure import sample_traces
from repro.data import commsml, federated

# distinct from every other campaign test in the suite: the executable
# cache is global and the cold-trace-count assertions need cold keys
ROUNDS = 6


@pytest.fixture(scope="module")
def small_ae():
    return AutoencoderConfig(input_dim=commsml.N_FEATURES, hidden=(16,),
                             code_dim=4, dropout=0.2)


@pytest.fixture(scope="module")
def small_data():
    X, y = commsml.generate(seed=0, samples_per_class=60)
    split = federated.make_split(X, y, num_devices=10, num_clusters=5,
                                 anomaly_classes=[3], seed=0)
    dx, counts = federated.pad_devices(split)
    return dx, counts, split.test_x, split.test_y


def _spec(small_ae, small_data, *, aot, shard=False):
    dx, counts, tx, ty = small_data
    base = SimConfig(num_devices=10, rounds=ROUNDS, lr=1e-3,
                     dropout=False)
    tcfg = dataclasses.replace(base, scheme="tolfl", num_clusters=5)
    traces = sample_traces(np.random.default_rng(3), tcfg.topology(), 0.5,
                           max_events=8, rounds=ROUNDS, num_traces=2)
    return ExperimentSpec(
        data=DataSpec(model=small_ae, device_x=dx, device_counts=counts,
                      test_x=tx, test_y=ty, name="commsml"),
        base=base,
        # one fused non-fl single bucket + one fl iso bucket + one
        # multi bucket = all three executable kinds, 3 buckets
        cells=(CellSpec("tolfl", 2), CellSpec("fl", 1),
               CellSpec("ifca", 2)),
        traces=TraceSpec(traces=tuple(traces)), seeds=SeedSpec((0,)),
        exec_plan=ExecPlan(shard=shard, aot=aot) if (aot or shard)
        else None)


def _assert_identical(res_a, res_b):
    for a, b in zip(res_a.results, res_b.results):
        assert type(a) is type(b)
        for f in dataclasses.fields(a):
            va = getattr(a, f.name)
            if isinstance(va, np.ndarray):
                np.testing.assert_array_equal(va, getattr(b, f.name),
                                              err_msg=f.name)


# ---------------------------------------------------------------------------
# parity: AOT == jit, bit for bit
# ---------------------------------------------------------------------------
def test_aot_bit_identical_to_jit(small_ae, small_data):
    before = campaign.TRACE_COUNT
    res_jit = run_experiment(_spec(small_ae, small_data, aot=False))
    assert campaign.TRACE_COUNT - before == 3      # one per bucket
    rep = res_jit.compile_report
    assert rep is not None and not rep.aot
    assert [b.cache for b in rep.buckets] == ["", "", ""]
    assert rep.execute_s > 0

    # cold AOT: plan-time lowering, same trace budget, same bits
    campaign.clear_executable_caches()
    before = campaign.TRACE_COUNT
    res_aot = run_experiment(_spec(small_ae, small_data, aot=True))
    assert campaign.TRACE_COUNT - before == 3
    rep = res_aot.compile_report
    assert rep.aot and rep.traces == 3
    assert all(b.aot for b in rep.buckets)
    assert [b.cache for b in rep.buckets] == ["compiled"] * 3
    # the speculative plan-time avals matched the concrete arrays: the
    # thread-pool compiles were the ones actually used
    assert [b.aval_match for b in rep.buckets] == [True] * 3
    assert all(b.compile_s > 0 for b in rep.buckets)
    _assert_identical(res_jit, res_aot)

    # warm AOT: zero traces, executables straight from process memory
    before = campaign.TRACE_COUNT
    res_warm = run_experiment(_spec(small_ae, small_data, aot=True))
    assert campaign.TRACE_COUNT - before == 0
    assert [b.cache for b in res_warm.compile_report.buckets] == \
        ["memory"] * 3
    _assert_identical(res_jit, res_warm)


def test_aot_disk_warm_skips_tracing(small_ae, small_data):
    """In-process fresh-process simulation: empty executable caches +
    the populated persistent directory (written by the cold run above,
    pointed at the suite's hermetic tmp dir) -> whole executables
    deserialise, zero traces, identical bits."""
    res_ref = run_experiment(_spec(small_ae, small_data, aot=True))
    campaign.clear_executable_caches()
    before = campaign.TRACE_COUNT
    res_disk = run_experiment(_spec(small_ae, small_data, aot=True))
    assert campaign.TRACE_COUNT - before == 0, \
        "disk-warm AOT re-traced instead of deserialising"
    rep = res_disk.compile_report
    assert [b.cache for b in rep.buckets] == ["disk"] * 3
    assert rep.cache_dir is not None
    _assert_identical(res_ref, res_disk)


def test_matmul_precision_compiles_its_own_executables(small_ae,
                                                       small_data):
    """The default matmul precision changes the lowered program but not
    the avals: a run under another precision compiles its own bucket
    executables instead of reusing the default ones from memory or
    disk."""
    spec = _spec(small_ae, small_data, aot=True)
    run_experiment(spec)
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "float32")
    try:
        res = run_experiment(spec)
    finally:
        jax.config.update("jax_default_matmul_precision", prev)
    assert [b.cache for b in res.compile_report.buckets] == \
        ["compiled"] * 3


# ---------------------------------------------------------------------------
# warn-once (shard degrade)
# ---------------------------------------------------------------------------
def test_shard_degrade_warns_exactly_once_per_execute(small_ae, small_data):
    if jax.local_device_count() > 1:
        pytest.skip("host has multiple devices")
    spec = _spec(small_ae, small_data, aot=False, shard=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = plan(spec)                    # planning never warns
    for _ in range(2):                    # ... every execute warns ONCE
        with pytest.warns(UserWarning) as rec:
            execute(p)
        hits = [w for w in rec
                if "single local device" in str(w.message)]
        assert len(hits) == 1, [str(w.message) for w in rec]


# ---------------------------------------------------------------------------
# cross-process persistent cache
# ---------------------------------------------------------------------------
_SUBPROCESS_SCRIPT = r"""
import dataclasses, hashlib, json
import numpy as np
from repro.api import (AutoencoderConfig, CellSpec, DataSpec, ExecPlan,
                       ExperimentSpec, SeedSpec, SimConfig, TraceSpec,
                       run_experiment, xla_compile_stats)
from repro.core import campaign
from repro.core.failure import sample_traces
from repro.data import commsml, federated

X, y = commsml.generate(seed=0, samples_per_class=40)
split = federated.make_split(X, y, num_devices=6, num_clusters=2,
                             anomaly_classes=[3], seed=0)
dx, counts = federated.pad_devices(split)
ae = AutoencoderConfig(input_dim=commsml.N_FEATURES, hidden=(8,),
                       code_dim=3, dropout=0.2)
base = SimConfig(num_devices=6, rounds=3, lr=1e-3, dropout=False)
tcfg = dataclasses.replace(base, scheme="tolfl", num_clusters=2)
traces = sample_traces(np.random.default_rng(5), tcfg.topology(), 0.4,
                       max_events=6, rounds=3, num_traces=2)
spec = ExperimentSpec(
    data=DataSpec(model=ae, device_x=dx, device_counts=counts,
                  test_x=split.test_x, test_y=split.test_y,
                  name="commsml"),
    base=base, cells=(CellSpec("tolfl", 2),),
    traces=TraceSpec(traces=tuple(traces)), seeds=SeedSpec((0,)),
    exec_plan=ExecPlan(aot=True))
res = run_experiment(spec)
r = res.results[0]
h = hashlib.sha256()
h.update(np.ascontiguousarray(r.auroc_used).tobytes())
h.update(np.ascontiguousarray(r.final_auroc).tobytes())
h.update(np.ascontiguousarray(r.loss_curves).tobytes())
print(json.dumps({
    "traces": campaign.TRACE_COUNT,
    "stats": xla_compile_stats(),
    "caches": [b.cache for b in res.compile_report.buckets],
    "digest": h.hexdigest(),
}))
"""


def test_disk_cache_second_process_zero_xla_compiles(tmp_path):
    """The disk-cache contract: a fresh process re-running a spec
    against a populated ``JAX_COMPILATION_CACHE_DIR`` performs ZERO XLA
    compiles and ZERO traces — every bucket executable deserialises
    whole — and the results are bit-identical to the process that
    compiled them."""
    # repro is a namespace package (__file__ is None): derive src/ from
    # a real module inside it
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(campaign.__file__))))
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p))

    def run_once():
        out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run_once()
    assert first["traces"] == 1                   # one bucket, compiled
    assert first["caches"] == ["compiled"]
    assert first["stats"]["exe_stores"] >= 1      # ... and persisted

    second = run_once()
    assert second["traces"] == 0, "second process re-traced"
    assert second["stats"]["misses"] == 0, \
        f"second process invoked XLA: {second['stats']}"
    assert second["stats"]["exe_hits"] >= 1
    assert second["caches"] == ["disk"]
    assert second["digest"] == first["digest"]
