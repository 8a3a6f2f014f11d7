"""The program's spans and counters (``repro.obs``).

What is proven:

* spans and counters under four threads: each thread's
  :class:`~repro.obs.Tally` adds up its own spans and counts, and holds
  nothing of the other threads';
* one ``execute()``: per bucket the five stage times sum to
  ``execute_s`` within 1 ms, and the execution appends exactly one
  registry record;
* every stage scope is in the debug-info text of the lowered
  single-model, multi-model and serving cores;
* in a CPU profiler trace the ``campaign.*`` spans lie inside the
  enclosing ``execute`` annotation on one timeline (the shared clock);
* the fused output-layer kernel by shape: a 112-wide core lowered for
  the TPU has no kernel call, a 1024-wide one exactly one per local
  step (and none lowered for the CPU); an execution record counts the
  buckets that run it (``fused_out_buckets``);
* the GC hook logs a forced full collection and ignores generation 0;
* the executable cache's directory tag follows the cores' source
  digest.
"""
import gc
import glob
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import (NO_FAILURE, AutoencoderConfig, CellSpec, DataSpec,
                       ExecPlan, ExperimentSpec, FailureSpec, SeedSpec,
                       SimConfig, TraceSpec, execute, plan)
from repro.core import compilecache, experiment
from repro.core.baselines import (MultiModelConfig, _build_multimodel_core,
                                  as_multimodel_trace,
                                  prepare_multimodel_arrays)
from repro.core.failure import as_trace
from repro.core.simulate import _build_core_arrays, _prepare_arrays
from repro.data import commsml, federated
from repro.models.detector import as_detector
from repro.serving.anomaly import engine

# distinct from every other campaign test: the executable cache is global
ROUNDS = 3


@pytest.fixture(scope="module")
def small():
    ae = AutoencoderConfig(input_dim=commsml.N_FEATURES, hidden=(16,),
                           code_dim=4, dropout=0.2)
    X, y = commsml.generate(seed=1, samples_per_class=40)
    split = federated.make_split(X, y, num_devices=6, num_clusters=3,
                                 anomaly_classes=[3], seed=0)
    dx, counts = federated.pad_devices(split)
    return ae, dx, counts, split.test_x, split.test_y


def _spec(small):
    ae, dx, counts, tx, ty = small
    return ExperimentSpec(
        data=DataSpec(model=ae, device_x=dx, device_counts=counts,
                      test_x=tx, test_y=ty),
        base=SimConfig(num_devices=6, rounds=ROUNDS, lr=1e-3),
        cells=(CellSpec("tolfl", 3), CellSpec("fl", 1), CellSpec("ifca", 2)),
        traces=TraceSpec(traces=(NO_FAILURE, FailureSpec(1, "server"))),
        seeds=SeedSpec.range(2), exec_plan=ExecPlan(aot=True))


# ---------------------------------------------------------------------------
def test_spans_and_counts_under_four_threads():
    n_threads, n_iter = 4, 200
    tallies = [None] * n_threads
    entries = [0] * n_threads
    barrier = threading.Barrier(n_threads)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work(i):
        barrier.wait()
        with obs.Tally() as t:
            for _ in range(n_iter):
                with obs.span(f"test.thread{i}"):
                    with obs.span("test.shared"):
                        obs.count("test.counter", i + 1)
                        with obs.Tally() as inner:
                            obs.count("test.counter", 0.5)
                        # the inner tally saw its own count only
                        entries[i] += dict(inner.counters) == {
                            "test.counter": 0.5}
        tallies[i] = t

    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert entries == [n_iter] * n_threads
    for i, t in enumerate(tallies):
        # a tally sees its own thread's spans and nothing of the others'
        assert set(t.seconds) == {f"test.thread{i}", "test.shared"}
        assert set(t.counters) == {"test.counter"}
        # the outer tally also took every count of the nested one
        assert t.counters["test.counter"] == n_iter * (i + 1.5)
        assert 0 < t.seconds["test.shared"] <= t.seconds[f"test.thread{i}"]
        assert t.seconds[f"test.thread{i}"] <= t.end - t.start


def test_execute_stage_times_sum_to_execute_s(small):
    p = plan(_spec(small))
    n0 = len(obs.executions())
    res = execute(p)
    recs = obs.executions()
    assert len(recs) == min(n0 + 1, obs.MAX_EXECUTIONS)
    rec = recs[-1]
    report = res.compile_report
    assert len(report.buckets) == 3
    for b in report.buckets:
        stages = b.build_s + b.resolve_s + b.dispatch_s + b.fetch_s + b.post_s
        assert abs(stages - b.execute_s) < 1e-3, b
        assert b.build_s > 0 and b.dispatch_s > 0 and b.fetch_s > 0
    for stage in ("build", "resolve", "dispatch", "fetch", "post"):
        assert f"{stage}=" in report.describe()
        assert rec[f"{stage}_s"] == pytest.approx(
            sum(getattr(b, f"{stage}_s") for b in report.buckets))
    assert rec["scenarios"] == p.num_scenarios
    assert rec["wall_s"] >= report.execute_s
    # every bucket moves its device_x, counts and test_x at least
    ae, dx, counts, tx, _ = small
    assert rec["h2d_bytes"] >= 3 * (dx.nbytes + tx.nbytes)
    assert 0.0 <= rec["gc_s"] <= rec["wall_s"]


def _scopes_in(fn, *args) -> set:
    """The stage scopes named in the debug info of ``fn``'s lowering (a
    scan body's names start at its own scope: ``alive_mask/add``)."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return {part for name in re.findall(r'loc\("([^"]*)"', text)
            for part in name.split("/")[:-1]}


@pytest.mark.parametrize("track_iso", [False, True])
def test_single_core_has_every_scope(small, track_iso):
    ae, dx, counts, tx, _ = small
    cfg = SimConfig(scheme="tolfl", num_devices=6, num_clusters=3,
                    rounds=ROUNDS, lr=1e-3, local_epochs=2)
    topo = cfg.topology()
    core = _build_core_arrays(ae, cfg, 6, 3, track_iso=track_iso,
                              score_history=False)
    d, c, v = _prepare_arrays(cfg, dx, counts)
    found = _scopes_in(
        core, d, c, v, jnp.asarray(tx),
        jnp.asarray(topo.device_cluster_array()),
        jnp.asarray(np.array(topo.heads)), jnp.ones((3,), jnp.float32),
        as_trace(FailureSpec(1, "server"), topo), 0)
    want = obs.SINGLE_SCOPES if track_iso else tuple(
        s for s in obs.SINGLE_SCOPES if s != "iso_fallback")
    assert set(want) <= found, set(want) - found


@pytest.mark.parametrize("scheme", ["ifca", "fesem", "fedgroup"])
def test_multi_core_has_every_scope(small, scheme):
    ae, dx, counts, tx, _ = small
    cfg = MultiModelConfig(scheme=scheme, num_devices=6, num_models=2,
                           rounds=ROUNDS)
    core = _build_multimodel_core(ae, cfg)
    d, c, v = prepare_multimodel_arrays(dx, counts)
    found = _scopes_in(core, d, c, v, jnp.asarray(tx),
                       jnp.ones((2,), jnp.float32),
                       as_multimodel_trace(NO_FAILURE, 6), 0)
    assert set(obs.MULTI_SCOPES) <= found, set(obs.MULTI_SCOPES) - found


def test_score_core_has_its_scope(small):
    ae, _, _, tx, _ = small
    score = engine._build_score_core(ae)
    rows = jax.tree.map(lambda p: jnp.stack([p, p]),
                        as_detector(ae).init_params(jax.random.PRNGKey(0)))
    x = jnp.asarray(tx[:32].reshape(2, 16, -1))
    found = _scopes_in(score, rows, jnp.int32(1), x)
    assert set(obs.SERVE_SCOPES) <= found


# ---------------------------------------------------------------------------
# the fused output layer, by shape
# ---------------------------------------------------------------------------
WIDE = AutoencoderConfig(input_dim=1024, hidden=(16,), code_dim=4,
                         dropout=0.2)


@pytest.fixture(scope="module")
def wide(small):
    """``small``'s federation at the narrowest width that takes the
    kernel (its rows tiled to 1024 features)."""
    _, dx, counts, tx, ty = small
    reps = -(-WIDE.input_dim // dx.shape[-1])
    return (WIDE, np.tile(dx, reps)[..., :WIDE.input_dim], counts,
            np.tile(tx, reps)[:, :WIDE.input_dim], ty)


def _kernel_calls(core, data, track_iso, platform):
    ae, dx, counts, tx, _ = data
    cfg = SimConfig(scheme="tolfl", num_devices=6, num_clusters=3,
                    rounds=ROUNDS, lr=1e-3, local_epochs=2)
    topo = cfg.topology()
    d, c, v = _prepare_arrays(cfg, dx, counts)
    traced = jax.jit(core(ae, cfg, 6, 3, track_iso=track_iso,
                          score_history=False)).trace(
        d, c, v, jnp.asarray(tx), jnp.asarray(topo.device_cluster_array()),
        jnp.asarray(np.array(topo.heads)), jnp.ones((3,), jnp.float32),
        as_trace(FailureSpec(1, "server"), topo), 0)
    return traced.lower(lowering_platforms=(platform,)).as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("track_iso", [False, True])
def test_fused_output_layer_by_width(small, wide, track_iso):
    # the local steps are a scan: one call in its body is one per step
    assert _kernel_calls(_build_core_arrays, wide, track_iso, "tpu") == 1
    assert _kernel_calls(_build_core_arrays, wide, track_iso, "cpu") == 0
    assert _kernel_calls(_build_core_arrays, small, track_iso, "tpu") == 0
    assert as_detector(wide[0]).fuses_output_layer()
    assert not as_detector(small[0]).fuses_output_layer()


@pytest.mark.parametrize("backend,want", [("tpu", (2, 0)), ("cpu", (0, 0))])
def test_execution_record_counts_fused_buckets(small, wide, monkeypatch,
                                               backend, want):
    monkeypatch.setattr(experiment, "_backend", lambda: backend)
    got = []
    for data in (wide, small):
        ae, dx, counts, tx, ty = data
        execute(plan(ExperimentSpec(
            data=DataSpec(model=ae, device_x=dx, device_counts=counts,
                          test_x=tx, test_y=ty),
            base=SimConfig(num_devices=6, rounds=ROUNDS, lr=1e-3),
            cells=(CellSpec("tolfl", 3), CellSpec("fl", 1)),
            traces=TraceSpec(traces=(NO_FAILURE,)),
            seeds=SeedSpec.range(1))))
        got.append(obs.executions()[-1]["fused_out_buckets"])
    assert tuple(got) == want


def test_campaign_spans_share_the_profiler_clock(small, tmp_path):
    from jax.profiler import ProfileData
    p = plan(_spec(small))
    execute(p)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("execute"):
            execute(p)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    lines = [ln for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:") for ln in pl.lines]
    for ln in lines:
        evs = [(e.name, e.start_ns, e.end_ns) for e in ln.events]
        outer = [e for e in evs if e[0] == "execute"]
        if not outer:
            continue
        _, lo, hi = outer[0]
        stages = [e for e in evs if e[0] in obs.CAMPAIGN_STAGES]
        assert {e[0] for e in stages} >= {"campaign.build",
                                          "campaign.dispatch",
                                          "campaign.fetch", "campaign.post"}
        for name, s, e in stages:
            assert lo <= s <= e <= hi, (name, s, e, lo, hi)
        return
    pytest.fail("no 'execute' span on any host line of the trace")


def test_gc_hook_logs_full_collections_only():
    was_enabled = gc.isenabled()
    gc.disable()                     # no automatic collection meanwhile
    try:
        n0 = len(obs.gc_pauses())
        gc.collect(0)
        assert len(obs.gc_pauses()) == n0
        gc.collect()
        pauses = obs.gc_pauses()
    finally:
        if was_enabled:
            gc.enable()
    assert len(pauses) == n0 + 1
    s, e = pauses[-1]
    assert s <= e
    assert obs.gc_pause_s(s, e) == pytest.approx(e - s)
    assert obs.gc_pause_s(e, e + 1.0) == 0.0


def test_executable_cache_tag_follows_source_digest(monkeypatch, tmp_path):
    real = compilecache.source_digest
    real.cache_clear()
    d = real()
    assert len(d) == 16 and d == real()
    monkeypatch.setitem(compilecache._state, "dir", str(tmp_path))
    tag_a = compilecache._exe_dir()
    assert tag_a.endswith(f"-src-{d}")
    monkeypatch.setattr(compilecache, "source_digest", lambda: "0" * 16)
    assert compilecache._exe_dir() != tag_a
    # the digest reads every source's bytes: a changed file changes it,
    # and so does a new one, wherever it lies in the package
    src = tmp_path / "repro"
    for rel in ("core/simulate.py", "core/campaign.py", "core/sharding.py"):
        (src / rel).parent.mkdir(parents=True, exist_ok=True)
        (src / rel).write_text("x = 1\n")
    monkeypatch.setattr(compilecache, "_REPRO_DIR", str(src))
    digests = []
    try:
        for edit in (None, "core/campaign.py", "core/sharding.py",
                     "serving/new.py"):
            if edit is not None:
                (src / edit).parent.mkdir(parents=True, exist_ok=True)
                (src / edit).write_text("x = 2\n")
            real.cache_clear()
            digests.append(real())
    finally:
        real.cache_clear()
    assert len(set(digests)) == len(digests)
