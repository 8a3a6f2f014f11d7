"""Pluggable detector bodies: interface, cache-key identity, parity pins.

Three contracts guard the DetectorModel seam
(:mod:`repro.models.detector`):

* **Bit-identity for autoencoder specs** — threading the interface
  through simulate/baselines/campaign must not move a single bit for
  the paper autoencoder: the executable-cache key tuples, the
  persistent-cache fingerprints AND a whole campaign's result digest
  are pinned against values captured on the pre-refactor tree.
* **Deprecation alias** — ``DataSpec(ae_cfg=...)`` keeps working and
  emits exactly ONE ``DeprecationWarning`` per process.
* **Second body end-to-end** — a :class:`SeqDetector` (RG-LRU windowed
  sequence detector) campaign runs through ``plan(check=True)`` →
  ``execute`` with a clean static-analysis report, under its own named
  plancheck budgets.
"""
import dataclasses
import hashlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CellSpec, DataSpec, ExperimentSpec, SeedSpec,
                       SimConfig, TraceSpec, execute, plan)
from repro.configs.autoencoder_paper import AutoencoderConfig
from repro.core import campaign, compilecache
from repro.core import experiment as _x
from repro.core.failure import NO_FAILURE, FailureSpec
from repro.core.simulate import comm_mb_per_round
from repro.data import commsml
from repro.models import detector as D

# a whole tolfl/fl/ifca campaign's result arrays, hashed in execution
# order.  Captured on the pre-refactor tree under jax 0.4.37 (see the
# module docstring), then re-captured with the engine unchanged under
# jax/jaxlib 0.9.0 on the CPU, whose XLA and RNG defaults give
# different float bits.
PRE_REFACTOR_DIGEST = \
    "aaa3b9c40394ddcaf10b90e0525a8859288c2dbea8981284a2db6f6a2520218a"
# sha256(repr(_exe_key(...))) of a representative fused single key
PRE_REFACTOR_FINGERPRINT = \
    "7c37de1094b392f2ceec5b6c253dd57e8e44e6100e5c081c63bd3bfe7fc87c13"


# ---------------------------------------------------------------------------
# interface + registry
# ---------------------------------------------------------------------------
def test_as_detector_normalises_and_rejects(tiny_ae_cfg):
    det = D.as_detector(tiny_ae_cfg)
    assert isinstance(det, D.AutoencoderDetector)
    assert det.cfg == tiny_ae_cfg
    assert D.as_detector(det) is det
    with pytest.raises(TypeError):
        D.as_detector("not a model")


def test_canonical_key_collapses_both_spellings(tiny_ae_cfg):
    det = D.AutoencoderDetector(tiny_ae_cfg)
    assert D.canonical_model_key(det) == tiny_ae_cfg
    assert D.canonical_model_key(tiny_ae_cfg) == tiny_ae_cfg
    seq = D.SeqDetector(input_dim=16, window=8, d_model=4)
    assert D.canonical_model_key(seq) == seq


def test_registry_roundtrip_and_replacement_guard():
    assert set(D.detector_names()) >= {"autoencoder", "seq-rglru"}
    det = D.make_detector("seq-rglru", input_dim=16, window=8, d_model=4)
    assert isinstance(det, D.SeqDetector)
    with pytest.raises(KeyError):
        D.make_detector("nope")
    # idempotent same-factory re-register; silent replacement forbidden
    D.register_detector("seq-rglru", D.SeqDetector)
    with pytest.raises(ValueError):
        D.register_detector("seq-rglru", D.AutoencoderDetector)
    assert D.SeqDetector in D.spec_classes()


def test_param_sizes_derive_from_real_init():
    from repro.models import params as P
    ae = D.AutoencoderDetector(
        AutoencoderConfig(input_dim=8, hidden=(4,), code_dim=2))
    params = ae.init_params(jax.random.PRNGKey(0))
    assert ae.param_count() == P.param_count(params)
    assert ae.param_bytes() == P.param_bytes(params)
    seq = D.SeqDetector(input_dim=16, window=8, d_model=4)
    assert seq.param_bytes() == P.param_bytes(
        seq.init_params(jax.random.PRNGKey(0)))


def test_comm_cost_accepts_detector_or_bytes():
    seq = D.SeqDetector(input_dim=16, window=8, d_model=4)
    via_det = comm_mb_per_round("tolfl", 10, 5, seq)
    via_int = comm_mb_per_round("tolfl", 10, 5, seq.param_bytes())
    assert via_det == via_int > 0


def test_seq_detector_loss_and_scores_shapes():
    seq = D.SeqDetector(input_dim=commsml.N_FEATURES, window=16,
                        d_model=8, dropout=0.2)
    params = seq.init_params(jax.random.PRNGKey(0))
    x = jnp.ones((5, commsml.N_FEATURES))
    valid = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0])
    loss_eval = seq.loss(params, x, valid)
    loss_drop = seq.loss(params, x, valid, key=jax.random.PRNGKey(1))
    assert loss_eval.shape == () and np.isfinite(float(loss_eval))
    assert float(loss_drop) != float(loss_eval)   # dropout engaged
    scores = seq.anomaly_scores(params, x)
    assert scores.shape == (5,)
    grads = jax.grad(lambda p: seq.loss(p, x, valid))(params)
    total = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert total > 0.0                            # gradients flow


# ---------------------------------------------------------------------------
# bit-identity pins (autoencoder specs, vs the pre-refactor tree)
# ---------------------------------------------------------------------------
def test_exe_key_bit_identical_for_both_spellings():
    cfg = SimConfig(scheme="tolfl", num_devices=6, num_clusters=2,
                    rounds=2, dropout=False)
    ae = AutoencoderConfig(input_dim=8, hidden=(4,), code_dim=2)
    key_raw = campaign._exe_key("single", ae, cfg, 4, None, False, True)
    key_det = campaign._exe_key("single", D.AutoencoderDetector(ae),
                                cfg, 4, None, False, True)
    assert key_raw == key_det
    assert key_raw == ("single", ae, cfg, 4, None, False, True)
    assert (compilecache.exe_fingerprint(key_raw)
            == PRE_REFACTOR_FINGERPRINT)


def test_campaign_digest_bit_identical(tiny_ae_cfg, tiny_padded,
                                       tiny_split):
    """The pre-refactor pin: a 3-cell tolfl/fl/ifca campaign (2 traces x
    2 seeds) through plan -> execute hashes to the digest captured
    BEFORE the DetectorModel seam existed — the refactor moved zero
    bits.  Both DataSpec spellings produce the same digest."""
    dx, counts = tiny_padded

    def digest(model_kwargs):
        spec = ExperimentSpec(
            data=DataSpec(device_x=dx, device_counts=counts,
                          test_x=tiny_split.test_x,
                          test_y=tiny_split.test_y, **model_kwargs),
            base=SimConfig(num_devices=10, rounds=3, lr=1e-3,
                           dropout=True),
            cells=(CellSpec("tolfl", 5), CellSpec("fl", 1),
                   CellSpec("ifca", 2)),
            traces=TraceSpec(traces=(NO_FAILURE,
                                     FailureSpec(1, "server"))),
            seeds=SeedSpec((0, 1)))
        res = execute(plan(spec))
        h = hashlib.sha256()
        for r in res.results:
            for name in ("auroc_used", "final_auroc", "loss_curves",
                         "best_auroc", "multi_auroc"):
                arr = getattr(r, name, None)
                if arr is not None:
                    h.update(np.ascontiguousarray(
                        np.asarray(arr, np.float64)).tobytes())
        return h.hexdigest()

    assert digest({"model": tiny_ae_cfg}) == PRE_REFACTOR_DIGEST
    assert (digest({"model": D.AutoencoderDetector(tiny_ae_cfg)})
            == PRE_REFACTOR_DIGEST)


# ---------------------------------------------------------------------------
# deprecation alias
# ---------------------------------------------------------------------------
def test_ae_cfg_alias_warns_exactly_once_per_process(tiny_ae_cfg,
                                                     tiny_padded,
                                                     tiny_split):
    dx, counts = tiny_padded
    kw = dict(device_x=dx, device_counts=counts,
              test_x=tiny_split.test_x, test_y=tiny_split.test_y)
    _x._AE_CFG_WARNED = False
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            d1 = DataSpec(ae_cfg=tiny_ae_cfg, **kw)
            d2 = DataSpec(ae_cfg=tiny_ae_cfg, **kw)
        dep = [w for w in caught
               if issubclass(w.category, DeprecationWarning)]
        assert len(dep) == 1, [str(w.message) for w in dep]
    finally:
        _x._AE_CFG_WARNED = True
    # the alias normalises into model AND reads back as the raw config
    for d in (d1, d2):
        assert isinstance(d.model, D.AutoencoderDetector)
        assert d.ae_cfg == tiny_ae_cfg
    # the model= spelling never warns, and non-autoencoder bodies read
    # back ae_cfg=None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        dm = DataSpec(model=tiny_ae_cfg, **kw)
        ds = DataSpec(model=D.SeqDetector(input_dim=commsml.N_FEATURES),
                      **kw)
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)]
    assert dm.ae_cfg == tiny_ae_cfg and ds.ae_cfg is None
    with pytest.raises(TypeError):
        DataSpec(**kw)          # no model at all


# ---------------------------------------------------------------------------
# second body end-to-end
# ---------------------------------------------------------------------------
def test_seq_detector_campaign_end_to_end(tiny_padded, tiny_split):
    """A SeqDetector campaign through plan(check=True) -> execute: the
    static analyzer is CLEAN (the seq budget family covers its bigger
    cores), results are finite, and warm re-execution costs 0 traces."""
    dx, counts = tiny_padded
    seq = D.SeqDetector(input_dim=commsml.N_FEATURES, window=16,
                        d_model=8)
    spec = ExperimentSpec(
        data=DataSpec(model=seq, device_x=dx, device_counts=counts,
                      test_x=tiny_split.test_x,
                      test_y=tiny_split.test_y, name="seq-e2e"),
        base=SimConfig(num_devices=10, rounds=2, lr=1e-3,
                       dropout=False),
        cells=(CellSpec("tolfl", 2), CellSpec("fl", 1),
               CellSpec("ifca", 2)),
        traces=TraceSpec(traces=(NO_FAILURE, FailureSpec(1, "server"))),
        seeds=SeedSpec((0,)))
    p = plan(spec, check=True)
    assert p.static_report().clean, p.describe()
    res = execute(p)
    assert res.num_scenarios == 6
    for key, r in res.per_cell().items():
        auroc = (r.auroc_used if hasattr(r, "auroc_used")
                 else r.best_auroc)
        assert np.all(np.isfinite(auroc)), (key, auroc)
        assert np.all((auroc >= 0.0) & (auroc <= 1.0)), (key, auroc)
    # warm re-execution: the detector-keyed executables are cached
    t0 = campaign.TRACE_COUNT
    execute(plan(spec))
    assert campaign.TRACE_COUNT == t0


def test_seq_buckets_fall_under_named_seq_budgets(tiny_padded,
                                                  tiny_split):
    from repro.analysis.plancheck import budgets as pc_budgets
    dx, counts = tiny_padded
    seq = D.SeqDetector(input_dim=commsml.N_FEATURES, window=16,
                        d_model=8)
    data = DataSpec(model=seq, device_x=dx, device_counts=counts,
                    test_x=tiny_split.test_x, test_y=tiny_split.test_y)
    spec = ExperimentSpec(
        data=data,
        base=SimConfig(num_devices=10, rounds=2, lr=1e-3,
                       dropout=False),
        cells=(CellSpec("tolfl", 2), CellSpec("ifca", 2)),
        traces=TraceSpec(traces=(NO_FAILURE,)), seeds=SeedSpec((0,)))
    p = plan(spec)
    for b in p.buckets:
        cells = [p.cells[i] for i in b.cell_indices]
        avals = _x._bucket_avals(data, b, cells)
        jitted = campaign._executable(*_x._bucket_exe_args(data, b))
        n = pc_budgets.count_jaxpr(jax.make_jaxpr(jitted)(*avals))
        name = pc_budgets.bucket_budget_name(b.kind, b.fused,
                                             seq.budget_family)
        assert name.endswith(":seq"), name
        assert pc_budgets.check_budget(name, n) is None, (name, n)
    # an unknown family is a finding, not a KeyError
    f = pc_budgets.check_budget("campaign_core_single:unknown", 1, "x")
    assert f is not None and "no named budget" in f.message


def test_seq_and_ae_executables_never_alias(tiny_ae_cfg):
    cfg = SimConfig(scheme="tolfl", num_devices=6, num_clusters=2,
                    rounds=2, dropout=False)
    seq = D.SeqDetector(input_dim=commsml.N_FEATURES, window=16,
                        d_model=8)
    k_ae = campaign._exe_key("single", tiny_ae_cfg, cfg, 4, None,
                             False, True)
    k_seq = campaign._exe_key("single", seq, cfg, 4, None, False, True)
    assert k_ae != k_seq
    assert (compilecache.exe_fingerprint(k_ae)
            != compilecache.exe_fingerprint(k_seq))
    # replacing only a detector hyperparameter forks the key too
    k_seq2 = campaign._exe_key(
        "single", dataclasses.replace(seq, d_model=4), cfg, 4, None,
        False, True)
    assert k_seq2 != k_seq
