"""Pre-compiled batched score entry points for the anomaly service.

One bucket = one fixed batch size = ONE compiled executable, in the
style of SHARK-Engine's ``BatchGenerateService`` (fixed-size entry
points per batch bucket).  The core is deliberately tiny::

    (row_params, row, x) -> (B, W) anomaly scores

``row_params`` is the service's stacked parameter bank — row 0 the
global (cluster-head) model, rows ``1..N`` the isolated per-client
models (:class:`repro.serving.anomaly.bank.ModelBank`) — and ``row``
(a scalar operand) selects the ONE model the whole batch scores
against; the service groups a tick's windows by routed row and
dispatches one bucket call per distinct row.  Row selection is a
gather, so scoring a failed-over group against row ``c + 1`` is
bit-identical to scoring the isolated model directly: the routing
decision never touches the arithmetic.  Keeping the weights UNIFORM
across the batch is what makes the bucket as fast as a direct
``anomaly_scores`` call — XLA folds the shared-weight vmap into the
same big GEMMs (a per-request weight gather would materialise B copies
of the model and lower to strided per-example GEMMs, measured ~1.4x
slower at B=64).

Executables resolve through the same three-layer discipline as the
campaign's AOT path (:func:`repro.core.campaign.aot_executable`):
in-process memory cache -> persistent serialized-executable cache
(:mod:`repro.core.compilecache`, so a fresh process serves warm with
zero traces and zero XLA) -> lower + compile (and store).  The cache
key is the canonical detector-model key plus the abstract-argument
signature — shapes (bucket size, window length, feature dim, bank
height) live in the avals, everything program-changing lives in the
detector spec, mirroring ``campaign._exe_key``'s contract.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.campaign import (_AOT_COMPILER_OPTIONS, AotTimes,
                                 _avals_signature, _lowering_config)
from repro.core.failure import trace_alive_mask
from repro.models import detector as D
from repro.models.detector import ModelLike

_SCORE_CACHE: Dict[tuple, Any] = {}
_SCORE_LOCK = threading.Lock()


def _build_score_core(model: ModelLike):
    """(row_params, row, x) -> (B, W) scores; scalar ``row`` gathers one
    bank row, ``x`` is the (B, W, D) window batch scored against it
    (per-window via vmap — sequence detectors see each window whole)."""
    det = D.as_detector(model)

    def score(row_params, row, x):
        with jax.named_scope("score_bucket"):    # obs.SERVE_SCOPES
            rows = jax.tree.map(lambda p: p[row], row_params)
            return jax.vmap(det.anomaly_scores, in_axes=(None, 0))(rows, x)

    return score


@functools.lru_cache(maxsize=32)
def _jitted_score(model: ModelLike):
    """Jitted score core, lru-cached on the CANONICAL model key (the
    caller normalises) — the trace cache the AOT path lowers through,
    so an AOT compile followed by a jit call retraces nothing."""
    return jax.jit(_build_score_core(model))


def score_executable(model: ModelLike, abstract_args
                     ) -> Tuple[Any, AotTimes]:
    """Compiled bucket entry point for ``abstract_args`` (the
    ``(row_params, row, x)`` aval tuple): memory -> disk -> compile,
    exactly the campaign AOT resolution.  Returns
    ``(compiled, AotTimes)``; ``compiled(*concrete)`` is bit-identical
    to the jitted call (same lowering)."""
    from repro.core import compilecache as _cc
    _cc.ensure_persistent_cache()
    key = (("serve_score", D.canonical_model_key(model))
           + _avals_signature(abstract_args) + _lowering_config())
    with _SCORE_LOCK:
        hit = _SCORE_CACHE.get(key)
    if hit is not None:
        return hit, AotTimes(source="memory")
    fp = _cc.exe_fingerprint(key)
    t0 = time.perf_counter()
    loaded = _cc.load_executable(fp)
    if loaded is not None:
        with _SCORE_LOCK:
            loaded = _SCORE_CACHE.setdefault(key, loaded)
        return loaded, AotTimes(compile_s=time.perf_counter() - t0,
                                source="disk")
    jitted = _jitted_score(D.canonical_model_key(model))
    t0 = time.perf_counter()
    lowered = jitted.lower(*abstract_args)
    t1 = time.perf_counter()
    compiled = lowered.compile(compiler_options=_AOT_COMPILER_OPTIONS)
    t2 = time.perf_counter()
    _cc.store_executable(fp, compiled)
    with _SCORE_LOCK:
        compiled = _SCORE_CACHE.setdefault(key, compiled)
    return compiled, AotTimes(lower_s=t1 - t0, compile_s=t2 - t1)


def alive_table(trace, num_devices: int, n_epochs: int):
    """(n_epochs, num_devices) float liveness table — every epoch's
    :func:`~repro.core.failure.trace_alive_mask` in ONE vmapped device
    call, precomputed at service construction so a tick indexes a host
    array instead of dispatching eager ops (measured ~1 ms/tick,
    comparable to a whole 64-bucket dispatch)."""
    return np.asarray(jax.vmap(
        lambda e: trace_alive_mask(trace, num_devices, e))(
            jnp.arange(n_epochs, dtype=jnp.int32)))


def score_budget_name(family: str = "ae") -> str:
    """The plancheck eqn budget governing the batched score core (one
    named ceiling per detector ``budget_family``, like
    :func:`repro.analysis.plancheck.budgets.bucket_budget_name`)."""
    return ("serving_score_core" if family == "ae"
            else f"serving_score_core:{family}")


def clear_score_cache() -> None:
    """Drop the in-process compiled-bucket cache (the persistent disk
    cache is untouched, mirroring ``campaign.clear_executable_caches``)."""
    with _SCORE_LOCK:
        _SCORE_CACHE.clear()
    _jitted_score.cache_clear()
