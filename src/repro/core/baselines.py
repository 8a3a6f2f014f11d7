"""Clustered-FL baselines the paper compares against (Section V-A).

* **FedGroup** [arXiv:2010.06870] — static grouping by a data-driven
  measure: devices are clustered once (cosine similarity of their initial
  local updates, k-means in gradient space), then per-group FedAvg.
* **IFCA** [NeurIPS'20] — iterative: every round each device picks the
  model with the lowest loss on its local data, trains it, and models are
  aggregated over their adopters.
* **FeSEM** [arXiv:2005.01026] — multi-center EM: devices are assigned to
  the nearest center in parameter space after a local step; centers move
  to the weighted mean of their members.

All train M model instances.  Reporting matches the paper's columns:
``best`` (*) = highest test AUROC of any single instance; ``multi`` (†) =
per-sample min reconstruction error over instances (the multi-model
oracle score).

Like :mod:`repro.core.simulate`, the whole engine is a pure *core*
function whose only dynamic inputs are ``(dx, counts, valid, tx,
model_valid, trace, seed)`` — everything else (scheme, rounds, ...) is
closed over statically, and ``cfg.num_models`` is only the STATIC model
axis length: the live-model count arrives in the ``model_valid`` mask,
so (scheme, M) sweep cells padded to a common max M share one compiled
executable (:func:`repro.core.campaign.sweep_grid`'s fused dispatch).
The trace split into client events (device mask) and server events
(group mask) happens in-graph, so the core ``vmap``s over stacked
traces: :func:`repro.core.campaign.run_multimodel_campaign` sweeps a
whole (trace x seed) grid through ONE compiled executable, while
:func:`run_multimodel` stays the single-scenario entry point on the
same cached jitted core.  Declaratively, a multi-model cell is just a
``CellSpec("ifca", M)`` in an :class:`repro.core.experiment.
ExperimentSpec` — the planner groups same-scheme cells into one
padded-M bucket.

Failure semantics: a *client* failure removes that device; a *server*
failure kills the aggregator of group 0 — that instance freezes and its
devices stop contributing (they keep their last model for evaluation).

Default targets differ by encoding: a legacy ``FailureSpec`` with
``device=None`` kills device N-1 here (there are no cluster heads to
anchor the Tol-FL default), while a ``FailureTrace`` carries explicit
device ids resolved against the Tol-FL topology at construction time.
Pass an explicit ``device`` when comparing the two encodings.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.failure import (Failure, FailureTrace, KIND_CODES,
                                MAX_EVENTS, NO_FAILURE, PAD_EPOCH,
                                trace_alive_mask, trace_faulty_scale)
from repro.models import detector as D
from repro.models.detector import ModelLike
from repro.training.metrics import auroc_batch


@dataclass(frozen=True)
class MultiModelConfig:
    scheme: str = "ifca"          # fedgroup | ifca | fesem
    num_devices: int = 10
    num_models: int = 3
    rounds: int = 100
    lr: float = 1e-4
    dropout: bool = True
    seed: int = 0


@dataclass(frozen=True)
class FaultyMultiModelConfig(MultiModelConfig):
    """Faulty-update variant of the multi-model engine: per-device
    deltas are scaled by the ORIGINAL trace's faulty channel before the
    per-model aggregation (assignment probes stay clean — a faulty
    device corrupts what it sends, not how it measures).  A distinct
    frozen subclass for the same reason as
    :class:`repro.core.simulate.FaultySimConfig`: class identity keys
    the cached cores/fingerprints while plain-config keys stay
    bit-identical."""
    faulty_updates: bool = True


@dataclass
class MultiModelResult:
    best_auroc: float             # the paper's * column
    multi_auroc: float            # the paper's dagger column
    loss_curve: np.ndarray
    assignments: np.ndarray       # final device -> model map


class MultiOutputs(NamedTuple):
    """Raw in-graph outputs of one multi-model scenario (pre-AUROC)."""
    losses: jax.Array             # (rounds,) per-sample-min test loss
    final_scores: jax.Array       # (M, T) per-instance anomaly scores
    assignments: jax.Array        # (N,) final device -> model map


def _grad_fn(model: ModelLike, dropout: bool):
    det = D.as_detector(model)

    def local_loss(params, x, valid, key):
        return det.loss(params, x, valid, key if dropout else None)
    return local_loss, jax.grad(local_loss)


def _flat(tree):
    return jnp.concatenate([t.ravel() for t in jax.tree.leaves(tree)])


def _kmeans_groups(vectors: jax.Array, m: int, key: jax.Array,
                   iters: int = 20,
                   center_valid: Optional[jax.Array] = None) -> jax.Array:
    """Tiny in-graph k-means for FedGroup's static gradient-similarity
    grouping (cosine metric: rows are L2-normalised).

    Traceable/vmappable: centers seed from a random permutation of the
    data, and a group that empties during Lloyd iterations is RE-SEEDED
    on a random data point instead of keeping a stale center.

    ``center_valid`` (shape ``(m,)``, 1 = real) supports the padded-M
    sweep path: padded center slots never win the assignment argmax, and
    every per-center random draw is keyed on the CENTER INDEX (scalar
    ``fold_in`` draws, not one shape-``(m,)`` draw), so the assignment
    of the valid centers is bit-identical whatever ``m`` they are padded
    to.
    """
    n = vectors.shape[0]
    if m > n:
        raise ValueError(
            f"FedGroup k-means needs num_models <= num_devices to seed "
            f"distinct centers; got num_models={m} > num_devices={n}")
    v = vectors / (jnp.linalg.norm(vectors, axis=1, keepdims=True) + 1e-9)
    init_key, reseed_key = jax.random.split(key)
    centers = v[jax.random.permutation(init_key, n)[:m]]

    def masked_assign(sim):
        if center_valid is not None:
            sim = jnp.where(center_valid[None, :] > 0, sim, -jnp.inf)
        return jnp.argmax(sim, axis=1)

    def step(centers, i):
        assign = masked_assign(v @ centers.T)
        onehot = jax.nn.one_hot(assign, m, dtype=v.dtype)      # (n, m)
        cnt = jnp.sum(onehot, axis=0)                          # (m,)
        means = onehot.T @ v / jnp.maximum(cnt[:, None], 1.0)
        means = means / (jnp.linalg.norm(means, axis=1,
                                         keepdims=True) + 1e-9)
        ki = jax.random.fold_in(reseed_key, i)
        idx = jax.vmap(lambda j: jax.random.randint(
            jax.random.fold_in(ki, j), (), 0, n))(jnp.arange(m))
        reseed = v[idx]
        return jnp.where((cnt > 0)[:, None], means, reseed), None

    centers, _ = jax.lax.scan(step, centers, jnp.arange(iters))
    return masked_assign(v @ centers.T)


def as_multimodel_trace(failure: Failure, num_devices: int,
                        max_events: int = MAX_EVENTS) -> FailureTrace:
    """Normalise a failure to a trace with the BASELINE default targets.

    A legacy single-event ``FailureSpec`` with ``device=None`` resolves
    to device N-1 for client failures (there are no cluster heads here)
    and to an arbitrary device for server failures (server events kill
    group 0 whatever device they name).
    """
    if isinstance(failure, FailureTrace):
        return failure
    if failure.kind == "none":
        return FailureTrace.none(max_events)
    device = failure.device
    if device is None:
        device = num_devices - 1 if failure.kind == "client" else 0
    ep = np.full((max_events,), PAD_EPOCH, np.int32)
    dev = np.full((max_events,), -1, np.int32)
    alv = np.ones((max_events,), np.float32)
    knd = np.zeros((max_events,), np.int32)
    ep[0], dev[0], alv[0] = failure.epoch, device, 0.0
    knd[0] = KIND_CODES[failure.kind]
    return FailureTrace(jnp.asarray(ep), jnp.asarray(dev),
                        jnp.asarray(alv), jnp.asarray(knd))


def _split_trace(trace: FailureTrace) -> Tuple[FailureTrace, FailureTrace]:
    """In-graph split of one trace into (client events -> device mask,
    server events -> group-0 mask).  Pure ``jnp.where`` on the kind
    codes, so it survives ``vmap`` over stacked traces; the slots of the
    other kind keep ``PAD_EPOCH`` and never fire."""
    is_client = trace.kinds == KIND_CODES["client"]
    is_server = trace.kinds == KIND_CODES["server"]
    client_tr = FailureTrace(
        epochs=jnp.where(is_client, trace.epochs, PAD_EPOCH),
        devices=trace.devices,
        alive_after=trace.alive_after,
        kinds=trace.kinds)
    # server events all target group 0, whatever device they named
    server_tr = FailureTrace(
        epochs=jnp.where(is_server, trace.epochs, PAD_EPOCH),
        devices=jnp.zeros_like(trace.devices),
        alive_after=trace.alive_after,
        kinds=trace.kinds)
    return client_tr, server_tr


def prepare_multimodel_arrays(device_x: np.ndarray,
                              device_counts: np.ndarray):
    dx = obs.to_device(device_x)
    counts = obs.to_device(device_counts, jnp.float32)
    valid = (jnp.arange(device_x.shape[1])[None, :]
             < counts[:, None]).astype(jnp.float32)
    return dx, counts, valid


def _build_multimodel_core(model: ModelLike, cfg: MultiModelConfig):
    """Pure scenario function: (dx, counts, valid, tx, model_valid,
    trace, seed) -> :class:`MultiOutputs`, mirroring
    ``simulate._build_core``.

    ``model_valid`` (shape ``(M,)``, 1 = real) is the multi-model twin of
    the single-model path's ``head_valid``: ``cfg.num_models`` is only
    the STATIC length of the model axis, and the number of LIVE model
    instances arrives in the mask, so cells of a (scheme, M) sweep padded
    to a common max M share ONE compiled executable per scheme
    (:func:`repro.core.campaign.sweep_grid`).  Padded model slots never
    win an assignment, aggregate zero devices (so they stay at init), and
    are masked out of the per-sample-min loss — live-model results are
    bit-identical to the unpadded build.  All-ones restores the legacy
    semantics exactly.

    PRNG discipline: the root key splits into disjoint streams for model
    inits, FedGroup's grouping probe (probe init / probe grads / k-means
    seeding), and the training scan — grouping and training randomness
    are uncorrelated.
    """
    N, M = cfg.num_devices, cfg.num_models
    det = D.as_detector(model)
    local_loss, grad_fn = _grad_fn(det, cfg.dropout)
    # faulty-update gate (static, class-level — see FaultyMultiModelConfig)
    faulty = bool(getattr(cfg, "faulty_updates", False))

    def core(dx, counts, valid, tx, model_valid, trace: FailureTrace,
             seed):
        key = jax.random.PRNGKey(seed)
        k_init, k_group, k_train = jax.random.split(key, 3)
        # M model instances with different inits
        models = []
        for j in range(M):
            models.append(det.init_params(jax.random.fold_in(k_init, j)))
        models = jax.tree.map(lambda *xs: jnp.stack(xs), *models)

        client_tr, server_tr = _split_trace(trace)
        # number of LIVE models (a traced scalar; == M when unpadded)
        m_live = jnp.maximum(jnp.sum(model_valid), 1.0).astype(jnp.int32)

        # ---- initial assignment ----
        with jax.named_scope("assign"):
            if cfg.scheme == "fedgroup":
                k_probe, k_pgrad, k_km = jax.random.split(k_group, 3)
                p0 = det.init_params(k_probe)
                g0 = jax.vmap(lambda x, v, k_: _flat(grad_fn(p0, x, v, k_)),
                              in_axes=(0, 0, 0))(
                    dx, valid, jax.random.split(k_pgrad, N))
                assign0 = _kmeans_groups(g0, M, k_km,
                                         center_valid=model_valid)
            else:
                assign0 = jnp.arange(N) % m_live

        def device_losses(models_, x, v, k_):
            """(M,) local loss of each model instance on one device."""
            return jax.vmap(lambda p: local_loss(p, x, v, k_))(models_)

        def round_fn(carry, epoch):
            # each stage under a jax.named_scope (obs.MULTI_SCOPES): HLO
            # op metadata only, the program is unchanged
            models_, assign, rkey = carry
            with jax.named_scope("local_grad"):
                rkey, dkey = jax.random.split(rkey)
                dkeys = jax.random.split(dkey, N)
            with jax.named_scope("alive_mask"):
                a_dev = trace_alive_mask(client_tr, N, epoch)
                a_grp = trace_alive_mask(server_tr, M, epoch)

            # ---- (re)assignment ----
            # padded model slots never win: their loss/distance is +inf
            with jax.named_scope("assign"):
                if cfg.scheme == "ifca":
                    losses = jax.vmap(device_losses,
                                      in_axes=(None, 0, 0, 0))(
                        models_, dx, valid, dkeys)          # (N, M)
                    losses = jnp.where(model_valid[None, :] > 0, losses,
                                       jnp.inf)
                    assign = jnp.argmin(losses, axis=1)
                elif cfg.scheme == "fesem":
                    # e-step: distance between one-step-updated local
                    # params and each center, in parameter space
                    def dev_assign(x, v, k_, a):
                        p_cur = jax.tree.map(lambda t: t[a], models_)
                        g = grad_fn(p_cur, x, v, k_)
                        upd = jax.tree.map(lambda p_, g_: p_ - cfg.lr * g_,
                                           p_cur, g)
                        fu = _flat(upd)
                        d = jax.vmap(lambda j: jnp.sum(jnp.square(
                            fu - _flat(jax.tree.map(lambda t: t[j],
                                                    models_)))))(
                            jnp.arange(M))
                        return jnp.argmin(jnp.where(model_valid > 0, d,
                                                    jnp.inf))
                    assign = jax.vmap(dev_assign)(dx, valid, dkeys, assign)
                # fedgroup: static

            # ---- local grads on the assigned model ----
            def dev_grad(x, v, k_, a):
                p_cur = jax.tree.map(lambda t: t[a], models_)
                return grad_fn(p_cur, x, v, k_)
            with jax.named_scope("local_grad"):
                gs = jax.vmap(dev_grad)(dx, valid, dkeys, assign)
            with jax.named_scope("combine"):
                if faulty:
                    # faulty channel lives on the ORIGINAL trace's shadow
                    # device range — _split_trace PADs kind-3 rows out of
                    # both client/server splits, so read ``trace``
                    # directly
                    fscale = trace_faulty_scale(trace, N, epoch)
                    gs = jax.tree.map(
                        lambda g_: g_ * fscale.reshape(
                            (-1,) + (1,) * (g_.ndim - 1)), gs)

                # ---- per-model weighted aggregation ----
                onehot = jax.nn.one_hot(assign, M,
                                        dtype=jnp.float32)       # (N, M)
                w = counts * a_dev
                denom = onehot.T @ w                              # (M,)

                def agg_leaf(gleaf):
                    flatg = gleaf.reshape(N, -1)
                    num = onehot.T @ (flatg * w[:, None])
                    mean = num / jnp.maximum(denom[:, None], 1e-30)
                    return mean.reshape((M,) + gleaf.shape[1:])
                g_m = jax.tree.map(agg_leaf, gs)
                upd_gate = ((denom > 0).astype(jnp.float32) * a_grp)
                models_ = jax.tree.map(
                    lambda p_, g_: p_ - cfg.lr * upd_gate.reshape(
                        (-1,) + (1,) * (g_.ndim - 1)) * g_,
                    models_, g_m)

            with jax.named_scope("test_scoring"):
                scores = jax.vmap(
                    lambda p: det.anomaly_scores(p, tx))(models_)
                # per-sample min over LIVE models only (padded slots hold
                # untrained inits whose scores must not leak into the
                # loss)
                tl = jnp.mean(jnp.min(
                    jnp.where(model_valid[:, None] > 0, scores, jnp.inf),
                    axis=0))
            return (models_, assign, rkey), tl

        (models, assign, _), losses = jax.lax.scan(
            round_fn, (models, assign0, k_train), jnp.arange(cfg.rounds))
        with jax.named_scope("final_scoring"):
            final_scores = jax.vmap(
                lambda p: det.anomaly_scores(p, tx))(models)
        return MultiOutputs(losses, final_scores, assign)

    return core


@functools.lru_cache(maxsize=32)
def _jitted_multimodel_core_cached(model: ModelLike,
                                   cfg: MultiModelConfig):
    return jax.jit(_build_multimodel_core(model, cfg))


def _jitted_multimodel_core(model: ModelLike,
                            cfg: MultiModelConfig):
    """Compiled single-scenario core, cached on static config (the seed
    field of ``cfg`` is ignored — seed is a dynamic argument; the model
    spec is canonicalised so the config and detector spellings of the
    same autoencoder share one cache entry)."""
    return _jitted_multimodel_core_cached(
        D.canonical_model_key(model), dataclasses.replace(cfg, seed=0))


def run_multimodel(model: ModelLike, device_x: np.ndarray,
                   device_counts: np.ndarray, test_x: np.ndarray,
                   test_y: np.ndarray, cfg: MultiModelConfig,
                   failure: Failure = NO_FAILURE) -> MultiModelResult:
    """Single-scenario entry point on the shared cached jitted core.

    A repeated call with a new failure/seed reuses the compiled
    executable; :func:`repro.core.campaign.run_multimodel_campaign`
    batches whole (trace x seed) grids through the same core.
    """
    trace = as_multimodel_trace(failure, cfg.num_devices)
    dx, counts, valid = prepare_multimodel_arrays(device_x, device_counts)
    tx = jnp.asarray(test_x)
    core = _jitted_multimodel_core(model, cfg)
    out = core(dx, counts, valid, tx,
               jnp.ones((cfg.num_models,), jnp.float32), trace,
               jnp.int32(cfg.seed))

    final_scores = np.asarray(out.final_scores)                # (M, T)
    per_model = auroc_batch(final_scores, np.asarray(test_y))
    multi = auroc_batch(final_scores.min(axis=0, keepdims=True),
                        np.asarray(test_y))[0]
    return MultiModelResult(float(np.max(per_model)), float(multi),
                            np.asarray(out.losses),
                            np.asarray(out.assignments))
