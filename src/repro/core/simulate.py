"""Paper-scale Tol-FL simulator (Tables III-VI, Figures 4-5).

Simulates N federated devices training the paper's autoencoder with the
single-model schemes — Batch (centralised), FL (k=1), SBT (k=N),
Tol-FL (1<k<N) — under client / server failures.  The whole federation is
one jitted ``lax.scan`` over rounds: device gradients via ``vmap``, the
Tol-FL combine via the shared algebra in :mod:`repro.core.aggregation`,
failures via in-graph masks from :mod:`repro.core.failure`.

The round loop is factored into a pure *core* function whose only
dynamic inputs are (data arrays, failure trace, seed).  The core is
built once per static configuration (``_core_cache``), jitted, and
shared by

* :func:`run_simulation` — the single-scenario entry point (a repeated
  call with a new trace/seed reuses the compiled executable), and
* :mod:`repro.core.campaign` — which ``vmap``s the same core over a
  stacked batch of (trace, seed) scenarios so an entire Monte-Carlo
  sweep is ONE compile.  Whole (scheme, k) grids are declared through
  the spec -> plan -> execute pipeline on top
  (:mod:`repro.core.experiment` / :mod:`repro.api`);
  :func:`run_simulation` stays the scalar core beneath it.

FL server failure triggers the paper's fallback: remaining devices
continue training *isolated* local models (Section V-C / Fig 4); the
reported metric then averages the independent devices, exactly as the
paper's Fig 4 caption describes.

Multi-model baselines (FedGroup / IFCA / FeSEM) live in
:mod:`repro.core.baselines`.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aggregation as agg
from repro.core.failure import (Failure, FailureTrace, NO_FAILURE, as_trace,
                                effective_weights_arrays, trace_alive_mask,
                                trace_faulty_scale)
from repro.core.topology import Topology
from repro.models import detector as D
from repro.models.detector import ModelLike
from repro.training.metrics import auroc, auroc_batch


@dataclass(frozen=True)
class SimConfig:
    scheme: str = "tolfl"          # batch | fl | sbt | tolfl
    num_devices: int = 10
    num_clusters: int = 5          # k (tolfl); fl -> 1, sbt -> N
    rounds: int = 100
    lr: float = 1e-3
    local_epochs: int = 1          # E local steps per round
    combine: str = "streaming"     # streaming (faithful) | direct
    dropout: bool = True
    seed: int = 0

    def topology(self) -> Topology:
        if self.scheme == "batch":
            return Topology(1, 1)
        if self.scheme == "fl":
            return Topology(self.num_devices, 1)
        if self.scheme == "sbt":
            return Topology(self.num_devices, self.num_devices)
        return Topology(self.num_devices, self.num_clusters)


@dataclass(frozen=True)
class FaultySimConfig(SimConfig):
    """The faulty-update engine variant: identical training except the
    TRANSMITTED per-device deltas are scaled by the trace's faulty
    channel (:func:`repro.core.failure.trace_faulty_scale`) before the
    hierarchical combine — FedFm-style corrupted updates from devices
    that are otherwise alive.  Local/isolated training stays clean (the
    corruption happens on the wire, not on the device).

    A distinct SUBCLASS rather than a ``SimConfig`` field on purpose:
    dataclass ``__eq__``/``repr`` include the class, so faulty cores
    get their own cached-core and executable-fingerprint entries while
    every plain-config key and persisted fingerprint stays
    bit-identical.  ``plan()`` swaps cells onto this class whenever a
    ``TraceSpec`` declares a process with ``needs_faulty_engine``."""
    faulty_updates: bool = True


@dataclass
class SimResult:
    final_auroc: float
    iso_auroc: float               # mean of isolated devices (fl fallback)
    auroc_used: float              # what the paper would report
    loss_curve: np.ndarray         # (rounds,) REPORTED test loss: global
    #                                model, except that FL server-dead
    #                                rounds carry the isolated mean (Fig 4)
    auroc_curve: np.ndarray        # (rounds,) reported AUROC, same switch
    iso_loss_curve: np.ndarray     # (rounds,) alive-mean isolated loss
    iso_active: bool
    rounds_to_loss: Optional[int] = None


class SimOutputs(NamedTuple):
    """Raw in-graph outputs of one simulated scenario (pre-AUROC)."""
    losses: jax.Array            # (rounds,) global-model test loss
    iso_losses: jax.Array        # (rounds,) alive-mean isolated test loss
    final_scores: jax.Array      # (T,) anomaly scores of the final model
    iso_final_scores: jax.Array  # (N, T) per-device isolated scores
    final_alive: jax.Array       # (N,) alive mask at the last round
    server_dead: jax.Array       # () 1.0 iff every cluster head is dead
    server_dead_rounds: jax.Array  # (rounds,) 1.0 where all heads dead
    score_hist: jax.Array        # (rounds, T) or (rounds, 0) if untracked
    iso_score_hist: jax.Array    # (rounds, N, T) or (rounds, 0, 0)


def _device_grad_fn(model: ModelLike, dropout: bool):
    det = D.as_detector(model)

    def local_loss(params, x, valid, key):
        return det.loss(params, x, valid, key if dropout else None)
    return jax.grad(local_loss)


def _local_delta_fn(model: ModelLike, cfg: SimConfig):
    """E local SGD steps; returns the (negated-gradient-like) delta/lr.

    With E=1 this is exactly the local gradient (paper Algorithm 1)."""
    grad_fn = _device_grad_fn(model, cfg.dropout)

    def delta(params, x, valid, key):
        if cfg.local_epochs == 1:
            return grad_fn(params, x, valid, key)

        def step(p, i):
            g = grad_fn(p, x, valid, jax.random.fold_in(key, i))
            return jax.tree.map(lambda a, b: a - cfg.lr * b, p, g), None

        p_end, _ = jax.lax.scan(step, params, jnp.arange(cfg.local_epochs))
        # pseudo-gradient: (theta - theta_local) / lr
        return jax.tree.map(lambda a, b: (a - b) / cfg.lr, params, p_end)

    return delta


def _build_core_arrays(model: ModelLike, cfg: SimConfig,
                       num_devices: int, num_clusters: int,
                       track_iso: bool, score_history: bool,
                       return_params: bool = False):
    """Pure scenario function with the topology as DYNAMIC operands:
    (dx, counts, valid, tx, cluster_ids, heads, head_valid, trace, seed)
    -> :class:`SimOutputs`.

    ``num_clusters`` is only the STATIC length of the cluster axis; the
    actual structure arrives in the arrays, so cells of a (scheme, k)
    sweep that pad ``heads``/``head_valid`` to a common max-k share ONE
    compiled executable (:func:`repro.core.campaign.sweep_grid`).
    Padded cluster slots carry zero counts and an invalid head, which
    the combine algebra absorbs as exact no-ops — results are
    bit-identical to the per-cell build.  ``cfg.scheme`` is deliberately
    unread here (``track_iso`` replaces it) so one build serves
    fl/sbt/tolfl alike."""
    N = num_devices
    k = num_clusters
    det = D.as_detector(model)
    delta_fn = _local_delta_fn(det, cfg)
    # faulty-update engine gate: static (class-level), so plain configs
    # trace the byte-identical graph they always did
    faulty = bool(getattr(cfg, "faulty_updates", False))

    def core(dx, counts, valid, tx, cluster_ids, heads, head_valid,
             trace: FailureTrace, seed):
        key = jax.random.PRNGKey(seed)
        params = det.init_params(key)

        def test_loss(p):
            s = det.anomaly_scores(p, tx)
            return jnp.mean(s)

        def heads_alive_max(alive):
            """max over VALID heads only — padded head slots never argue
            the server back to life."""
            return jnp.max(jnp.where(head_valid > 0, alive[heads], 0.0))

        def round_fn(carry, epoch):
            # each stage under a jax.named_scope (obs.SINGLE_SCOPES):
            # HLO op metadata only, the program is unchanged
            params, iso_params, rkey = carry
            with jax.named_scope("local_delta"):
                rkey, dkey = jax.random.split(rkey)
            with jax.named_scope("alive_mask"):
                alive = trace_alive_mask(trace, N, epoch)
                w = effective_weights_arrays(alive, cluster_ids, heads)
            with jax.named_scope("local_delta"):
                dkeys = jax.random.split(dkey, N)
            with jax.named_scope("alive_mask"):
                head_dead = 1.0 - heads_alive_max(alive)  # all heads dead
            with jax.named_scope("local_delta"):
                if track_iso:
                    # One N-way delta vmap serves BOTH the global combine
                    # and the isolated fallback.  Exactness: while any
                    # head is alive, ``iso_params`` rows all equal
                    # ``params`` (the where-reset below), so these ARE
                    # the global-path gradients; on all-heads-dead rounds
                    # the values differ but every effective weight is
                    # zero, so the combine is gated off
                    # (``has_update == 0``) and the difference never
                    # reaches ``new_params``.  This halves the per-round
                    # gradient work of iso-tracking cores.
                    iso_params = jax.tree.map(
                        lambda ip, p_: jnp.where(
                            head_dead > 0, ip,
                            jnp.broadcast_to(p_, ip.shape)),
                        iso_params, params)
                    gs = jax.vmap(delta_fn, in_axes=(0, 0, 0, 0))(
                        iso_params, dx, valid, dkeys)
                else:
                    gs = jax.vmap(delta_fn, in_axes=(None, 0, 0, 0))(
                        params, dx, valid, dkeys)
            with jax.named_scope("combine"):
                gs_tx = gs
                if faulty:
                    # corrupt the TRANSMITTED deltas only: the isolated
                    # fallback below keeps the clean ``gs`` (faulty
                    # devices train fine locally, they just send garbage)
                    fscale = trace_faulty_scale(trace, N, epoch)
                    gs_tx = jax.tree.map(
                        lambda g_: g_ * fscale.reshape(
                            (-1,) + (1,) * (g_.ndim - 1)), gs)
                ns = counts * w
                # ---- Tol-FL hierarchical combine (Algorithm 1) ----
                cluster_gs, n_c = agg.cluster_reduce(gs_tx, ns,
                                                     cluster_ids, k)
                if cfg.combine == "streaming":
                    n_tot, g = agg.stacked_streaming_mean(cluster_gs, n_c)
                else:
                    g = agg.weighted_mean(cluster_gs, n_c)
                    n_tot = jnp.sum(n_c)
                has_update = (n_tot > 0).astype(jnp.float32)
                new_params = jax.tree.map(
                    lambda p_, g_: p_ - cfg.lr * has_update * g_, params,
                    g)

            # ---- isolated fallback (fl server failure) ----
            if track_iso:
                # ``iso_params`` already track the global model until
                # failure (the where-reset above) and ``gs`` holds the
                # per-device gradients at exactly those params
                with jax.named_scope("iso_fallback"):
                    iso_step = head_dead * alive  # only alive devices train
                    iso_params = jax.tree.map(
                        lambda ip, g_: ip - cfg.lr * iso_step.reshape(
                            (-1,) + (1,) * (g_.ndim - 1)) * g_,
                        iso_params, gs)
                # Fig 4 reporting averages the surviving devices only:
                # weight each device's test loss by its alive mask (the
                # dead server keeps a frozen model and is excluded)
                with jax.named_scope("test_scoring"):
                    per_dev_tl = jax.vmap(test_loss)(iso_params)
                    iso_tl = (jnp.sum(alive * per_dev_tl)
                              / jnp.maximum(jnp.sum(alive), 1.0))
            else:
                iso_tl = jnp.float32(0)

            with jax.named_scope("test_scoring"):
                tl = test_loss(new_params)
                if score_history:
                    scores = det.anomaly_scores(new_params, tx)
                else:
                    scores = jnp.zeros((0,), jnp.float32)
                if track_iso and score_history:
                    iso_scores = jax.vmap(
                        lambda p: det.anomaly_scores(p, tx))(iso_params)
                else:
                    iso_scores = jnp.zeros((0, 0), jnp.float32)
            return (new_params, iso_params, rkey), (tl, scores, iso_tl,
                                                    iso_scores, head_dead)

        iso0 = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (N,) + p.shape).copy(),
            params)
        (final_params, iso_params, _), \
            (losses, score_hist, iso_losses, iso_score_hist, dead_rounds) = \
            jax.lax.scan(round_fn, (params, iso0, key),
                         jnp.arange(cfg.rounds))

        with jax.named_scope("final_scoring"):
            final_alive = trace_alive_mask(trace, N,
                                           jnp.int32(cfg.rounds - 1))
            server_dead = 1.0 - heads_alive_max(final_alive)
            final_scores = det.anomaly_scores(final_params, tx)
            if track_iso:
                iso_final_scores = jax.vmap(
                    lambda p: det.anomaly_scores(p, tx))(iso_params)
            else:
                iso_final_scores = jnp.zeros((N, 0), jnp.float32)
        outputs = SimOutputs(losses, iso_losses, final_scores,
                             iso_final_scores, final_alive, server_dead,
                             dead_rounds, score_hist, iso_score_hist)
        if return_params:
            # params export (the serving layer's model bank): the final
            # global params and the per-device isolated params leave the
            # graph alongside the metrics.  Static flag, default False,
            # so every pre-existing core traces the byte-identical
            # graph it always did.
            return outputs, final_params, iso_params
        return outputs

    return core


def _build_core(model: ModelLike, cfg: SimConfig,
                score_history: bool):
    """Pure scenario function: (dx, counts, valid, tx, trace, seed)
    -> :class:`SimOutputs`.  The topology is closed over statically (a
    thin wrapper over :func:`_build_core_arrays`); the FL
    isolated-fallback branch exists whenever scheme == "fl" and is
    gated in-graph by the trace, so one graph serves every trace."""
    topo = cfg.topology()
    cluster_ids = jnp.asarray(topo.device_cluster_array())
    heads = jnp.asarray(np.array(topo.heads))
    head_valid = jnp.ones((topo.num_clusters,), jnp.float32)
    arrays_core = _build_core_arrays(model, cfg, topo.num_devices,
                                     topo.num_clusters,
                                     track_iso=(cfg.scheme == "fl"),
                                     score_history=score_history)

    def core(dx, counts, valid, tx, trace: FailureTrace, seed):
        return arrays_core(dx, counts, valid, tx, cluster_ids, heads,
                           head_valid, trace, seed)

    return core


@functools.lru_cache(maxsize=32)
def _jitted_core_cached(model: ModelLike, cfg: SimConfig,
                        score_history: bool):
    return jax.jit(_build_core(model, cfg, score_history))


def _jitted_core(model: ModelLike, cfg: SimConfig,
                 score_history: bool):
    """Compiled single-scenario core, cached on static config (the seed
    field of ``cfg`` is ignored — seed is a dynamic argument; the model
    spec is canonicalised so the config and detector spellings of the
    same autoencoder share one cache entry)."""
    return _jitted_core_cached(D.canonical_model_key(model),
                               dataclasses.replace(cfg, seed=0),
                               score_history)


def _prepare_arrays(cfg: SimConfig, device_x: np.ndarray,
                    device_counts: np.ndarray):
    """Scheme-aware device arrays: batch centralises all data onto the
    single server device."""
    if cfg.scheme == "batch":
        flat = np.concatenate([device_x[i, :device_counts[i]]
                               for i in range(len(device_counts))], 0)
        device_x = flat[None]
        device_counts = np.array([len(flat)])
    dx = obs.to_device(device_x)
    counts = obs.to_device(device_counts, jnp.float32)
    valid = (jnp.arange(device_x.shape[1])[None, :]
             < counts[:, None]).astype(jnp.float32)     # (N, n_max)
    return dx, counts, valid


# ---------------------------------------------------------------------------
# Params export (the serving layer's model bank)
# ---------------------------------------------------------------------------
def _build_params_core(model: ModelLike, cfg: SimConfig):
    """Pure scenario function returning trained PARAMETERS instead of
    metrics: (dx, counts, valid, tx, cluster_ids, heads, head_valid,
    trace, seed) -> (global_params, iso_params, final_alive).

    Same round loop as every other core (``_build_core_arrays`` with
    ``return_params=True``), so the exported params are exactly what the
    campaign trained.  ``head_valid`` is a dynamic operand: passing the
    real mask trains the scheme's global model; passing ZEROS makes
    every round an all-heads-dead round, i.e. each device trains its own
    isolated model on its local shard from the shared init — the
    serving failover bank — through the SAME compiled executable."""
    topo = cfg.topology()
    arrays_core = _build_core_arrays(model, cfg, topo.num_devices,
                                     topo.num_clusters, track_iso=True,
                                     score_history=False,
                                     return_params=True)

    def core(dx, counts, valid, tx, cluster_ids, heads, head_valid,
             trace: FailureTrace, seed):
        out, params, iso_params = arrays_core(
            dx, counts, valid, tx, cluster_ids, heads, head_valid,
            trace, seed)
        return params, iso_params, out.final_alive

    return core


@functools.lru_cache(maxsize=16)
def _params_core_cached(model: ModelLike, cfg: SimConfig):
    return jax.jit(_build_params_core(model, cfg))


def trained_params(model: ModelLike, device_x: np.ndarray,
                   device_counts: np.ndarray, cfg: SimConfig,
                   failure: Failure = NO_FAILURE,
                   isolated: bool = False):
    """Train one scenario and export its parameters.

    Returns ``(global_params, iso_params, final_alive)`` as device
    arrays: the scheme's final global model, the per-device isolated
    models (leaves carry a leading ``(N,)`` axis), and the final alive
    mask.  With ``isolated=True`` the cluster-head validity mask is
    zeroed so every device trains its OWN model on its local shard from
    the shared init — the genuinely-isolated failover models the
    scoring service banks (:mod:`repro.serving.anomaly`)."""
    topo = cfg.topology()
    trace = as_trace(failure, topo)
    dx, counts, valid = _prepare_arrays(cfg, device_x, device_counts)
    assert dx.shape[0] == topo.num_devices, (dx.shape, topo.num_devices)
    cluster_ids = jnp.asarray(topo.device_cluster_array())
    heads = jnp.asarray(np.array(topo.heads))
    head_valid = (jnp.zeros if isolated else jnp.ones)(
        (topo.num_clusters,), jnp.float32)
    # the test-loss operand is unused by the params outputs; keep it to
    # one row so the export never pays for a test sweep
    tx = jnp.zeros((1, dx.shape[-1]), dx.dtype)
    core = _params_core_cached(D.canonical_model_key(model),
                               dataclasses.replace(cfg, seed=0))
    return core(dx, counts, valid, tx, cluster_ids, heads, head_valid,
                trace, jnp.int32(cfg.seed))


def iso_mean_auroc(iso_scores: np.ndarray, final_alive: np.ndarray,
                   test_y: np.ndarray) -> float:
    """Paper Fig 4 reporting: mean AUROC over the *alive* isolated
    devices (the dead server keeps its frozen model and is excluded)."""
    # loops over the bounded DEVICE axis (N, not scenarios) with a
    # data-dependent alive filter -- not the batched-metric bug class
    # plancheck: ignore[PC-AST-LOOPMETRIC]
    per_dev = [auroc(iso_scores[i], test_y)
               for i in range(iso_scores.shape[0]) if final_alive[i] > 0]
    return float(np.mean(per_dev)) if per_dev else float("nan")


def run_simulation(model: ModelLike, device_x: np.ndarray,
                   device_counts: np.ndarray, test_x: np.ndarray,
                   test_y: np.ndarray, cfg: SimConfig,
                   failure: Failure = NO_FAILURE,
                   target_loss: Optional[float] = None) -> SimResult:
    """device_x: (N, n_max, D) padded; device_counts: (N,).

    ``model`` is any :class:`repro.models.detector.DetectorModel` (a raw
    :class:`AutoencoderConfig` — the historical first argument — still
    works).  ``failure`` may be a legacy single-event
    :class:`FailureSpec` or a multi-event :class:`FailureTrace`."""
    topo = cfg.topology()
    N = topo.num_devices
    trace = as_trace(failure, topo)
    dx, counts, valid = _prepare_arrays(cfg, device_x, device_counts)
    assert dx.shape[0] == N, (dx.shape, N)
    tx = jnp.asarray(test_x)

    core = _jitted_core(model, cfg, True)
    out = core(dx, counts, valid, tx, trace, jnp.int32(cfg.seed))

    losses = np.asarray(out.losses).copy()
    iso_losses = np.asarray(out.iso_losses)
    scores_all = np.asarray(out.score_hist)
    aurocs = auroc_batch(scores_all, np.asarray(test_y))
    final = float(aurocs[-1])

    # isolated final AUROC: mean over alive devices of per-device AUROC
    track_iso = (cfg.scheme == "fl")
    dead_rounds = np.asarray(out.server_dead_rounds) > 0     # (rounds,)
    fl_server_fallback = track_iso and bool(dead_rounds[-1])
    iso_final = float("nan")
    if fl_server_fallback:
        iso_final = iso_mean_auroc(np.asarray(out.iso_final_scores),
                                   np.asarray(out.final_alive), test_y)

    # Fig 4 semantics: from the round the FL server dies the global
    # model is frozen and meaningless — the reported curves switch to
    # the isolated-mean curve for every server-dead round (a later
    # recovery switches back, matching ``auroc_used``'s round-wise
    # notion of "what the system can actually serve").
    if track_iso and dead_rounds.any():
        iso_hist = np.asarray(out.iso_score_hist)            # (R, N, T)
        for t in np.flatnonzero(dead_rounds):
            alive_t = np.asarray(trace_alive_mask(trace, N, jnp.int32(t)))
            aurocs[t] = iso_mean_auroc(iso_hist[t], alive_t, test_y)
            losses[t] = iso_losses[t]

    used = iso_final if fl_server_fallback else final
    r2l = None
    if target_loss is not None:
        hit = np.where(losses <= target_loss)[0]
        r2l = int(hit[0]) + 1 if len(hit) else None
    return SimResult(final, iso_final, used, losses, aurocs, iso_losses,
                     fl_server_fallback, r2l)


# ---------------------------------------------------------------------------
# Resource-usage models (Table II / VI, Fig 5)
# ---------------------------------------------------------------------------
def comm_transfers_per_round(scheme: str, n: int, k: int) -> int:
    """Model transfers per training round (Table VI accounting)."""
    if scheme == "batch":
        return 0
    if scheme == "fl":
        return 2 * n                       # broadcast + gather
    if scheme == "sbt":
        return n - 1                       # sequential ring pass
    if scheme == "tolfl":
        # members -> heads (n - k), head chain (k - 1), head broadcast (k)
        return n + k - 1
    raise ValueError(scheme)


def _resolve_model_bytes(model_bytes) -> int:
    """``model_bytes`` may be a raw byte count or any detector spec /
    AutoencoderConfig — sized via ``models.params.param_count`` over the
    actual parameter tree instead of hand-rolled arithmetic."""
    if isinstance(model_bytes, (int, float, np.integer, np.floating)):
        return int(model_bytes)
    return D.as_detector(model_bytes).param_bytes()


def comm_mb_per_round(scheme: str, n: int, k: int, model_bytes) -> float:
    return (comm_transfers_per_round(scheme, n, k)
            * _resolve_model_bytes(model_bytes) / 1e6)


def round_time_model(scheme: str, n: int, k: int, samples: int,
                     model_bytes, flops_per_sample: float,
                     device_flops: float = 5e9, link_bw: float = 10e6
                     ) -> float:
    """Seconds per round under the paper's Section IV-A task-sequencing
    model: parallel stages take the max over participants, sequential
    stages sum.  link_bw in bytes/s (wireless-ish)."""
    t_model = _resolve_model_bytes(model_bytes) / link_bw
    per_dev = samples / max(n, 1) * flops_per_sample / device_flops
    if scheme == "batch":
        return samples * flops_per_sample / device_flops
    if scheme == "fl":
        return per_dev + 2 * t_model
    if scheme == "sbt":
        return per_dev + (n - 1) * t_model
    if scheme == "tolfl":
        return per_dev + 2 * t_model + (k - 1) * t_model
    raise ValueError(scheme)
