"""Plain float32 references of the campaign and the scoring service.

Independent of the program: nothing here imports ``repro``.  The
detector body (its initial weights, training loss and anomaly score)
is the configuration's body file, ``chipbench/bodies/<kind>.py``
(:func:`cbench.harness.body`); this module holds what every body
shares.  The semantics follow the paper (Algorithm 1, Section V) as
the program states them:

* a device's update: E local SGD steps on its masked mean loss, sent as
  (theta - theta_E) / lr (the plain gradient when E = 1);
* Tol-FL's hierarchical combine written as what it equals (the paper's
  k-invariance): the sample-weighted mean over devices that are alive
  and whose cluster head is alive; no update when that weight is 0;
* FL's isolated fallback: while every head is dead, each alive device
  steps its own model from where the global model stopped, and the
  reported loss and AUROC are the means over the alive devices;
* IFCA: each device takes the model with the lowest loss on its data,
  models average the gradients of their alive adopters, a dead
  aggregator (server event) freezes model 0;
* the random streams: ``PRNGKey(seed)``; per round ``rkey, dkey =
  split(rkey)`` and one key per device from ``split(dkey, N)``; local
  step e folds e into the device key, and the body draws its dropout
  from that key.

Every matrix product goes through :func:`matmul`: ``"highest"`` is
float32 (six bfloat16 passes on a TPU); ``"bf16_3x"`` (three passes,
JAX's ``tensorfloat32``) and ``"bf16"`` (one pass, JAX's ``bfloat16``)
are emulated explicitly, so that they mean the same on every backend.
:data:`CONTROL` names, for the precision a configuration states, the
nearest one below it: the control that the comparison must reject.
"""
from __future__ import annotations

import functools
import json
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cbench import harness
from cbench.auroc import auroc_rows

PRECISIONS = ("highest", "bf16_3x", "bf16")
#: configuration's matmul precision -> the control's
CONTROL = {"float32": "bf16_3x", "tensorfloat32": "bf16"}


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm3_impl(a, b):
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def f(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@jax.custom_vjp
def _mm3(a, b):
    return _mm3_impl(a, b)


def _mm3_fwd(a, b):
    return _mm3_impl(a, b), (a, b)


def _mm3_bwd(res, g):
    a, b = res
    return (_mm3(g, jnp.swapaxes(b, -1, -2)),
            _mm3(jnp.swapaxes(a, -1, -2), g))


_mm3.defvjp(_mm3_fwd, _mm3_bwd)


def _mm1_impl(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def _mm1(a, b):
    return _mm1_impl(a, b)


def _mm1_fwd(a, b):
    return _mm1_impl(a, b), (a, b)


def _mm1_bwd(res, g):
    a, b = res
    return (_mm1(g, jnp.swapaxes(b, -1, -2)),
            _mm1(jnp.swapaxes(a, -1, -2), g))


_mm1.defvjp(_mm1_fwd, _mm1_bwd)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16_3x":
        return _mm3(a, b)
    if precision == "bf16":
        return _mm1(a, b)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Detector bodies
# ---------------------------------------------------------------------------
def body_key(model: dict) -> Tuple[str, str]:
    """A configuration's model as a static, hashable argument: its kind
    and the whole model dict, frozen as canonical JSON."""
    return model["kind"], json.dumps(model, sort_keys=True)


def body_of(key: Tuple[str, str]):
    """(body module, model dict) of a :func:`body_key`."""
    kind, frozen = key
    return harness.body(kind), json.loads(frozen)


def _tree_axpy(a, x, y):
    """y + a * x over two parameter trees."""
    return jax.tree.map(lambda xi, yi: yi + a * xi, x, y)


# ---------------------------------------------------------------------------
# Single-model schemes (FL / SBT / Tol-FL)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "body", "rounds", "local_steps", "lr", "track_iso", "precision"))
def _single(dx, counts, valid, tx, alive, head_of, is_head, seed, *,
            body, rounds, local_steps, lr, track_iso, precision):
    n_dev = dx.shape[0]
    mod, model = body_of(body)
    key = jax.random.PRNGKey(seed)
    params = mod.init(key, model)
    grad = jax.grad(mod.train_loss)

    def scores(p, x):
        return mod.scores(p, x, precision, model)

    def local_update(p, x, v, k):
        if local_steps == 1:
            return grad(p, x, v, k, precision, model)
        q = p
        for e in range(local_steps):
            g = grad(q, x, v, jax.random.fold_in(k, e), precision, model)
            q = jax.tree.map(lambda a, b: a - lr * b, q, g)
        return jax.tree.map(lambda a, b: (a - b) / lr, p, q)

    def mean_score(p):
        return jnp.mean(scores(p, tx))

    def round_fn(carry, alive_r):
        params, iso, rkey = carry
        rkey, dkey = jax.random.split(rkey)
        dkeys = jax.random.split(dkey, n_dev)
        weight = alive_r * alive_r[head_of]
        head_dead = 1.0 - jnp.max(alive_r * is_head)
        if track_iso:
            start = jax.tree.map(
                lambda i, p: jnp.where(head_dead > 0, i,
                                       jnp.broadcast_to(p, i.shape)),
                iso, params)
        else:
            start = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (n_dev,) + p.shape), params)
        upd = jax.vmap(local_update)(start, dx, valid, dkeys)
        n = counts * weight
        n_tot = jnp.sum(n)
        frac = jnp.where(n_tot > 0, n / jnp.maximum(n_tot, 1e-30), 0.0)
        mean_upd = jax.tree.map(
            lambda u: jnp.sum(frac.reshape((-1,) + (1,) * (u.ndim - 1))
                              * u, axis=0), upd)
        params = _tree_axpy(-lr, mean_upd, params)
        if track_iso:
            step = (head_dead * alive_r).reshape(-1, 1)
            iso = jax.tree.map(
                lambda s, u: s - lr * step.reshape(
                    (-1,) + (1,) * (u.ndim - 1)) * u, start, upd)
            per_dev = jax.vmap(mean_score)(iso)
            iso_loss = (jnp.sum(alive_r * per_dev)
                        / jnp.maximum(jnp.sum(alive_r), 1.0))
        else:
            iso_loss = jnp.float32(0.0)
        return (params, iso, rkey), (mean_score(params), iso_loss,
                                     head_dead)

    iso0 = jax.tree.map(lambda p: jnp.broadcast_to(p, (n_dev,) + p.shape),
                        params)
    (params, iso, _), (loss, iso_loss, dead) = jax.lax.scan(
        round_fn, (params, iso0, key), alive)
    final = scores(params, tx)
    iso_final = (jax.vmap(lambda p: scores(p, tx))(iso)
                 if track_iso else jnp.zeros((n_dev, 0), jnp.float32))
    return loss, iso_loss, dead, final, iso_final


def single_scenario(model: dict, dx, counts, tx, test_y, alive: np.ndarray,
                    num_clusters: int, seed: int, *, rounds: int,
                    local_steps: int, lr: float, track_iso: bool,
                    precision: str) -> dict:
    """One FL / SBT / Tol-FL scenario: the reported test-loss curve and
    AUROC.  ``alive`` is the (rounds, N) liveness of the trace."""
    n_dev = dx.shape[0]
    m = n_dev // num_clusters
    head_of = (np.arange(n_dev) // m) * m
    is_head = (np.arange(n_dev) % m == 0).astype(np.float32)
    valid = (np.arange(dx.shape[1])[None, :]
             < np.asarray(counts)[:, None]).astype(np.float32)
    loss, iso_loss, dead, final, iso_final = _single(
        jnp.asarray(dx), jnp.asarray(counts, jnp.float32),
        jnp.asarray(valid), jnp.asarray(tx), jnp.asarray(alive),
        jnp.asarray(head_of, jnp.int32), jnp.asarray(is_head),
        jnp.int32(seed), body=body_key(model), rounds=rounds,
        local_steps=local_steps, lr=lr, track_iso=track_iso,
        precision=precision)
    loss, iso_loss = np.asarray(loss), np.asarray(iso_loss)
    dead = np.asarray(dead) > 0
    auroc = float(auroc_rows(np.asarray(final)[None], test_y)[0])
    if track_iso:
        loss = np.where(dead, iso_loss, loss)
        if dead[-1]:
            up = alive[-1] > 0
            per_dev = auroc_rows(np.asarray(iso_final)[up], test_y)
            auroc = float(np.mean(per_dev)) if len(per_dev) else np.nan
    return {"loss": loss, "auroc": np.array([auroc])}


# ---------------------------------------------------------------------------
# IFCA
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "body", "num_models", "lr", "precision"))
def _ifca(dx, counts, valid, tx, alive_dev, alive_model, seed, *, body,
          num_models, lr, precision):
    n_dev = dx.shape[0]
    mod, model = body_of(body)
    key = jax.random.PRNGKey(seed)
    k_init, _, k_train = jax.random.split(key, 3)
    models = [mod.init(jax.random.fold_in(k_init, j), model)
              for j in range(num_models)]
    models = jax.tree.map(lambda *xs: jnp.stack(xs), *models)
    assign0 = jnp.arange(n_dev) % num_models

    def scores(p, x):
        return mod.scores(p, x, precision, model)

    def loss_of(p, x, v, k):
        return mod.train_loss(p, x, v, k, precision, model)

    def round_fn(carry, xs):
        models, _, rkey = carry
        a_dev, a_model = xs
        rkey, dkey = jax.random.split(rkey)
        dkeys = jax.random.split(dkey, n_dev)
        losses = jax.vmap(lambda x, v, k: jax.vmap(
            lambda p: loss_of(p, x, v, k))(models))(dx, valid, dkeys)
        assign = jnp.argmin(losses, axis=1)
        grads = jax.vmap(lambda a, x, v, k: jax.grad(loss_of)(
            jax.tree.map(lambda t: t[a], models), x, v, k))(
                assign, dx, valid, dkeys)
        w = counts * a_dev
        member = (assign[None, :] == jnp.arange(num_models)[:, None])
        wm = jnp.where(member, w[None, :], 0.0)             # (M, N)
        denom = jnp.sum(wm, axis=1)
        frac = wm / jnp.maximum(denom, 1e-30)[:, None]
        mean_g = jax.tree.map(
            lambda g: jnp.sum(frac.reshape(frac.shape + (1,) * (g.ndim - 1))
                              * g[None], axis=1), grads)
        gate = (denom > 0).astype(jnp.float32) * a_model
        models = jax.tree.map(
            lambda p, g: p - lr * gate.reshape((-1,) + (1,) * (g.ndim - 1))
            * g, models, mean_g)
        s = jax.vmap(lambda p: scores(p, tx))(models)
        return (models, assign, rkey), jnp.mean(jnp.min(s, axis=0))

    (models, _, _), loss = jax.lax.scan(
        round_fn, (models, assign0, k_train), (alive_dev, alive_model))
    final = jax.vmap(lambda p: scores(p, tx))(models)
    return loss, final


def ifca_scenario(model: dict, dx, counts, tx, test_y, alive_dev: np.ndarray,
                  alive_model: np.ndarray, seed: int, *, num_models: int,
                  lr: float, precision: str) -> dict:
    """One IFCA scenario: the per-sample-min loss curve, the best single
    model's AUROC and the AUROC of the per-sample minimum score."""
    valid = (np.arange(dx.shape[1])[None, :]
             < np.asarray(counts)[:, None]).astype(np.float32)
    loss, final = _ifca(
        jnp.asarray(dx), jnp.asarray(counts, jnp.float32),
        jnp.asarray(valid), jnp.asarray(tx), jnp.asarray(alive_dev),
        jnp.asarray(alive_model), jnp.int32(seed),
        body=body_key(model), num_models=num_models, lr=lr,
        precision=precision)
    final = np.asarray(final)
    best = float(np.max(auroc_rows(final, test_y)))
    multi = float(auroc_rows(final.min(axis=0)[None], test_y)[0])
    return {"loss": np.asarray(loss), "auroc": np.array([best, multi])}


# ---------------------------------------------------------------------------
# Scoring service
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("body", "precision"))
def _score_rows(row_params, rows, x, *, body, precision):
    """Scores of windows ``x`` (B, W, D) each against bank row
    ``rows[b]``."""
    mod, model = body_of(body)

    def one(r, xw):
        p = jax.tree.map(lambda t: t[r], row_params)
        return mod.scores(p, xw, precision, model)
    return jax.vmap(one)(rows, x)


def service_scores(model: dict, row_params, rows: np.ndarray,
                   windows: np.ndarray, precision: str,
                   block: int = 4096) -> np.ndarray:
    """(B, W) reference scores of every window against its bank row
    (``row_params`` in the body's reference layout, each leaf with a
    leading row axis), in blocks of ``block`` windows (the last block
    padded)."""
    out = []
    for s in range(0, len(rows), block):
        r = rows[s:s + block]
        x = windows[s:s + block]
        pad = block - len(r)
        if pad:
            r = np.concatenate([r, np.zeros(pad, r.dtype)])
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        got = _score_rows(row_params, jnp.asarray(r, jnp.int32),
                          jnp.asarray(x), body=body_key(model),
                          precision=precision)
        out.append(np.asarray(got)[:block - pad])
    return np.concatenate(out, 0)
