"""Plain float32 references of the campaign and the scoring service.

Independent of the program: nothing here imports ``repro``.  The
semantics follow the paper (Algorithm 1, Section V) as the program
states them:

* the autoencoder: fully connected, ReLU hidden layers, linear output,
  inverted dropout at rate 0.2 on every hidden layer while training;
  initial weights N(0, 1/fan_in), zero biases; the anomaly score of a
  row is its squared reconstruction error;
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
  step e folds e into the device key; dropout layer i folds in i.

Every matrix product goes through :func:`matmul`: ``"highest"`` is
float32 (six bfloat16 passes on a TPU); ``"bf16_3x"`` (three passes,
JAX's ``tensorfloat32``) and ``"bf16"`` (one pass, JAX's ``bfloat16``)
are emulated explicitly, so that they mean the same on every backend.
:data:`CONTROL` names, for the precision a configuration states, the
nearest one below it: the control that the comparison must reject.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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
# The autoencoder
# ---------------------------------------------------------------------------
def layer_dims(model: dict) -> List[int]:
    hidden = list(model["hidden"])
    return ([model["input_dim"]] + hidden + [model["code_dim"]]
            + hidden[::-1] + [model["input_dim"]])


def init_params(key, dims: Sequence[int]) -> List[Dict[str, jax.Array]]:
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": (jax.random.normal(ks[i], (dims[i], dims[i + 1]))
                   * (1.0 / math.sqrt(dims[i]))).astype(jnp.float32),
             "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(len(dims) - 1)]


def forward(params, x, precision: str, dropout_key=None,
            rate: float = 0.0):
    h = x
    for i, layer in enumerate(params):
        h = matmul(h, layer["w"], precision) + layer["b"]
        if i < len(params) - 1:
            h = jnp.maximum(h, 0.0)
            if dropout_key is not None and rate > 0:
                keep = jax.random.bernoulli(
                    jax.random.fold_in(dropout_key, i), 1.0 - rate,
                    h.shape)
                h = jnp.where(keep, h / (1.0 - rate), 0.0)
    return h


def scores(params, x, precision: str):
    """Squared reconstruction error per row."""
    return jnp.sum(jnp.square(x - forward(params, x, precision)), axis=-1)


def train_loss(params, x, valid, key, precision: str, rate: float):
    err = jnp.sum(jnp.square(x - forward(params, x, precision, key, rate)),
                  axis=-1)
    return jnp.sum(err * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _tree_axpy(a, x, y):
    """y + a * x over two parameter lists."""
    return jax.tree.map(lambda xi, yi: yi + a * xi, x, y)


# ---------------------------------------------------------------------------
# Single-model schemes (FL / SBT / Tol-FL)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "dims", "rounds", "local_steps", "lr", "rate", "track_iso",
    "precision"))
def _single(dx, counts, valid, tx, alive, head_of, is_head, seed, *,
            dims, rounds, local_steps, lr, rate, track_iso, precision):
    n_dev = dx.shape[0]
    key = jax.random.PRNGKey(seed)
    params = init_params(key, dims)
    grad = jax.grad(train_loss)

    def local_update(p, x, v, k):
        if local_steps == 1:
            return grad(p, x, v, k, precision, rate)
        q = p
        for e in range(local_steps):
            g = grad(q, x, v, jax.random.fold_in(k, e), precision, rate)
            q = jax.tree.map(lambda a, b: a - lr * b, q, g)
        return jax.tree.map(lambda a, b: (a - b) / lr, p, q)

    def mean_score(p):
        return jnp.mean(scores(p, tx, precision))

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
    final = scores(params, tx, precision)
    iso_final = (jax.vmap(lambda p: scores(p, tx, precision))(iso)
                 if track_iso else jnp.zeros((n_dev, 0), jnp.float32))
    return loss, iso_loss, dead, final, iso_final


def single_scenario(model: dict, dx, counts, tx, test_y, alive: np.ndarray,
                    num_clusters: int, seed: int, *, rounds: int,
                    local_steps: int, lr: float, rate: float,
                    track_iso: bool, precision: str) -> dict:
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
        jnp.int32(seed), dims=tuple(layer_dims(model)), rounds=rounds,
        local_steps=local_steps, lr=lr, rate=rate, track_iso=track_iso,
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
    "dims", "num_models", "lr", "rate", "precision"))
def _ifca(dx, counts, valid, tx, alive_dev, alive_model, seed, *, dims,
          num_models, lr, rate, precision):
    n_dev = dx.shape[0]
    key = jax.random.PRNGKey(seed)
    k_init, _, k_train = jax.random.split(key, 3)
    models = [init_params(jax.random.fold_in(k_init, j), dims)
              for j in range(num_models)]
    models = jax.tree.map(lambda *xs: jnp.stack(xs), *models)
    assign0 = jnp.arange(n_dev) % num_models

    def loss_of(p, x, v, k):
        return train_loss(p, x, v, k, precision, rate)

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
        s = jax.vmap(lambda p: scores(p, tx, precision))(models)
        return (models, assign, rkey), jnp.mean(jnp.min(s, axis=0))

    (models, _, _), loss = jax.lax.scan(
        round_fn, (models, assign0, k_train), (alive_dev, alive_model))
    final = jax.vmap(lambda p: scores(p, tx, precision))(models)
    return loss, final


def ifca_scenario(model: dict, dx, counts, tx, test_y, alive_dev: np.ndarray,
                  alive_model: np.ndarray, seed: int, *, num_models: int,
                  lr: float, rate: float, precision: str) -> dict:
    """One IFCA scenario: the per-sample-min loss curve, the best single
    model's AUROC and the AUROC of the per-sample minimum score."""
    valid = (np.arange(dx.shape[1])[None, :]
             < np.asarray(counts)[:, None]).astype(np.float32)
    loss, final = _ifca(
        jnp.asarray(dx), jnp.asarray(counts, jnp.float32),
        jnp.asarray(valid), jnp.asarray(tx), jnp.asarray(alive_dev),
        jnp.asarray(alive_model), jnp.int32(seed),
        dims=tuple(layer_dims(model)), num_models=num_models, lr=lr,
        rate=rate, precision=precision)
    final = np.asarray(final)
    best = float(np.max(auroc_rows(final, test_y)))
    multi = float(auroc_rows(final.min(axis=0)[None], test_y)[0])
    return {"loss": np.asarray(loss), "auroc": np.array([best, multi])}


# ---------------------------------------------------------------------------
# Scoring service
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("precision",))
def _score_rows(row_params, rows, x, *, precision):
    """Scores of windows ``x`` (B, W, D) each against bank row
    ``rows[b]``."""
    def one(r, xw):
        p = jax.tree.map(lambda t: t[r], row_params)
        return scores(p, xw, precision)
    return jax.vmap(one)(rows, x)


def service_scores(row_params, rows: np.ndarray, windows: np.ndarray,
                   precision: str, block: int = 4096) -> np.ndarray:
    """(B, W) reference scores of every window against its bank row, in
    blocks of ``block`` windows (the last block padded)."""
    out = []
    for s in range(0, len(rows), block):
        r = rows[s:s + block]
        x = windows[s:s + block]
        pad = block - len(r)
        if pad:
            r = np.concatenate([r, np.zeros(pad, r.dtype)])
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        got = _score_rows(row_params, jnp.asarray(r, jnp.int32),
                          jnp.asarray(x), precision=precision)
        out.append(np.asarray(got)[:block - pad])
    return np.concatenate(out, 0)
