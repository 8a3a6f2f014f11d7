"""The paper's anomaly-detection autoencoder (Section V-A).

Fully-connected encoder/decoder; hidden layers 128-64 / 64-128 around a
32-wide code; ReLU hidden activations, linear output; dropout 0.2 on hidden
layers during training.  Anomaly score = squared reconstruction error.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.autoencoder_paper import AutoencoderConfig
from repro.models import params as P


def init_params(key, cfg: AutoencoderConfig) -> Tuple[P.Params, P.Axes]:
    dims = ([cfg.input_dim] + list(cfg.hidden) + [cfg.code_dim]
            + list(reversed(cfg.hidden)) + [cfg.input_dim])
    p, a = {}, {}
    ks = jax.random.split(key, len(dims) - 1)
    for i in range(len(dims) - 1):
        p[f"fc{i}"], a[f"fc{i}"] = P.dense_init(
            ks[i], dims[i], dims[i + 1], None, None, "float32", bias=True)
    return p, a


def num_layers(cfg: AutoencoderConfig) -> int:
    return 2 * (len(cfg.hidden) + 1)


def forward(params: P.Params, cfg: AutoencoderConfig, x: jax.Array,
            dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """x: (B, input_dim) -> reconstruction (B, input_dim).

    Pass ``dropout_key`` during training to enable dropout on hidden
    layers (paper: p=0.2)."""
    return P.dense_apply(params[f"fc{num_layers(cfg) - 1}"],
                         hidden(params, cfg, x, dropout_key))


def hidden(params: P.Params, cfg: AutoencoderConfig, x: jax.Array,
           dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """x: (B, input_dim) -> the last hidden layer's output (B, hidden[0]),
    the input of the linear output layer."""
    act = P.activation(cfg.act)
    h = x
    for i in range(num_layers(cfg) - 1):
        h = act(P.dense_apply(params[f"fc{i}"], h))
        if dropout_key is not None and cfg.dropout > 0:
            dk = jax.random.fold_in(dropout_key, i)
            keep = jax.random.bernoulli(dk, 1.0 - cfg.dropout, h.shape)
            h = jnp.where(keep, h / (1.0 - cfg.dropout), 0.0)
    return h


def recon_loss(params: P.Params, cfg: AutoencoderConfig, x: jax.Array,
               dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """Mean squared reconstruction error J(x) = ||x - x_hat||^2."""
    x_hat = forward(params, cfg, x, dropout_key)
    return jnp.mean(jnp.sum(jnp.square(x - x_hat), axis=-1))


def anomaly_scores(params: P.Params, cfg: AutoencoderConfig, x: jax.Array
                   ) -> jax.Array:
    """Per-sample anomaly score (no dropout at eval)."""
    x_hat = forward(params, cfg, x)
    return jnp.sum(jnp.square(x - x_hat), axis=-1)
