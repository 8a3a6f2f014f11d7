"""RG-LRU recurrent block (RecurrentGemma / Griffin) [arXiv:2402.19427].

    r_t = sigmoid(W_a x_t)                    (recurrence gate)
    i_t = sigmoid(W_x x_t)                    (input gate)
    a_t = a ** (c * r_t),  a = sigmoid(lambda)   (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

The recurrence is diagonal-linear, so the pure-JAX path uses
``jax.lax.associative_scan`` (log-depth — the TPU-friendly formulation);
``repro.kernels.rglru_scan`` is the time-blocked Pallas version.  The block
wraps the LRU with the Griffin residual structure: gelu gate branch x conv1d
+ LRU branch, then an output projection.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.sharding import logical as L

C_EXP = 8.0


def rglru_init(key, cfg: ModelConfig) -> Tuple[P.Params, P.Axes]:
    d = cfg.d_model
    w = cfg.recurrent.lru_width or d
    cw = cfg.recurrent.conv1d_width
    dt = cfg.param_dtype
    ks = jax.random.split(key, 7)
    p, a = {}, {}
    p["in_x"], a["in_x"] = P.dense_init(ks[0], d, w, "embed", "state", dt)
    p["in_gate"], a["in_gate"] = P.dense_init(ks[1], d, w, "embed", "state", dt)
    p["conv_w"] = P.normal_init(ks[2], (cw, w), jnp.dtype(dt), 0.02)
    a["conv_w"] = ("conv", "state")
    p["conv_b"] = jnp.zeros((w,), jnp.dtype(dt))
    a["conv_b"] = ("state",)
    p["gate_a"], a["gate_a"] = P.dense_init(ks[3], w, w, "state", None, dt,
                                            scale=0.02)
    p["gate_x"], a["gate_x"] = P.dense_init(ks[4], w, w, "state", None, dt,
                                            scale=0.02)
    # lambda init so that a = sigmoid(lambda) in [0.9, 0.999]
    u = jax.random.uniform(ks[5], (w,), minval=0.9, maxval=0.999)
    p["lam"] = jnp.log(u / (1 - u)).astype(jnp.dtype(dt))
    a["lam"] = ("state",)
    p["out"], a["out"] = P.dense_init(ks[6], w, d, "state", "embed", dt)
    return p, a


def _causal_conv1d(xw: jax.Array, w: jax.Array, b: jax.Array,
                   state: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over time.  xw: (B,S,W); w: (cw,W).

    state: (B, cw-1, W) trailing context from the previous segment."""
    B, S, W = xw.shape
    cw = w.shape[0]
    pad = (jnp.zeros((B, cw - 1, W), xw.dtype) if state is None
           else state.astype(xw.dtype))
    xp = jnp.concatenate([pad, xw], axis=1)
    # NOT zeros_like: that would inherit xw's (full-mesh) sharding, which
    # is rejected inside partial-manual shard_map regions
    out = jnp.zeros(xw.shape, xw.dtype)
    for i in range(cw):
        out = out + xp[:, i:i + S, :] * w[i].astype(xw.dtype)
    out = out + b.astype(xw.dtype)
    return out, xp[:, -(cw - 1):, :] if cw > 1 else jnp.zeros((B, 0, W), xw.dtype)


def _lru_scan(a_t: jax.Array, b_t: jax.Array, h0: Optional[jax.Array],
              use_pallas: bool = False) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t over axis 1.  a_t, b_t: (B,S,W) float32."""
    if use_pallas:
        from repro.kernels import ops
        return ops.rglru(a_t, b_t, h0)
    if h0 is not None:
        # fold the carried state into the first step
        b_t = b_t.at[:, 0, :].add(a_t[:, 0, :] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a_t, b_t), axis=1)
    return h


def rglru_apply(p: P.Params, x: jax.Array, cfg: ModelConfig,
                state: Optional[dict] = None, use_pallas: bool = False
                ) -> Tuple[jax.Array, dict]:
    """x: (B,S,d) -> (out, new_state).

    state: {'h': (B,W) f32, 'conv': (B,cw-1,W)} or None."""
    B, S, d = x.shape
    gate_branch = jax.nn.gelu(P.dense_apply(p["in_gate"], x, x.dtype))
    xw = P.dense_apply(p["in_x"], x, x.dtype)
    xw = L.constrain(xw, ("batch", "seq", "state"))
    conv_state = None if state is None else state["conv"]
    xw, new_conv = _causal_conv1d(xw, p["conv_w"], p["conv_b"], conv_state)

    r = jax.nn.sigmoid(P.dense_apply(p["gate_a"], xw, jnp.float32))
    i = jax.nn.sigmoid(P.dense_apply(p["gate_x"], xw, jnp.float32))
    log_a = C_EXP * r * jax.nn.log_sigmoid(p["lam"].astype(jnp.float32))
    a_t = jnp.exp(log_a)
    # sqrt(1 - a^2) normaliser, clamped for stability
    norm = jnp.sqrt(jnp.clip(1.0 - jnp.square(a_t), 1e-12, None))
    b_t = norm * (i * xw.astype(jnp.float32))
    h0 = None if state is None else state["h"]
    h = _lru_scan(a_t, b_t, h0, use_pallas)
    h = L.constrain(h, ("batch", "seq", "state"))
    out = P.dense_apply(p["out"], (h.astype(x.dtype)) * gate_branch, x.dtype)
    out = L.constrain(out, ("batch", "seq", "embed"))
    new_state = {"h": h[:, -1, :], "conv": new_conv}
    return out, new_state


def rglru_decode(p: P.Params, x: jax.Array, cfg: ModelConfig, state: dict
                 ) -> Tuple[jax.Array, dict]:
    """Single-token step: x (B,1,d)."""
    return rglru_apply(p, x, cfg, state=state, use_pallas=False)
