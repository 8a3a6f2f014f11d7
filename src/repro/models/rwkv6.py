"""RWKV6 "Finch" time-mix / channel-mix blocks [arXiv:2404.05892].

Attention-free: per-head matrix-valued state S (N x N) with data-dependent
diagonal decay w_t:

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

Token-shift interpolation (ddlerp) uses learned mus plus LoRA adapters on
the shifted mix, per the Finch paper.  The sequential recurrence is a
``lax.scan`` over time in the pure-JAX path; ``repro.kernels.rwkv6_scan``
is the time-blocked Pallas version.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.sharding import logical as L

DDLERP_RANK = 32
DECAY_RANK = 64
MIX_NAMES = ("w", "k", "v", "r", "g")


def timemix_init(key, cfg: ModelConfig) -> Tuple[P.Params, P.Axes]:
    d = cfg.d_model
    H, N = cfg.recurrent.num_heads, cfg.recurrent.head_size
    assert H * N == d, (H, N, d)
    ks = jax.random.split(key, 12)
    p, a = {}, {}
    dt = cfg.param_dtype
    # token-shift base mus: one per mix target + the ddlerp input mix
    p["mu"] = P.normal_init(ks[0], (len(MIX_NAMES) + 1, d), jnp.dtype(dt), 0.02)
    a["mu"] = (None, "embed")
    # ddlerp LoRA: (d -> rank -> 5*d)
    p["ddlerp_a"], a["ddlerp_a"] = P.dense_init(
        ks[1], d, DDLERP_RANK * len(MIX_NAMES), "embed", None, dt, scale=0.02)
    p["ddlerp_b"], a["ddlerp_b"] = P.dense_init(
        ks[2], DDLERP_RANK * len(MIX_NAMES), len(MIX_NAMES) * d, None, "embed",
        dt, scale=0.02)
    for i, nm in enumerate(("r", "k", "v", "g")):
        p[nm], a[nm] = P.dense_init(ks[3 + i], d, d, "embed", "heads", dt)
    p["o"], a["o"] = P.dense_init(ks[7], d, d, "heads", "embed", dt)
    # data-dependent decay: w_t = exp(-exp(decay_base + lora(x_w)))
    p["decay_base"] = P.normal_init(ks[8], (d,), jnp.dtype(dt), 0.02)
    a["decay_base"] = ("embed",)
    p["decay_a"], a["decay_a"] = P.dense_init(ks[9], d, DECAY_RANK, "embed",
                                              None, dt, scale=0.02)
    p["decay_b"], a["decay_b"] = P.dense_init(ks[10], DECAY_RANK, d, None,
                                              "embed", dt, scale=0.02)
    p["bonus"] = P.normal_init(ks[11], (d,), jnp.dtype(dt), 0.02)  # u
    a["bonus"] = ("embed",)
    # group-norm over heads on the output
    p["ln_x"] = {"scale": jnp.ones((d,), jnp.dtype(dt)),
                 "bias": jnp.zeros((d,), jnp.dtype(dt))}
    a["ln_x"] = {"scale": ("embed",), "bias": ("embed",)}
    return p, a


def _ddlerp(p, x, sx):
    """Finch data-dependent token-shift: returns dict name->mixed input."""
    B, S, d = x.shape
    diff = sx - x
    xx = x + diff * p["mu"][len(MIX_NAMES)].astype(x.dtype)
    lora = jnp.tanh(P.dense_apply(p["ddlerp_a"], xx, x.dtype))
    lora = P.dense_apply(p["ddlerp_b"], lora, x.dtype)
    lora = lora.reshape(B, S, len(MIX_NAMES), d)
    out = {}
    for i, nm in enumerate(MIX_NAMES):
        mix = p["mu"][i].astype(x.dtype) + lora[:, :, i]
        out[nm] = x + diff * mix
    return out


def _wkv_scan(r, k, v, w, u, state0):
    """Sequential WKV: r,k,v,w (B,S,H,N); u (H,N); state (B,H,N,N).

    Returns y (B,S,H,N), final state."""
    def step(S, rkvw):
        rt, kt, vt, wt = rkvw          # (B,H,N)
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,N,N)
        y = jnp.einsum("bhn,bhnm->bhm", rt,
                       S + u[None, :, :, None] * kv)
        S_new = wt[..., :, None] * S + kv
        return S_new, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    state, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1), state


def timemix_apply(p: P.Params, x: jax.Array, cfg: ModelConfig,
                  state: Optional[dict] = None, use_pallas: bool = False
                  ) -> Tuple[jax.Array, dict]:
    """x: (B,S,d).  state: {'shift': (B,d), 'wkv': (B,H,N,N)} or None."""
    B, S, d = x.shape
    H, N = cfg.recurrent.num_heads, cfg.recurrent.head_size
    if state is None:
        shift0 = jnp.zeros((B, d), x.dtype)
        wkv0 = jnp.zeros((B, H, N, N), jnp.float32)
    else:
        shift0, wkv0 = state["shift"], state["wkv"]
    sx = jnp.concatenate([shift0[:, None, :], x[:, :-1, :]], axis=1)
    mixed = _ddlerp(p, x, sx)
    r = P.dense_apply(p["r"], mixed["r"], x.dtype).reshape(B, S, H, N)
    k = P.dense_apply(p["k"], mixed["k"], x.dtype).reshape(B, S, H, N)
    v = P.dense_apply(p["v"], mixed["v"], x.dtype).reshape(B, S, H, N)
    g = jax.nn.silu(P.dense_apply(p["g"], mixed["g"], x.dtype))
    decay = (p["decay_base"].astype(jnp.float32)
             + P.dense_apply(p["decay_b"],
                             jnp.tanh(P.dense_apply(p["decay_a"], mixed["w"],
                                                    jnp.float32)),
                             jnp.float32))
    w = jnp.exp(-jnp.exp(decay)).reshape(B, S, H, N)
    u = p["bonus"].astype(jnp.float32).reshape(H, N)
    r32, k32, v32, w32 = (t.astype(jnp.float32) for t in (r, k, v, w))
    if use_pallas:
        from repro.kernels import ops
        y, wkv = ops.rwkv6(r32, k32, v32, w32, u, wkv0)
    else:
        y, wkv = _wkv_scan(r32, k32, v32, w32, u, wkv0)
    y = y.reshape(B, S, d)
    # group-norm per head
    y = y.reshape(B, S, H, N)
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.var(y, axis=-1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, d)
    y = (y * p["ln_x"]["scale"].astype(jnp.float32)
         + p["ln_x"]["bias"].astype(jnp.float32)).astype(x.dtype)
    out = P.dense_apply(p["o"], y * g, x.dtype)
    out = L.constrain(out, ("batch", "seq", "embed"))
    new_state = {"shift": x[:, -1, :], "wkv": wkv}
    return out, new_state


def channelmix_init(key, cfg: ModelConfig) -> Tuple[P.Params, P.Axes]:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    dt = cfg.param_dtype
    p["mu"] = P.normal_init(ks[0], (2, d), jnp.dtype(dt), 0.02)
    a["mu"] = (None, "embed")
    p["key"], a["key"] = P.dense_init(ks[1], d, f, "embed", "ff", dt)
    p["value"], a["value"] = P.dense_init(ks[2], f, d, "ff", "embed", dt)
    return p, a


def channelmix_apply(p: P.Params, x: jax.Array,
                     state: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """RWKV channel-mix: squared-relu mlp with token shift.

    state: (B, d) previous token (decode) or None (train)."""
    B, S, d = x.shape
    shift0 = jnp.zeros((B, d), x.dtype) if state is None else state
    sx = jnp.concatenate([shift0[:, None, :], x[:, :-1, :]], axis=1)
    diff = sx - x
    xk = x + diff * p["mu"][0].astype(x.dtype)
    k = jnp.square(jax.nn.relu(P.dense_apply(p["key"], xk, x.dtype)))
    k = L.constrain(k, ("batch", "seq", "ff"))
    out = P.dense_apply(p["value"], k, x.dtype)
    return L.constrain(out, ("batch", "seq", "embed")), x[:, -1, :]
