"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def attention_reference(q, k, v, causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """Naive O(S^2) attention.  q (B,Sq,H,D); k,v (B,Sk,KVH,D)."""
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    dpos = jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :]
    ok = jnp.ones(dpos.shape, bool)
    if causal:
        ok &= dpos >= 0
    if window is not None:
        ok &= dpos < window
    s = jnp.where(ok[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def rwkv6_reference(r, k, v, w, u, state0) -> Tuple[jax.Array, jax.Array]:
    """Sequential WKV6.  r,k,v,w: (B,S,H,N) f32; u: (H,N); state0: (B,H,N,N).

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S + k v^T."""
    def step(S, rkvw):
        rt, kt, vt, wt = rkvw
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhn,bhnm->bhm", rt, S + u[None, :, :, None] * kv)
        return wt[..., :, None] * S + kv, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    state, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1), state


def rglru_reference(a_t, b_t, h0=None) -> jax.Array:
    """Sequential diagonal linear recurrence h_t = a_t h_{t-1} + b_t.
    a_t, b_t: (B,S,W) f32; h0: (B,W) or None."""
    B, S, W = a_t.shape
    h = jnp.zeros((B, W), jnp.float32) if h0 is None else h0

    def step(h, ab):
        a, b = ab
        h = a * h + b
        return h, h

    _, hs = jax.lax.scan(step, h, (jnp.moveaxis(a_t, 1, 0),
                                   jnp.moveaxis(b_t, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)


def tolfl_combine_reference(gs, ns) -> jax.Array:
    """Streaming weighted mean over the leading axis of a stacked gradient
    block.  gs: (K, ...) f32; ns: (K,) f32.  Equals the direct weighted
    mean (the paper's k-invariance)."""
    K = gs.shape[0]
    n = jnp.zeros(())
    g = jnp.zeros_like(gs[0])
    for i in range(K):
        n_new = n + ns[i]
        r = jnp.where(n_new > 0, ns[i] / jnp.maximum(n_new, 1e-30), 0.0)
        g = (1 - r) * g + r * gs[i]
        n = n_new
    return g


def recon_out_reference(h, w, b, x, valid) -> jax.Array:
    """The autoencoder's output layer and masked reconstruction loss:
    ``sum(||x - (h w + b)||^2 * valid) / max(sum(valid), 1)``."""
    x_hat = h @ w + b
    err = jnp.sum(jnp.square(x - x_hat), axis=-1) * valid
    return jnp.sum(err) / jnp.maximum(jnp.sum(valid), 1.0)
