"""Attention: GQA projections, RoPE, memory-efficient blocked attention.

The training/prefill path is a two-level ``lax.scan`` (outer over query
blocks, inner over key/value blocks) computing online-softmax — the pure-XLA
analogue of flash attention, keeping peak memory at
O(q_block x kv_block x heads) instead of O(seq^2).  The Pallas kernel in
``repro.kernels.flash_attention`` implements the same contraction with
explicit VMEM BlockSpecs; ``use_pallas=True`` routes through it.

Masks: causal, sliding-window (RecurrentGemma local attention / the
long-context dense variant), or full (whisper encoder & cross-attention).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.models import params as P
from repro.sharding import logical as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables: positions (...,) -> (..., head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D//2) or (S, D//2)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------
def attn_init(key, d_model: int, cfg: AttentionConfig, dtype: str
              ) -> Tuple[P.Params, P.Axes]:
    ks = jax.random.split(key, 6)
    q_dim = cfg.num_heads * cfg.head_dim
    kv_dim = cfg.num_kv_heads * cfg.head_dim
    p, a = {}, {}
    p["q"], a["q"] = P.dense_init(ks[0], d_model, q_dim, "embed", "heads",
                                  dtype, bias=cfg.qkv_bias)
    p["k"], a["k"] = P.dense_init(ks[1], d_model, kv_dim, "embed", "kv_heads",
                                  dtype, bias=cfg.qkv_bias)
    p["v"], a["v"] = P.dense_init(ks[2], d_model, kv_dim, "embed", "kv_heads",
                                  dtype, bias=cfg.qkv_bias)
    p["o"], a["o"] = P.dense_init(ks[3], q_dim, d_model, "heads", "embed", dtype)
    if cfg.qk_norm:
        p["q_norm"], a["q_norm"] = {"scale": jnp.ones((cfg.head_dim,))}, {"scale": ("head_dim",)}
        p["k_norm"], a["k_norm"] = {"scale": jnp.ones((cfg.head_dim,))}, {"scale": ("head_dim",)}
    return p, a


def _qk_norm(p, x, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
            ).astype(x.dtype)


def project_qkv(p: P.Params, x: jax.Array, cfg: AttentionConfig,
                positions: jax.Array, norm_eps: float = 1e-6,
                compute_dtype=None):
    """x: (B,S,E) -> q (B,S,H,D), k/v (B,S,KVH,D) with rope + optional qk-norm."""
    B, S, _ = x.shape
    q = P.dense_apply(p["q"], x, compute_dtype).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = P.dense_apply(p["k"], x, compute_dtype).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = P.dense_apply(p["v"], x, compute_dtype).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _qk_norm(p["q_norm"], q, norm_eps)
        k = _qk_norm(p["k_norm"], k, norm_eps)
    if cfg.rope_theta > 0:
        cos, sin = rope_freqs(cfg.head_dim, cfg.rope_theta, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = L.constrain(q, ("batch", "seq", "heads", None))
    k = L.constrain(k, ("batch", "seq", "kv_heads", None))
    v = L.constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


# ---------------------------------------------------------------------------
# Blocked online-softmax attention (training / prefill)
# ---------------------------------------------------------------------------
def _block_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(qb, kb) additive mask from absolute positions."""
    d = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(d.shape, dtype=bool)
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, window: Optional[int] = None,
                      q_block: int = 512, kv_block: int = 512,
                      q_offset: int = 0) -> jax.Array:
    """Memory-efficient attention.

    q: (B, Sq, H, D); k, v: (B, Sk, KVH, D) with H % KVH == 0.
    Returns (B, Sq, H, D).  Peak intermediate is (B, qb, H, kb).
    """
    from repro.models.transformer import divisor_block
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    q_block = divisor_block(Sq, q_block)
    kv_block = divisor_block(Sk, kv_block)
    nq, nk = Sq // q_block, Sk // kv_block
    scale = 1.0 / (D ** 0.5)

    # (nq, B, qb, KVH, G, D)
    qs = q.reshape(B, nq, q_block, KVH, G, D).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kv_block, KVH, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_block, KVH, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, kj_blk):
            kj, kblk, vblk = kj_blk
            acc, m, l = carry
            k_pos = kj * kv_block + jnp.arange(kv_block)
            # scores: (B, qb, KVH, G, kb)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _block_mask(q_pos, k_pos, causal, window)[None, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, q_block, KVH, G, D), jnp.float32)
        m0 = jnp.full((B, q_block, KVH, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, KVH, G), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    # (nq, B, qb, KVH, G, D) -> (B, Sq, H, D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, D)
    return out


def naive_attention(q, k, v, causal=True, window=None, q_offset=0):
    """O(S^2)-memory reference used by tests as the oracle."""
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k,
                   preferred_element_type=jnp.float32) / (D ** 0.5)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    s = s + _block_mask(q_pos, k_pos, causal, window)[None, :, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one new token against a cache)
# ---------------------------------------------------------------------------
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     k_new: jax.Array, v_new: jax.Array,
                     cache_valid: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,1,H,D); caches: (B,Sc,KVH,D); new k/v: (B,1,KVH,D).

    The new token attends to every cached position plus itself.  The cache
    seq dim may be sharded (sequence-parallel flash-decoding): the softmax
    reduction over it is handled by the SPMD partitioner (all-reduce of
    max / sum-exp), which is exactly the flash-decoding combine.

    ``cache_valid``: (Sc,) bool mask — False for empty / out-of-window
    slots (see :func:`cache_slot_validity`)."""
    B, _, H, D = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)
    scale = 1.0 / (D ** 0.5)
    s_c = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                     preferred_element_type=jnp.float32) * scale
    if cache_valid is not None:
        s_c = jnp.where(cache_valid[None, None, None, :], s_c, NEG_INF)
    s_n = jnp.einsum("bhgd,bkhd->bhgk", qg, k_new,
                     preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(jnp.max(s_c, axis=-1, keepdims=True),
                    jnp.max(s_n, axis=-1, keepdims=True))
    p_c = jnp.exp(s_c - m)
    p_n = jnp.exp(s_n - m)
    l = jnp.sum(p_c, axis=-1, keepdims=True) + jnp.sum(  # noqa: E741
        p_n, axis=-1, keepdims=True)
    o = (jnp.einsum("bhgk,bkhd->bhgd", p_c.astype(v_cache.dtype), v_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhgk,bkhd->bhgd", p_n.astype(v_new.dtype), v_new,
                      preferred_element_type=jnp.float32))
    out = (o / jnp.maximum(l.astype(jnp.float32), 1e-30)).astype(q.dtype)
    return out.reshape(B, 1, H, D)


def attn_apply(p: P.Params, x: jax.Array, cfg: AttentionConfig,
               norm_eps: float = 1e-6, window: Optional[int] = None,
               causal: Optional[bool] = None, use_pallas: bool = False,
               q_block: int = 512, kv_block: int = 512,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Full self-attention block for train/prefill: x (B,S,E) -> (B,S,E)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    causal = cfg.causal if causal is None else causal
    window = cfg.sliding_window if window is None else window
    q, k, v = project_qkv(p, x, cfg, positions, norm_eps,
                          compute_dtype=x.dtype)
    if use_pallas:
        from repro.kernels import ops
        out = ops.attention(q, k, v, causal=causal, window=window)
    else:
        out = blocked_attention(q, k, v, causal=causal, window=window,
                                q_block=q_block, kv_block=kv_block)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    out = P.dense_apply(p["o"], out, x.dtype)
    return L.constrain(out, ("batch", "seq", "embed"))


def cache_slot_validity(Sc: int, position: jax.Array,
                        window: Optional[int]) -> jax.Array:
    """(Sc,) bool: which circular-cache slots hold attendable positions.

    Ring invariant: slot i holds the largest absolute position p_i < position
    with p_i = i (mod Sc).  A slot is valid iff that position exists
    (p_i >= 0 — empty slots of a fresh or padded cache are excluded) and,
    for windowed layers, iff its distance is inside the window
    (position - p_i < window)."""
    idx = jnp.arange(Sc)
    pm1 = jnp.asarray(position, jnp.int32) - 1
    p_i = pm1 - jnp.mod(pm1 - idx, Sc)
    valid = p_i >= 0
    if window is not None:
        valid &= (jnp.asarray(position, jnp.int32) - p_i) < window
    return valid


def attn_decode(p: P.Params, x: jax.Array, cache: dict, cfg: AttentionConfig,
                position: jax.Array, norm_eps: float = 1e-6,
                window: Optional[int] = None) -> Tuple[jax.Array, dict]:
    """One-token decode: x (B,1,E), cache {'k','v': (B,Sc,KVH,D)}.

    The cache is circular (vLLM-style): the new k/v overwrite slot
    ``position % Sc`` via dynamic_update_slice — O(1) update for both the
    full-cache and sliding-window cases (for a window cache, Sc == window
    and the modulo implements the ring).  Empty or out-of-window slots are
    masked via :func:`cache_slot_validity`.
    """
    B, _, _ = x.shape
    positions = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (B, 1))
    q, k_new, v_new = project_qkv(p, x, cfg, positions, norm_eps,
                                  compute_dtype=x.dtype)
    valid = cache_slot_validity(cache["k"].shape[1], position, window)
    out = decode_attention(q, cache["k"], cache["v"], k_new, v_new,
                           cache_valid=valid)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    out = P.dense_apply(p["o"], out, x.dtype)
    Sc = cache["k"].shape[1]
    slot = jnp.asarray(position, jnp.int32) % Sc
    new_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, 1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, 1),
    }
    return L.constrain(out, ("batch", "seq", "embed")), new_cache
