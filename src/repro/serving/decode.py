"""Serving: KV/recurrent-state caches, prefill, and single-token decode.

``serve_step`` (decode) is what the decode_32k / long_500k input shapes
lower: ONE new token against a cache of ``seq_len`` (full-attention archs),
``window`` (sliding-window variants — the sub-quadratic long-context path),
or O(1) recurrent state (ssm / hybrid).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, LOCAL_ATTN, ModelConfig, RECURRENT,
                                RWKV)
from repro.models import attention as A
from repro.models import params as P
from repro.models import rglru as G
from repro.models import rwkv6 as R
from repro.models.mlp import mlp_apply
from repro.models.moe import moe_apply
from repro.models.transformer import (embed_tokens, logits_fn,
                                      sinusoidal_positions,
                                      unit_counts, unit_pattern)
from repro.sharding import logical as L


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def layer_cache_shape(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                      long_context: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStructs for one layer's cache entry."""
    a = cfg.attention
    dt = jnp.dtype(cfg.dtype)
    if kind in (ATTN, LOCAL_ATTN):
        if kind == LOCAL_ATTN and a.sliding_window:
            Sc = min(seq_len, a.sliding_window)
        elif long_context:
            Sc = min(seq_len, a.long_context_window)
        else:
            Sc = seq_len
        kv = jax.ShapeDtypeStruct((batch, Sc, a.num_kv_heads, a.head_dim), dt)
        return {"k": kv, "v": kv}
    if kind == RECURRENT:
        W = cfg.recurrent.lru_width or cfg.d_model
        cw = cfg.recurrent.conv1d_width
        return {"h": jax.ShapeDtypeStruct((batch, W), jnp.float32),
                "conv": jax.ShapeDtypeStruct((batch, cw - 1, W), dt)}
    if kind == RWKV:
        H, N = cfg.recurrent.num_heads, cfg.recurrent.head_size
        return {"shift_tm": jax.ShapeDtypeStruct((batch, cfg.d_model), dt),
                "wkv": jax.ShapeDtypeStruct((batch, H, N, N), jnp.float32),
                "shift_cm": jax.ShapeDtypeStruct((batch, cfg.d_model), dt)}
    raise ValueError(kind)


def cache_shape(cfg: ModelConfig, batch: int, seq_len: int,
                long_context: bool = False) -> Dict[str, Any]:
    """Full-model cache ShapeDtypeStruct tree (stacked over scan units)."""
    unit = unit_pattern(cfg)
    n_units, n_tail = unit_counts(cfg)
    per_unit = {f"l{i}": layer_cache_shape(cfg, kind, batch, seq_len,
                                           long_context)
                for i, (kind, _) in enumerate(unit)}
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_units,) + s.shape, s.dtype),
        per_unit)
    cache: Dict[str, Any] = {"units": stacked}
    if n_tail:
        cache["tail"] = {f"l{i}": layer_cache_shape(cfg, unit[i][0], batch,
                                                    seq_len, long_context)
                         for i in range(n_tail)}
    if cfg.is_encdec:
        a = cfg.attention
        xkv = jax.ShapeDtypeStruct(
            (cfg.num_layers, batch, cfg.encoder_seq, a.num_kv_heads,
             a.head_dim), jnp.dtype(cfg.dtype))
        cache["cross"] = {"k": xkv, "v": xkv}
    return cache


def cache_logical_axes(tree) -> Any:
    """Logical sharding axes for a cache tree built by ``cache_shape``."""
    def leaf_axes(path, s):
        names = [p.key for p in path if hasattr(p, "key")]
        nd = len(s.shape)
        if names[-1] in ("k", "v"):
            if names[0] == "cross" or names[0] == "units":
                # (L?, B, Sc, KVH, D)
                base = ("batch", "cache_seq", "kv_heads", None)
                return ("layers",) + base if nd == 5 else base
            return ("batch", "cache_seq", "kv_heads", None)
        if names[-1] == "wkv":
            base = ("batch", "heads", None, None)
            return ("layers",) + base if nd == 5 else base
        if names[-1] == "h":
            base = ("batch", "state")
            return ("layers",) + base if nd == 3 else base
        if names[-1] == "conv":
            base = ("batch", None, "state")
            return ("layers",) + base if nd == 4 else base
        if names[-1] in ("shift_tm", "shift_cm"):
            base = ("batch", "embed")
            return ("layers",) + base if nd == 3 else base
        return (None,) * nd

    return jax.tree_util.tree_map_with_path(leaf_axes, tree)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               long_context: bool = False):
    """Zero-initialised concrete cache."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shape(cfg, batch, seq_len, long_context))


def pad_cache(cache, cfg: ModelConfig, prompt_len: int, target_len: int):
    """Extend a prefill-produced cache so decode can run past the prompt.

    Attention k/v entries are padded with zero slots up to ``target_len``
    (windowed layers stay at their window size) and rolled so the ring
    invariant — slot i holds position = i (mod Sc) — is restored; the
    padded slots are excluded by :func:`cache_slot_validity` until they
    are written.  Recurrent / cross entries are O(1) state: untouched."""
    a = cfg.attention
    unit = unit_pattern(cfg)

    def leaf(path, x):
        names = [p.key for p in path if hasattr(p, "key")]
        if names[-1] not in ("k", "v") or names[0] == "cross":
            return x
        li = int(names[1][1:]) if names[1].startswith("l") else 0
        kind = unit[li % len(unit)][0]
        cap = (a.sliding_window
               if (kind == LOCAL_ATTN and a.sliding_window) else None)
        tgt = min(target_len, cap) if cap else target_len
        axis = x.ndim - 3                       # the cache_seq dim
        Sc = x.shape[axis]
        if Sc >= tgt:
            # already at (or beyond) target; restore ring alignment if the
            # prefill truncated to a window (slot j held prompt_len-Sc+j)
            if prompt_len > Sc:
                return jnp.roll(x, prompt_len % Sc, axis=axis)
            return x
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, tgt - Sc)
        return jnp.pad(x, pad)

    return jax.tree_util.tree_map_with_path(leaf, cache)


# ---------------------------------------------------------------------------
# Decode layer application
# ---------------------------------------------------------------------------
def _decode_window(cfg: ModelConfig, entry: Dict[str, Any]) -> Optional[int]:
    """Effective attention window for a decode cache entry.

    A layer decodes against a ring of size Sc; the window is Sc whenever the
    cache was sized BY a window (sliding_window or the long-context
    variant), and None (full attention over valid slots) otherwise."""
    a = cfg.attention
    Sc = entry["k"].shape[1]
    if a.sliding_window and Sc == a.sliding_window:
        return a.sliding_window
    if Sc == a.long_context_window:
        return a.long_context_window
    return None


def _sinusoidal_at(position: jax.Array, d: int) -> jax.Array:
    half = jnp.arange(0, d, 2, dtype=jnp.float32)
    div = jnp.exp(half * (-jnp.log(10000.0) / d))
    ang = position.astype(jnp.float32) * div
    pe = jnp.zeros((d,), jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(ang))
    pe = pe.at[1::2].set(jnp.cos(ang))
    return pe


def _apply_layer_decode(p: P.Params, x: jax.Array, cfg: ModelConfig,
                        kind: str, use_moe: bool, entry: Dict[str, Any],
                        position: jax.Array
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    h = P.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL_ATTN):
        h, new_entry = A.attn_decode(p["mix"], h, entry, cfg.attention,
                                     position, cfg.norm_eps,
                                     window=_decode_window(cfg, entry))
    elif kind == RECURRENT:
        h, st = G.rglru_apply(p["mix"], h, cfg,
                              state={"h": entry["h"], "conv": entry["conv"]})
        new_entry = st
    elif kind == RWKV:
        h, st = R.timemix_apply(p["mix"], h, cfg,
                                state={"shift": entry["shift_tm"],
                                       "wkv": entry["wkv"]})
        new_entry = {"shift_tm": st["shift"], "wkv": st["wkv"],
                     "shift_cm": entry["shift_cm"]}
    else:
        raise ValueError(kind)
    x = x + h
    h = P.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if kind == RWKV:
        h, shift_cm = R.channelmix_apply(p["mlp"], h, state=entry["shift_cm"])
        new_entry = dict(new_entry, shift_cm=shift_cm)
    elif use_moe:
        h, _ = moe_apply(p["mlp"], h, cfg.moe, cfg.act, cfg.glu, chunk=1)
    else:
        h = mlp_apply(p["mlp"], h, cfg.act, cfg.glu)
    return x + h, new_entry


def _cross_decode(p: P.Params, x: jax.Array, xk: jax.Array, xv: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    a = cfg.attention
    B = x.shape[0]
    h = P.rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    q = P.dense_apply(p["attn"]["q"], h, h.dtype).reshape(
        B, 1, a.num_heads, a.head_dim)
    # cross k/v are precomputed: attend directly
    KVH = xk.shape[2]
    G_ = a.num_heads // KVH
    qg = q.reshape(B, KVH, G_, a.head_dim)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, xk,
                   preferred_element_type=jnp.float32) / (a.head_dim ** 0.5)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr.astype(xv.dtype), xv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, a.num_heads * a.head_dim).astype(x.dtype)
    return x + P.dense_apply(p["attn"]["o"], o, x.dtype)


# ---------------------------------------------------------------------------
# serve_step: one-token decode
# ---------------------------------------------------------------------------
def decode_step(params: P.Params, cfg: ModelConfig, tokens: jax.Array,
                cache, position: jax.Array
                ) -> Tuple[jax.Array, Any]:
    """tokens: (B, 1) int32; returns (logits (B, Vp), new cache)."""
    x = embed_tokens(params, cfg, tokens)
    if cfg.attention.rope_theta == 0:
        x = x + _sinusoidal_at(position, cfg.d_model).astype(x.dtype)[None, None]
    unit = unit_pattern(cfg)
    n_units, n_tail = unit_counts(cfg)

    if cfg.is_encdec:
        def body(x, ps):
            up, cp, xk, xv, entry = ps
            h = P.rmsnorm_apply(up["l0"]["norm1"], x, cfg.norm_eps)
            h, new_entry = A.attn_decode(up["l0"]["mix"], h, entry["l0"],
                                         cfg.attention, position,
                                         cfg.norm_eps)
            x = x + h
            x = _cross_decode(cp, x, xk, xv, cfg)
            h = P.rmsnorm_apply(up["l0"]["norm2"], x, cfg.norm_eps)
            x = x + mlp_apply(up["l0"]["mlp"], h, cfg.act, cfg.glu)
            return x, {"l0": new_entry}

        x, new_units = jax.lax.scan(
            body, x, (params["units"], params["cross"]["layers"],
                      cache["cross"]["k"], cache["cross"]["v"],
                      cache["units"]))
        new_cache = dict(cache, units=new_units)
    else:
        def body(x, ps):
            up, entries = ps
            new_entries = {}
            for i, (kind, use_moe) in enumerate(unit):
                x, ne = _apply_layer_decode(up[f"l{i}"], x, cfg, kind,
                                            use_moe, entries[f"l{i}"],
                                            position)
                new_entries[f"l{i}"] = ne
            return x, new_entries

        x, new_units = jax.lax.scan(body, x, (params["units"],
                                              cache["units"]))
        new_cache = dict(cache, units=new_units)
        for i in range(n_tail):
            kind, use_moe = unit[i]
            x, ne = _apply_layer_decode(params["tail"][f"l{i}"], x, cfg,
                                        kind, use_moe, cache["tail"][f"l{i}"],
                                        position)
            new_cache["tail"] = dict(new_cache.get("tail", {}), **{f"l{i}": ne})
    x = P.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x[:, 0, :])
    logits = L.constrain(logits, ("batch", "vocab"))
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill: process a prompt, build the cache, return last-token logits
# ---------------------------------------------------------------------------
def _attn_prefill(p, h, cfg: ModelConfig, kind: str, use_pallas: bool):
    a = cfg.attention
    B, S, _ = h.shape
    window = a.sliding_window if kind == LOCAL_ATTN else a.sliding_window
    q, k, v = A.project_qkv(p, h, a, jnp.arange(S), cfg.norm_eps,
                            compute_dtype=h.dtype)
    if use_pallas:
        from repro.kernels import ops
        out = ops.attention(q, k, v, causal=True, window=window)
    else:
        out = A.blocked_attention(q, k, v, causal=True, window=window)
    out = out.reshape(B, S, a.num_heads * a.head_dim)
    out = P.dense_apply(p["o"], out, h.dtype)
    if kind == LOCAL_ATTN and a.sliding_window and S > a.sliding_window:
        k, v = k[:, -a.sliding_window:], v[:, -a.sliding_window:]
    return L.constrain(out, ("batch", "seq", "embed")), {"k": k, "v": v}


def _apply_layer_prefill(p, x, cfg: ModelConfig, kind: str, use_moe: bool,
                         use_pallas: bool):
    h = P.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL_ATTN):
        h, entry = _attn_prefill(p["mix"], h, cfg, kind, use_pallas)
    elif kind == RECURRENT:
        h, st = G.rglru_apply(p["mix"], h, cfg, use_pallas=use_pallas)
        entry = st
    elif kind == RWKV:
        h, st = R.timemix_apply(p["mix"], h, cfg, use_pallas=use_pallas)
        entry = {"shift_tm": st["shift"], "wkv": st["wkv"]}
    else:
        raise ValueError(kind)
    x = x + h
    h = P.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if kind == RWKV:
        h, shift_cm = R.channelmix_apply(p["mlp"], h)
        entry = dict(entry, shift_cm=shift_cm)
    elif use_moe:
        h, _ = moe_apply(p["mlp"], h, cfg.moe, cfg.act, cfg.glu)
    else:
        h = mlp_apply(p["mlp"], h, cfg.act, cfg.glu)
    return x + h, entry


def prefill(params: P.Params, cfg: ModelConfig, batch: Dict[str, Any],
            use_pallas: bool = False) -> Tuple[jax.Array, Any]:
    """batch: {'tokens': (B,S)} (+ 'frames'/'prefix').

    Returns (last-token logits (B, Vp), cache)."""
    from repro.models.transformer import encode
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend.kind == "vision" and "prefix" in batch:
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
    if cfg.attention.rope_theta == 0:
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model
                                     ).astype(x.dtype)[None]
    unit = unit_pattern(cfg)
    n_units, n_tail = unit_counts(cfg)
    cache: Dict[str, Any] = {}

    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["frames"], use_pallas)
        a = cfg.attention

        def body(x, ps):
            up, cp = ps
            h = P.rmsnorm_apply(up["l0"]["norm1"], x, cfg.norm_eps)
            h, entry = _attn_prefill(up["l0"]["mix"], h, cfg, ATTN,
                                     use_pallas)
            x = x + h
            from repro.models.transformer import cross_attend
            h2 = P.rmsnorm_apply(cp["norm"], x, cfg.norm_eps)
            x = x + cross_attend(cp["attn"], h2, enc_out, cfg, use_pallas)
            B, F = enc_out.shape[0], enc_out.shape[1]
            xk = P.dense_apply(cp["attn"]["k"], enc_out, x.dtype).reshape(
                B, F, a.num_kv_heads, a.head_dim)
            xv = P.dense_apply(cp["attn"]["v"], enc_out, x.dtype).reshape(
                B, F, a.num_kv_heads, a.head_dim)
            h = P.rmsnorm_apply(up["l0"]["norm2"], x, cfg.norm_eps)
            x = x + mlp_apply(up["l0"]["mlp"], h, cfg.act, cfg.glu)
            return x, ({"l0": entry}, xk, xv)

        x, (units_cache, xks, xvs) = jax.lax.scan(
            body, x, (params["units"], params["cross"]["layers"]))
        cache["units"] = units_cache
        cache["cross"] = {"k": xks, "v": xvs}
    else:
        def body(x, up):
            entries = {}
            for i, (kind, use_moe) in enumerate(unit):
                x, e = _apply_layer_prefill(up[f"l{i}"], x, cfg, kind,
                                            use_moe, use_pallas)
                entries[f"l{i}"] = e
            return x, entries

        x, units_cache = jax.lax.scan(body, x, params["units"])
        cache["units"] = units_cache
        if n_tail:
            cache["tail"] = {}
            for i in range(n_tail):
                kind, use_moe = unit[i]
                x, e = _apply_layer_prefill(params["tail"][f"l{i}"], x, cfg,
                                            kind, use_moe, use_pallas)
                cache["tail"][f"l{i}"] = e
    x = P.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x[:, -1, :])
    return L.constrain(logits, ("batch", "vocab")), cache
