"""Flash attention Pallas TPU kernel.

Blocked online-softmax attention with explicit VMEM BlockSpecs.  TPU
adaptation of the classic GPU flash attention: instead of warp-level
softmax reductions, the kernel keeps a (q_block, kv_block) score tile
resident in VMEM, uses the MXU for the two contractions (tile sizes are
multiples of 128 on the contracting/lane dims), and carries the running
max / denominator in VMEM scratch across the kv grid dimension.  Causal
and sliding-window masks are applied from block-relative position ids;
fully-masked kv blocks are skipped via the grid (causal upper-triangle
blocks simply contribute zero — masked before exp).

Grid: (batch*kv_heads, q_blocks, kv_blocks); the kv dimension is the
innermost (sequential on TPU) so the scratch carries across it.

Validated in interpret mode against ``ref.attention_reference``
(tests/test_kernels.py sweeps shapes & dtypes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: Optional[int], q_block: int,
                  kv_block: int, num_kv_blocks: int, scale: float):
    kj = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (qb, G, D)  G = heads per kv head
    k = k_ref[0]                       # (kb, D)
    v = v_ref[0]                       # (kb, D)
    qb, G, D = q.shape
    kb = k.shape[0]
    # scores: (qb*G, kb) via MXU
    s = jax.lax.dot_general(
        q.reshape(qb * G, D).astype(jnp.float32),
        k.astype(jnp.float32).T,
        (((1,), (0,)), ((), ()))) * scale
    s = s.reshape(qb, G, kb)
    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (qb, G, kb), 0)
    k_pos = kj * kv_block + jax.lax.broadcasted_iota(jnp.int32, (qb, G, kb), 2)
    d = q_pos - k_pos
    ok = jnp.ones(d.shape, jnp.bool_)
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                # (qb, G)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    # fully-masked rows: m stays NEG_INF; exp(NEG_INF - NEG_INF)=1 would
    # pollute — zero those
    p = jnp.where((m_new <= NEG_INF / 2)[..., None], 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p.reshape(qb * G, kb), v.astype(jnp.float32),
        (((1,), (0,)), ((), ()))).reshape(qb, G, D)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]  # noqa: E741 -- canonical FA accumulator name
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)[..., None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    q_block: int = 256, kv_block: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, Sk, KVH, D); H % KVH == 0.

    ``interpret=True`` runs the kernel body on any backend
    (:mod:`repro.kernels.ops` chooses by backend).
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    assert Sq % q_block == 0 and Sk % kv_block == 0
    nq, nk = Sq // q_block, Sk // kv_block
    scale = 1.0 / (D ** 0.5)

    # layout: (B*KVH, Sq, G, D) so one grid row sees one kv head
    qr = q.reshape(B, Sq, KVH, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B * KVH, Sq, G, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, D)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, num_kv_blocks=nk, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B * KVH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, G, D), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, kv_block, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, G, D), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, Sq, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, G), jnp.float32),
            pltpu.VMEM((q_block, G), jnp.float32),
            pltpu.VMEM((q_block, G, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KVH, Sq, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Sq, H, D)

