"""Tol-FL streaming weighted-mean combine as a Pallas TPU kernel.

The paper's core arithmetic (Algorithm 1/2): fold k cluster gradients into
a running sample-weighted mean.  On the cluster-head chip this is a pure
bandwidth op over the flattened gradient; the fused kernel streams one
(k, block) tile of the stacked gradients through VMEM and performs the
whole k-step recurrence per block — gradients are read exactly once and
no (k x P) intermediate or k separate elementwise kernels exist (the
XLA fallback materialises the scan carries).  The k sample counts live
in SMEM.

Validated against ``ref.tolfl_combine_reference`` in interpret mode; the
k-invariance property (streaming == direct weighted mean) is
hypothesis-tested in tests/test_tolfl_invariance.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(n_ref, g_ref, o_ref):
    """n_ref: (k,) sample counts in SMEM; g_ref: (k, block) gradient
    tile; o_ref: (1, block).  k is static, so the recurrence unrolls and
    every read is a static row of the tile."""
    k = g_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    tot = jnp.zeros((), jnp.float32)
    for i in range(k):
        ni = n_ref[i]
        tot = tot + ni
        r = jnp.where(tot > 0, ni / jnp.maximum(tot, 1e-30), 0.0)
        acc = (1.0 - r) * acc + r * g_ref[i:i + 1, :]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tolfl_combine(gs: jax.Array, ns: jax.Array, block: int = 4096,
                  interpret: bool = False) -> jax.Array:
    """gs: (k, P) stacked flattened cluster gradients (f32);
    ns: (k,) sample counts.  Returns the Tol-FL combined gradient (P,).

    Compiled for the TPU, ``block`` must be a multiple of 128 or cover
    all of P; ``interpret=True`` runs the kernel body on any backend
    (:mod:`repro.kernels.ops` chooses by backend)."""
    k, P = gs.shape
    block = min(block, P)
    pad = (-P) % block
    if pad:
        gs = jnp.pad(gs, ((0, 0), (0, pad)))
    nb = gs.shape[1] // block
    out = pl.pallas_call(
        _combine_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k, block), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, gs.shape[1]), jnp.float32),
        interpret=interpret,
    )(ns.astype(jnp.float32), gs.astype(jnp.float32))
    return out[0, :P]


def tolfl_combine_tree(gs_tree, ns, interpret: bool = False):
    """Apply the fused combine leaf-wise over a stacked gradient pytree."""
    def leaf(g):
        k = g.shape[0]
        flat = g.reshape(k, -1).astype(jnp.float32)
        return tolfl_combine(flat, ns, interpret=interpret).reshape(
            g.shape[1:]).astype(g.dtype)
    return jax.tree.map(leaf, gs_tree)
