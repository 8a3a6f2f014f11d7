"""RG-LRU diagonal linear recurrence as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, purely element-wise over the channel dim — the
kernel blocks channels across the grid (embarrassingly parallel) and steps
time inside with a ``fori_loop``, carrying h in a VMEM scratch row
across time blocks.  This is the Pallas counterpart of the
``associative_scan`` jnp path: the scan does O(log S) depth with O(S)
memory traffic multiplier; the kernel does one sequential sweep with every
operand touched exactly once — the classic latency-vs-traffic trade the
perf log discusses.

Validated against ``ref.rglru_reference`` in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, h0_ref, h_ref, h_scr, *, t_block: int):
    """One (t_block, w_block) tile per grid step; h carries across the
    time blocks in the (1, w_block) VMEM scratch."""
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    def step(t, h):
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]
        h_ref[0, pl.ds(t, 1), :] = h
        return h

    h_scr[...] = jax.lax.fori_loop(0, t_block, step, h_scr[...])


@functools.partial(jax.jit, static_argnames=("t_block", "w_block",
                                             "interpret"))
def rglru_scan(a_t: jax.Array, b_t: jax.Array,
               h0: Optional[jax.Array] = None, t_block: int = 128,
               w_block: int = 512, interpret: bool = False) -> jax.Array:
    """a_t, b_t: (B, S, W) f32; h0: (B, W) or None.  Returns h (B, S, W).

    Compiled for the TPU, ``t_block`` must be a multiple of 8 or S and
    ``w_block`` a multiple of 128 or W; ``interpret=True`` runs the
    kernel body on any backend (:mod:`repro.kernels.ops` chooses by
    backend)."""
    B, S, W = a_t.shape
    t_block = min(t_block, S)
    w_block = min(w_block, W)
    assert S % t_block == 0 and W % w_block == 0
    nt, nw = S // t_block, W // w_block
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)

    kernel = functools.partial(_lru_kernel, t_block=t_block)
    tile = pl.BlockSpec((1, t_block, w_block),
                        lambda bw, t: (bw // nw, t, bw % nw))
    h = pl.pallas_call(
        kernel,
        grid=(B * nw, nt),
        in_specs=[
            tile,
            tile,
            pl.BlockSpec((1, 1, w_block),
                         lambda bw, t: (bw // nw, 0, bw % nw)),
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, w_block), jnp.float32)],
        interpret=interpret,
    )(a_t, b_t, h0.reshape(B, 1, W))
    return h
