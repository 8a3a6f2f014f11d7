"""RWKV6 (Finch) WKV recurrence as a Pallas TPU kernel.

TPU adaptation of the CUDA WKV kernel: the GPU version assigns one thread
per channel and shuffles within warps; on TPU we instead keep the
(N x N) per-head state resident in VMEM scratch and step time inside the
kernel with a ``fori_loop`` of VPU (element-wise / outer-product) ops —
no MXU needed, the recurrence is rank-1 per step.  The grid walks
(batch*heads, time-blocks) with time innermost-sequential so the state
scratch carries across blocks; r/k/v/w stream through VMEM one
(time_block, N) tile at a time.

Validated against ``ref.rwkv6_reference`` in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_scr, *, t_block: int, num_t_blocks: int):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    r = r_ref[0]          # (T, N)
    k = k_ref[0]
    v = v_ref[0]
    w = w_ref[0]
    u = u_ref[0]          # (N,)

    def step(t, S):
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)[0]     # (N,)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)[0]
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)[0]
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)[0]
        kv = kt[:, None] * vt[None, :]                       # (N, N)
        y = jnp.sum(rt[:, None] * (S + u[:, None] * kv), axis=0)
        y_ref[0, t, :] = y
        return wt[:, None] * S + kv

    S = jax.lax.fori_loop(0, t_block, step, s_scr[...])
    s_scr[...] = S

    @pl.when(tj == num_t_blocks - 1)
    def _done():
        sout_ref[0] = S


@functools.partial(jax.jit, static_argnames=("t_block", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, state0: jax.Array, t_block: int = 64,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B,S,H,N) f32; u: (H,N); state0: (B,H,N,N).

    Returns (y (B,S,H,N), final state (B,H,N,N))."""
    B, S, H, N = r.shape
    t_block = min(t_block, S)
    assert S % t_block == 0
    nt = S // t_block
    # (B*H, S, N) layout so one grid row owns one head's state
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    rr, kk, vv, ww = map(to_bh, (r, k, v, w))
    uu = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    s0 = state0.reshape(B * H, N, N).astype(jnp.float32)

    kernel = functools.partial(_wkv_kernel, t_block=t_block,
                               num_t_blocks=nt)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, t_block, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, t_block, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, t_block, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, t_block, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N), lambda b, t: (b, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t_block, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, uu, s0)
    y = y.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return y, s_out.reshape(B, H, N, N)
