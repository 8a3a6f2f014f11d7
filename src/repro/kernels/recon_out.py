"""The autoencoder's output layer with its reconstruction loss, forward
and backward, as one Pallas TPU kernel.

For one device's rows ``h`` (the last hidden layer, (rows, H)), the
output layer ``W`` (H, F), ``b`` (F,), the rows ``x`` (rows, F) and the
row weights ``w = valid / max(sum(valid), 1)``:

    loss = sum_r w_r * ||x_r - (h_r W + b)||^2
    d    = 2 (h W + b - x) * w          (the loss's cotangent of x_hat)
    dW = h^T d,   db = sum_r d_r,   dh = d W^T

XLA writes x_hat's error ``d`` to HBM as a (rows, F) float32 tensor and
reads it back for each of the two gradient dots.  Here one grid step
loads a tile of ``block_rows`` rows of ``h`` and ``x``, forms x_hat and
``d`` in VMEM, writes the tile's ``dh`` and adds into ``dW``, ``db`` and the loss, which stay in VMEM across the
row tiles (the row axis is ``arbitrary``).  Only ``x`` is read from
HBM at the width F; nothing is written there at that width but ``dW``
and ``db``.  The last row tile may run past the rows: its rows are
masked by index, so nothing read past the end reaches a result.

The matmul precision follows ``jax_default_matmul_precision`` as XLA's
would: one bfloat16 pass by default, three under ``tensorfloat32``
(``hi*hi + hi*lo + lo*hi`` of the bfloat16 split of each float32
operand, accumulated in float32, as XLA's ``bf16_3x``), and Mosaic's
float32 contraction under ``float32``.

``vmap`` adds grid axes in front of the row axis (Pallas's batching
rule); an operand that is not batched, such as ``x`` shared by every
scenario of a campaign bucket, is read where it lies and never
broadcast.

Validated against ``jax.grad`` of the plain expression in interpret
mode (tests/test_kernels.py); compiled for a described v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bfloat16 passes per float32 dot, by ``jax_default_matmul_precision``
#: (6 stands for Mosaic's float32 contraction); a precision that is not
#: listed has no kernel and keeps the XLA expression
PASSES = {None: 1, "default": 1, "bfloat16": 1, "BF16_BF16_F32": 1,
          "high": 3, "tensorfloat32": 3, "BF16_BF16_F32_X3": 3,
          "highest": 6, "float32": 6, "F32_F32_F32": 6}
#: the rows split into tiles of equal size, each of at most this many
#: rows and MAX_TILE elements (a 512-row tile of 3072 timed fastest on a
#: v5e, among 128 to 512 rows)
MAX_BLOCK_ROWS = 512
MAX_TILE = 512 * 3072
#: VMEM the kernel may use (a v5e has 128 MiB; Mosaic's default scope of
#: 16 MiB is under what a tile of MAX_TILE float32 and its terms take)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def passes() -> Optional[int]:
    """The bfloat16 passes the configured matmul precision asks for, or
    None where the kernel has no such precision."""
    return PASSES.get(jax.config.jax_default_matmul_precision)


def _parts(a, n_passes: int) -> tuple:
    """A float32 operand as the terms its dots take: itself for the
    float32 contraction, else its bfloat16 ``hi`` (and ``lo``)."""
    if n_passes == 6:
        return (a,)
    hi = a.astype(jnp.bfloat16)
    if n_passes == 1:
        return (hi,)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(a: tuple, b: tuple, dims, n_passes: int):
    """``dot_general`` of two operands given as :func:`_parts`, at
    ``n_passes``, accumulated in float32 (the small terms first)."""
    dn = (dims, ((), ()))
    prec = (lax.Precision.HIGHEST if n_passes == 6
            else lax.Precision.DEFAULT)    # not the configured default

    def mm(p, q):
        return lax.dot_general(p, q, dn, precision=prec,
                               preferred_element_type=jnp.float32)
    if n_passes != 3:
        return mm(a[0], b[0])
    return (mm(a[0], b[1]) + mm(a[1], b[0])) + mm(a[0], b[0])


_NN = ((1,), (0,))      # (R, H) x (H, F)
_TN = ((0,), (0,))      # (R, H)^T x (R, F)
_NT = ((1,), (1,))      # (R, F) x (H, F)^T


def _kernel(h_ref, w_ref, b_ref, x_ref, wr_ref, *refs, rows: int,
            block_rows: int, n_passes: int, grads: bool):
    n_out = 4 if grads else 1
    outs, w_parts_ref = refs[:n_out], refs[n_out:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for o in outs[:3]:      # loss, dw, db add up over the row tiles
            o[...] = jnp.zeros_like(o)
        # W's bfloat16 terms, once for the row tiles of one W
        for ref, part in zip(w_parts_ref, _parts(w_ref[...], n_passes)):
            ref[...] = part

    h = h_ref[...]
    wr = wr_ref[...]
    ragged = rows % block_rows != 0     # the last tile runs past the rows
    if ragged:
        ok = i * block_rows + lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0) < rows
        h = jnp.where(ok, h, 0.0)
        wr = jnp.where(ok, wr, 0.0)
    h_p = _parts(h, n_passes)
    w_p = tuple(r[...] for r in (w_parts_ref or (w_ref,)))
    diff = _dot(h_p, w_p, _NN, n_passes) + b_ref[...] - x_ref[...]
    if ragged:
        diff = jnp.where(ok, diff, 0.0)
    err = jnp.sum(diff * diff, axis=1, keepdims=True) * wr
    outs[0][...] += jnp.broadcast_to(jnp.sum(err, axis=0, keepdims=True),
                                     outs[0].shape)
    if grads:
        _, dw_ref, db_ref, dh_ref = outs
        d = diff * (2.0 * wr)
        d_p = _parts(d, n_passes)
        dw_ref[...] += _dot(h_p, d_p, _TN, n_passes)
        db_ref[...] += jnp.sum(d, axis=0, keepdims=True)
        dh_ref[...] = _dot(d_p, w_p, _NT, n_passes)


def _block_rows(rows: int, width: int, max_rows: int) -> int:
    """Equal row tiles, each of at most ``max_rows`` rows and
    :data:`MAX_TILE` elements (a multiple of 8 rows), or all the rows in
    one tile."""
    cap = max(8, min(max_rows, MAX_TILE // width) // 8 * 8)
    n = pl.cdiv(rows, cap)
    if n == 1:
        return rows
    return -(-pl.cdiv(rows, n) // 8) * 8


def recon_out(h, w, b, x, row_weight, *, grads: bool, n_passes: int,
              block_rows: Optional[int] = None, interpret: bool = False
              ) -> Tuple[jax.Array, ...]:
    """The fused output layer of one device's rows.

    h (rows, H), w (H, F), b (F,), x (rows, F), row_weight (rows,).
    Returns ``(loss,)``, or with ``grads`` ``(loss, dw, db, dh)`` for a
    unit cotangent of the loss.  Compiled for the TPU, F must be a
    multiple of 128; ``interpret=True`` runs the body on any backend."""
    rows, hid = h.shape
    width = w.shape[1]
    br = _block_rows(rows, width, block_rows or MAX_BLOCK_ROWS)
    kernel = functools.partial(_kernel, rows=rows, block_rows=br,
                               n_passes=n_passes, grads=grads)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa: E731
    tile = lambda cols: pl.BlockSpec((br, cols), lambda i: (i, 0))  # noqa
    out_shape = [jax.ShapeDtypeStruct((1, 128), jnp.float32)]
    out_specs = [full((1, 128))]
    if grads:
        out_shape += [jax.ShapeDtypeStruct((hid, width), jnp.float32),
                      jax.ShapeDtypeStruct((1, width), jnp.float32),
                      jax.ShapeDtypeStruct((rows, hid), jnp.float32)]
        out_specs += [full((hid, width)), full((1, width)), tile(hid)]
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[tile(hid), full((hid, width)), full((1, width)),
                  tile(width), tile(1)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hid, width), jnp.bfloat16)] * (
            0 if n_passes == 6 else n_passes // 2 + 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * hid * width * (3 if grads else 1),
            transcendentals=0,
            bytes_accessed=4 * (rows * width + 2 * hid * width
                                + 2 * rows * hid)),
        interpret=interpret,
        name="recon_out",
    )(h, w, b.reshape(1, width), x, row_weight.reshape(rows, 1))
    loss = outs[0][0, 0]
    if not grads:
        return (loss,)
    _, dw, db, dh = outs
    return loss, dw, db[0], dh
