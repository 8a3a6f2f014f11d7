"""Jit'd public wrappers for the Pallas kernels.

One entry point per kernel with a ``backend`` switch:
  * "pallas"     — pl.pallas_call, compiled on a TPU and run by the
                   Pallas interpreter on any other backend.
  * "xla"        — the pure-jnp reference path (what the dry-run lowers;
                   also the oracle used by the parity tests).

The model code takes ``use_pallas`` flags and routes through these; the
kernels themselves default to compiled mode.  :func:`recon_out_layer`
is the exception: it chooses by the platform it is lowered for.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from repro.kernels import flash_attention as _fa
from repro.kernels import recon_out as _ro
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import tolfl_combine as _tc


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def attention(q, k, v, causal: bool = True, window: Optional[int] = None,
              backend: str = "pallas") -> jax.Array:
    if backend == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=_interpret())
    return _ref.attention_reference(q, k, v, causal=causal, window=window)


def rwkv6(r, k, v, w, u, state0, backend: str = "pallas"
          ) -> Tuple[jax.Array, jax.Array]:
    if backend == "pallas":
        return _rw.rwkv6_scan(r, k, v, w, u, state0,
                              interpret=_interpret())
    return _ref.rwkv6_reference(r, k, v, w, u, state0)


def rglru(a_t, b_t, h0=None, backend: str = "pallas") -> jax.Array:
    if backend == "pallas":
        return _rg.rglru_scan(a_t, b_t, h0, interpret=_interpret())
    if h0 is not None:
        b_t = b_t.at[:, 0, :].add(a_t[:, 0, :] * h0)
    return _ref.rglru_reference(a_t, b_t)


def tolfl_combine(gs, ns, backend: str = "pallas") -> jax.Array:
    if backend == "pallas":
        return _tc.tolfl_combine(gs, ns, interpret=_interpret())
    return _ref.tolfl_combine_reference(gs, ns)


#: the narrowest reconstruction that takes :func:`recon_out_layer`'s
#: kernel: below it the wide tensors are small and the layer sits under
#: a few MXU tiles, so XLA's fusions hold their own
RECON_OUT_MIN_WIDTH = 1024


def recon_out_applies(width: int) -> bool:
    """Whether an autoencoder of reconstruction width ``width`` takes
    :func:`recon_out_layer` (at the configured matmul precision)."""
    return (width >= RECON_OUT_MIN_WIDTH and width % 128 == 0
            and _ro.passes() is not None)


def _recon_out_fused(h, w, b, x, valid, grads: bool):
    row_weight = valid / jnp.maximum(jnp.sum(valid), 1.0)
    return _ro.recon_out(h, w, b, x, row_weight, grads=grads,
                         n_passes=_ro.passes())


def _recon_out_xla(h, w, b, x, valid):
    loss, (dh, dw, db) = jax.value_and_grad(
        _ref.recon_out_reference, argnums=(0, 1, 2))(h, w, b, x, valid)
    return loss, dw, db, dh


@jax.custom_vjp
def recon_out_layer(h, w, b, x, valid) -> jax.Array:
    """``ref.recon_out_reference(h, w, b, x, valid)``: the last layer of
    the autoencoder and its masked reconstruction loss, for one device's
    rows (h (rows, H), w (H, F), b (F,), x (rows, F), valid (rows,)).

    Lowered for a TPU it is one call of the fused kernel
    (:mod:`repro.kernels.recon_out`), which also returns the gradients
    wrt (h, w, b) when the loss is differentiated; lowered for any
    other platform it is the plain expression.  ``x`` and ``valid`` are
    data: differentiating wrt them raises."""
    return jax.lax.platform_dependent(
        h, w, b, x, valid,
        tpu=lambda *a: _recon_out_fused(*a, grads=False)[0],
        default=_ref.recon_out_reference)


def _recon_out_layer_fwd(h, w, b, x, valid):
    if x.perturbed or valid.perturbed:
        raise NotImplementedError(
            "recon_out_layer differentiates wrt h, w and b only")
    args = [a.value for a in (h, w, b, x, valid)]
    loss, dw, db, dh = jax.lax.platform_dependent(
        *args, tpu=functools.partial(_recon_out_fused, grads=True),
        default=_recon_out_xla)
    return loss, (dh, dw, db)


def _recon_out_layer_bwd(res, ct):
    if isinstance(ct, SymbolicZero):
        return (None,) * 5
    dh, dw, db = res
    # the loss is a scalar: the unit cotangent's gradients, scaled
    return ct * dh, ct * dw, ct * db, None, None


recon_out_layer.defvjp(_recon_out_layer_fwd, _recon_out_layer_bwd,
                       symbolic_zeros=True)
