"""Jit'd public wrappers for the Pallas kernels.

One entry point per kernel with a ``backend`` switch:
  * "pallas"     — pl.pallas_call, compiled on a TPU and run by the
                   Pallas interpreter on any other backend.
  * "xla"        — the pure-jnp reference path (what the dry-run lowers;
                   also the oracle used by the parity tests).

The model code takes ``use_pallas`` flags and routes through these; the
kernels themselves default to compiled mode.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels import flash_attention as _fa
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
