"""Pluggable per-device detector bodies for the Tol-FL campaign engine.

The paper's engine is model-agnostic: any detector with a masked training
loss and a per-sample anomaly score slots into the flat/star hybrid.  A
:class:`DetectorModel` is a **frozen, hashable spec** (a dataclass, like
the engine configs) exposing

* ``init_params(key)``          -> params pytree
* ``loss(params, x, valid, key)``  masked mean reconstruction loss;
  ``key=None`` disables dropout (evaluation / dropout-free training)
* ``anomaly_scores(params, x)`` -> (B,) per-sample scores
* ``param_count()`` / ``param_bytes()``  for the comm-cost models

Specs are closed over by the jitted campaign cores and are part of the
executable cache key (``campaign._exe_key``), so they MUST be frozen
dataclasses with value equality — ``plancheck.cachekey`` enforces this
structurally for every registered spec class.

Two bodies ship by default:

* :class:`AutoencoderDetector` — the paper's fully-connected autoencoder
  (the default; ``canonical_model_key`` maps it back to its raw
  :class:`AutoencoderConfig` so pre-existing cache keys, disk
  fingerprints and result digests are bit-identical).
* :class:`SeqDetector` — a reduced windowed sequence detector: features
  are folded into (seq, window) patches and reconstructed through a
  single RG-LRU recurrent block (``models/rglru.py``).

Budget note: each spec names a ``budget_family`` ("ae", "seq", ...);
``plancheck.budgets`` carries named eqn ceilings per family so campaign
cores built from a new body stay under a measured budget.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.autoencoder_paper import AutoencoderConfig, CONFIG
from repro.configs.base import ModelConfig, RecurrentConfig
from repro.kernels import ops
from repro.models import autoencoder as AE
from repro.models import params as P
from repro.models import rglru as R


# ---------------------------------------------------------------------------
# Interface
# ---------------------------------------------------------------------------
class DetectorModel:
    """Base class for detector specs (concrete specs are frozen dataclasses).

    Methods are pure functions of (params, data) given the frozen spec, so
    they can be closed over by jitted cores; the spec itself never holds
    arrays."""

    #: plancheck budget family — selects the named eqn ceiling the
    #: campaign cores built from this body are checked against.
    budget_family: str = "ae"

    def init_params(self, key) -> P.Params:
        raise NotImplementedError

    def loss(self, params: P.Params, x: jax.Array, valid: jax.Array,
             key: Optional[jax.Array] = None) -> jax.Array:
        raise NotImplementedError

    def anomaly_scores(self, params: P.Params, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def fuses_output_layer(self) -> bool:
        """Whether ``loss``, lowered for a TPU, runs its output layer as
        the fused kernel :func:`repro.kernels.ops.recon_out_layer`."""
        return False

    # ---- derived sizes (comm-cost models / benches) ----
    def param_count(self) -> int:
        return _spec_sizes(self)[0]

    def param_bytes(self) -> int:
        return _spec_sizes(self)[1]


@functools.lru_cache(maxsize=64)
def _spec_sizes(det: DetectorModel) -> Tuple[int, int]:
    """(param_count, param_bytes) of a spec, via one eager tiny init."""
    params = det.init_params(jax.random.PRNGKey(0))
    return P.param_count(params), P.param_bytes(params)


# ---------------------------------------------------------------------------
# Paper autoencoder (default body)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AutoencoderDetector(DetectorModel):
    """The paper's fully-connected autoencoder, behind the interface."""

    cfg: AutoencoderConfig = CONFIG

    budget_family = "ae"

    def init_params(self, key) -> P.Params:
        params, _ = AE.init_params(key, self.cfg)
        return params

    def loss(self, params, x, valid, key=None):
        if self.fuses_output_layer():
            out = params[f"fc{AE.num_layers(self.cfg) - 1}"]
            return ops.recon_out_layer(
                AE.hidden(params, self.cfg, x, dropout_key=key),
                out["w"], out["b"], x, valid)
        x_hat = AE.forward(params, self.cfg, x, dropout_key=key)
        err = jnp.sum(jnp.square(x - x_hat), axis=-1) * valid
        return jnp.sum(err) / jnp.maximum(jnp.sum(valid), 1.0)

    def anomaly_scores(self, params, x):
        return AE.anomaly_scores(params, self.cfg, x)

    def fuses_output_layer(self) -> bool:
        # by shape: wide reconstructions only (ops.recon_out_applies)
        return ops.recon_out_applies(self.cfg.input_dim)


# ---------------------------------------------------------------------------
# Reduced windowed sequence detector (RG-LRU body)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SeqDetector(DetectorModel):
    """Windowed sequence reconstruction through one RG-LRU block.

    The (B, input_dim) feature rows are zero-padded to a multiple of
    ``window`` and folded into (B, seq, window) patches; each patch is
    embedded, run through the Griffin-style RG-LRU recurrence
    (``models/rglru.py``), decoded back to window space, and scored by
    squared reconstruction error — the same loss/score contract as the
    paper autoencoder, so it trains under identical FailureTrace
    campaigns."""

    input_dim: int = 112
    window: int = 16
    d_model: int = 16
    lru_width: Optional[int] = None
    conv1d_width: int = 2
    dropout: float = 0.0
    act: str = "gelu"
    name: str = "seq-rglru"

    budget_family = "seq"

    @property
    def seq_len(self) -> int:
        return -(-self.input_dim // self.window)

    def _model_cfg(self) -> ModelConfig:
        return ModelConfig(
            name=self.name, d_model=self.d_model,
            recurrent=RecurrentConfig(lru_width=self.lru_width,
                                      conv1d_width=self.conv1d_width))

    def _windows(self, x: jax.Array) -> jax.Array:
        pad = self.seq_len * self.window - self.input_dim
        xp = jnp.pad(x, ((0, 0), (0, pad)))
        return xp.reshape(x.shape[0], self.seq_len, self.window)

    def init_params(self, key) -> P.Params:
        k_enc, k_core, k_dec = jax.random.split(key, 3)
        enc, _ = P.dense_init(k_enc, self.window, self.d_model, None, None,
                              "float32", bias=True)
        core, _ = R.rglru_init(k_core, self._model_cfg())
        dec, _ = P.dense_init(k_dec, self.d_model, self.window, None, None,
                              "float32", bias=True)
        return {"enc": enc, "rglru": core, "dec": dec}

    def _reconstruct(self, params, x, dropout_key=None):
        h = P.activation(self.act)(P.dense_apply(params["enc"],
                                                 self._windows(x)))
        h, _ = R.rglru_apply(params["rglru"], h, self._model_cfg())
        if dropout_key is not None and self.dropout > 0.0:
            keep = jax.random.bernoulli(dropout_key, 1.0 - self.dropout,
                                        h.shape)
            h = jnp.where(keep, h / (1.0 - self.dropout), 0.0)
        y = P.dense_apply(params["dec"], h).reshape(x.shape[0], -1)
        return y[:, :self.input_dim]

    def loss(self, params, x, valid, key=None):
        x_hat = self._reconstruct(params, x, dropout_key=key)
        err = jnp.sum(jnp.square(x - x_hat), axis=-1) * valid
        return jnp.sum(err) / jnp.maximum(jnp.sum(valid), 1.0)

    def anomaly_scores(self, params, x):
        x_hat = self._reconstruct(params, x)
        return jnp.sum(jnp.square(x - x_hat), axis=-1)


# ---------------------------------------------------------------------------
# Normalisation / cache-key canonicalisation
# ---------------------------------------------------------------------------
ModelLike = Union[DetectorModel, AutoencoderConfig]


def as_detector(model: ModelLike) -> DetectorModel:
    """Normalise user-facing model specs to a :class:`DetectorModel`.

    Raw :class:`AutoencoderConfig` values (the historical spelling) wrap
    into an :class:`AutoencoderDetector`."""
    if isinstance(model, DetectorModel):
        return model
    if isinstance(model, AutoencoderConfig):
        return AutoencoderDetector(model)
    raise TypeError(
        f"expected a DetectorModel or AutoencoderConfig, got {model!r}")


def canonical_model_key(model: ModelLike):
    """Canonical executable-cache-key component for a model spec.

    Autoencoder specs canonicalise to the raw :class:`AutoencoderConfig`
    — whichever spelling the caller used — so every pre-refactor
    ``_exe_key`` tuple, lru entry and persistent-cache fingerprint
    (``compilecache.exe_fingerprint`` hashes ``repr``) stays
    bit-identical.  Other bodies key on the frozen spec itself."""
    if isinstance(model, AutoencoderDetector):
        return model.cfg
    if isinstance(model, (DetectorModel, AutoencoderConfig)):
        return model
    raise TypeError(
        f"expected a DetectorModel or AutoencoderConfig, got {model!r}")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[..., DetectorModel]] = {}


def register_detector(name: str, factory: Callable[..., DetectorModel]
                      ) -> None:
    """Register a detector body under ``name`` (idempotent re-register of
    the same factory is allowed; silent replacement is not)."""
    prior = _REGISTRY.get(name)
    if prior is not None and prior is not factory:
        raise ValueError(f"detector {name!r} already registered")
    _REGISTRY[name] = factory


def make_detector(name: str, **kwargs) -> DetectorModel:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown detector {name!r}; known: {detector_names()}")
    det = _REGISTRY[name](**kwargs)
    return as_detector(det)


def detector_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def spec_classes() -> Tuple[type, ...]:
    """Registered spec classes (for plancheck's frozen/eq containment
    check over everything that can land inside ``_exe_key``)."""
    return tuple(f for f in _REGISTRY.values() if isinstance(f, type))


register_detector("autoencoder", AutoencoderDetector)
register_detector("seq-rglru", SeqDetector)
