"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  Single pod: 16x16 = 256 chips
(data x model).  Multi-pod: 2x16x16 = 512 chips (pod x data x model); the
"pod" axis is the outer Tol-FL SBT ring (DESIGN.md section 2).
"""
from __future__ import annotations

import jax

from repro.configs.base import MeshConfig
from repro.sharding.logical import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_config(cfg: MeshConfig):
    if cfg.multi_pod:
        return make_mesh((cfg.pods, cfg.data, cfg.model),
                         ("pod", "data", "model"))
    return make_mesh((cfg.data, cfg.model), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the host actually has (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))
