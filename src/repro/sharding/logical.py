"""Logical-axis sharding rules (MaxText-style).

Every parameter/activation dimension gets a *logical* axis name; a rules
table maps logical names to physical mesh axes ("pod", "data", "model").
``spec_for(axes, shape)`` builds a PartitionSpec, dropping any mapping whose
dimension is not divisible by the mesh-axis size (GSPMD/jit reject uneven
shardings — e.g. 40 heads over a 16-wide model axis stay replicated and the
partitioner shards the surrounding matmuls instead).

Two storage modes (DESIGN.md section 2):

* ``replicated_data`` — params sharded over "model" only, replicated over
  the Tol-FL data axis.  Required for the paper-faithful ring schedule
  (each federated group holds a full model replica) and for E>1 local
  epochs (per-group params diverge between syncs).
* ``fsdp`` — params additionally sharded over "data" on the d_model dims.
  Required for the 100B+ architectures; only compatible with the
  weighted-psum schedule at E=1 (gradient sync every step).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as PS

# Logical axis vocabulary.
BATCH = "batch"
SEQ = "seq"
EMBED = "embed"          # d_model dims
FF = "ff"                # mlp hidden
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
VOCAB = "vocab"
EXPERTS = "experts"
LAYERS = "layers"        # stacked scan dim
CACHE_SEQ = "cache_seq"  # kv-cache length dim (sequence-parallel decode)
STATE = "state"          # recurrent state width
CONV = "conv"

# rules: logical -> physical mesh axis (or tuple, or None)
BASE_RULES = {
    BATCH: ("pod", "data"),
    SEQ: None,
    EMBED: None,
    FF: "model",
    HEADS: "model",
    KV_HEADS: "model",
    HEAD_DIM: None,
    VOCAB: "model",
    EXPERTS: "model",
    LAYERS: None,
    CACHE_SEQ: ("pod", "data"),   # flash-decoding style sequence-parallel cache
    STATE: "model",
    CONV: None,
}

# FSDP overlay: additionally shard the d_model dims over the data axis
# (storage + all-gather at use; gradient reduce-scatter is the weighted-psum
# Tol-FL schedule).
FSDP_RULES = dict(BASE_RULES)
FSDP_RULES.update({
    EMBED: ("pod", "data"),
})


def rules_for(mode: str = "replicated_data") -> dict:
    return FSDP_RULES if mode == "fsdp" else dict(BASE_RULES)


# ---------------------------------------------------------------------------
# Active mesh plumbing.  Launchers call ``activate_mesh``; model code calls
# ``constrain`` which is a no-op when no mesh is active (CPU tests).
# ---------------------------------------------------------------------------
_STATE = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


def current_rules() -> dict:
    return getattr(_STATE, "rules", BASE_RULES)


@contextlib.contextmanager
def activate_mesh(mesh: Mesh, rules: Optional[dict] = None):
    # NOTE: deliberately NOT entering `with mesh:`/use_mesh — an ambient
    # mesh makes array-creation ops inherit context shardings, which
    # conflicts inside partial-manual shard_map regions (Manual vs Auto
    # axis types).  All shardings here are explicit NamedShardings.
    prev = (current_mesh(), current_rules())
    _STATE.mesh, _STATE.rules = mesh, (rules or BASE_RULES)
    try:
        yield mesh
    finally:
        _STATE.mesh, _STATE.rules = prev


def current_manual() -> frozenset:
    return getattr(_STATE, "manual", frozenset())


@contextlib.contextmanager
def manual_axes(names):
    """Mark mesh axes as shard_map-manual: ``constrain`` then omits them
    (inside the manual region each shard only sees its slice, and
    with_sharding_constraint may not mention manual axes)."""
    prev = current_manual()
    _STATE.manual = prev | frozenset(names)
    try:
        yield
    finally:
        _STATE.manual = prev


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def spec_for(axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None,
             rules: Optional[dict] = None,
             mesh: Optional[Mesh] = None) -> PS:
    """PartitionSpec from logical axis names, dropping uneven shardings.

    Mesh axes absent from the active mesh (e.g. "pod" on single-pod) are
    dropped; a physical axis is used at most once per spec.
    """
    rules = rules or current_rules()
    mesh = mesh or current_mesh()
    if mesh is None:
        return PS()
    avail = set(mesh.axis_names)
    used = set()
    out = []
    for i, ax in enumerate(axes):
        phys = rules.get(ax) if ax is not None else None
        if phys is None:
            out.append(None)
            continue
        if isinstance(phys, str):
            phys = (phys,)
        keep = []
        prod = 1
        for p in phys:
            if p not in avail or p in used:
                continue
            sz = _axis_size(mesh, p)
            if shape is not None and shape[i] % (prod * sz) != 0:
                continue
            keep.append(p)
            prod *= sz
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    return PS(*out)


def constrain(x: jax.Array, axes: Sequence[Optional[str]],
              rules: Optional[dict] = None) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op without one)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(axes, np.shape(x), rules, mesh)
    manual = current_manual()
    if manual:
        spec = PS(*[
            (None if p is None or (isinstance(p, str) and p in manual)
             else (tuple(q for q in p if q not in manual) or None
                   if isinstance(p, tuple) else p))
            for p in spec])
    if all(p is None for p in spec):
        # fully-replicated constraint is a no-op; skipping it also keeps
        # fully-manual shard_map regions (every axis manual) legal
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def sharding_for(mesh: Mesh, axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None,
                 rules: Optional[dict] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(axes, shape, rules, mesh))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ---------------------------------------------------------------------------
# Mesh construction and shard_map (shared by the launchers, the mesh train
# step and the campaign scenario-sharding executor).
# ---------------------------------------------------------------------------
def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Device mesh whose axes are all ``Auto`` (GSPMD-partitioned).

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` (:func:`constrain`) rejects; every mesh
    in the repo is built here so the axis types are stated once."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def scenario_shard_map(f, ndev: int, n_bcast: int, n_mapped: int):
    """Shard a batched campaign executable over an ``(ndev,)``-device
    "scenario" mesh axis: the leading ``n_bcast`` arguments (data /
    topology broadcasts) are replicated, the trailing ``n_mapped``
    arguments (the flattened (cell x trace x seed) scenario operands —
    stacked topology arrays, trace pytrees, seeds) are split on their
    leading axis, as are all outputs.  Centralised here so the campaign
    engine's fused and per-cell dispatches shard through one spec
    builder (the caller pads the batch to a device-divisible size)."""
    mesh = make_mesh((ndev,), ("scenario",))
    specs = (PS(),) * n_bcast + (PS("scenario"),) * n_mapped
    return manual_shard_map(f, mesh, in_specs=specs,
                            out_specs=PS("scenario"))


def manual_shard_map(f, mesh: Mesh, in_specs, out_specs, manual=None):
    """``jax.shard_map`` manual over the ``manual`` axes (all mesh axes
    when None) and auto (GSPMD) over the rest."""
    names = set(manual) if manual is not None else set(mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=names,
                         check_vma=False)
