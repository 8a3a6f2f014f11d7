"""Tol-FL on the TPU mesh: the production train step.

Two interchangeable gradient-sync schedules (DESIGN.md section 2):

* ``tolfl_ring`` (paper-faithful, Algorithm 1): per-shard gradients inside
  a partial-manual ``shard_map`` (manual over the federated axes
  ("pod", "data"), auto over "model" so tensor-parallel sharding flows
  through GSPMD untouched).  Intra-cluster FedAvg = ``psum`` with
  ``axis_index_groups``; the inter-cluster SBT chain = k-1 sequential
  single-pair ``ppermute`` hops carrying the running (n, g); the final
  broadcast = one masked ``psum``.  The pod axis forms an outer SBT ring.
* ``tolfl_psum`` (beyond-paper optimisation): the algebraically-identical
  failure-weighted mean expressed as a weighted loss under plain GSPMD —
  one reduce-scatter/all-gather pair, compatible with FSDP parameter
  sharding for the 100B+ architectures.

Failure tolerance is in-graph for both: ``alive: (G,)`` enters the jitted
step; weights follow the paper's head-failure semantics
(:func:`repro.core.failure.effective_weights`).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.configs.base import ModelConfig, OptimizerConfig, TolFLConfig
from repro.core import aggregation as agg
from repro.core.failure import effective_weights
from repro.core.topology import Topology
from repro.models import params as P
from repro.models import transformer as T
from repro.optim.optimizers import apply_updates, make_optimizer
from repro.sharding import logical as L


def data_axis_size(mesh: Mesh) -> int:
    sizes = L.mesh_axis_sizes(mesh)
    return sizes.get("data", 1)


def num_groups(mesh: Mesh) -> int:
    """Total federated groups = pod x data axis sizes."""
    sizes = L.mesh_axis_sizes(mesh)
    return sizes.get("pod", 1) * sizes.get("data", 1)


def global_topology(mesh: Mesh, tolfl: TolFLConfig) -> Topology:
    return Topology(num_groups(mesh), tolfl.num_clusters)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------
def init_state(key, mcfg: ModelConfig, ocfg: OptimizerConfig,
               state_dtype: Optional[str] = None) -> Dict[str, Any]:
    params, axes = T.init_params(key, mcfg)
    opt = make_optimizer(ocfg, state_dtype=state_dtype)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def params_logical_axes(mcfg: ModelConfig):
    """Axes tree without materialising params (uses eval_shape on values;
    axes come from running init under eval_shape — init is cheap to trace)."""
    out = {}

    def capture(key):
        p, a = T.init_params(key, mcfg)
        out["axes"] = a
        return p

    jax.eval_shape(capture, jax.random.PRNGKey(0))
    return out["axes"]


def state_logical_axes(mcfg: ModelConfig, ocfg: OptimizerConfig):
    a = params_logical_axes(mcfg)
    if ocfg.name in ("adam", "adamw"):
        from repro.optim.optimizers import AdamState
        opt = AdamState(step=(), mu=a, nu=a)
    else:
        from repro.optim.optimizers import SGDState
        opt = SGDState(step=(), momentum=None)
    return {"params": a, "opt": opt, "step": ()}


def state_shardings(mesh: Mesh, mcfg: ModelConfig, ocfg: OptimizerConfig,
                    rules: dict):
    """NamedSharding tree for the train state."""
    shapes = jax.eval_shape(
        lambda k: init_state(k, mcfg, ocfg), jax.random.PRNGKey(0))
    axes = state_logical_axes(mcfg, ocfg)

    def mk(ax, shp):
        if P.is_axes_leaf(ax) and len(ax) == len(shp.shape):
            return NamedSharding(mesh, L.spec_for(ax, shp.shape, rules, mesh))
        return NamedSharding(mesh, PS())

    return jax.tree.map(mk, axes, shapes, is_leaf=P.is_axes_leaf)


# ---------------------------------------------------------------------------
# GSPMD weighted-psum schedule (optimised; FSDP-compatible)
# ---------------------------------------------------------------------------
def make_psum_train_step(mcfg: ModelConfig, tolfl: TolFLConfig,
                         ocfg: OptimizerConfig, mesh: Mesh,
                         use_pallas: bool = False,
                         state_dtype: Optional[str] = None) -> Callable:
    topo = global_topology(mesh, tolfl)
    G = topo.num_devices
    opt = make_optimizer(ocfg, state_dtype=state_dtype)

    def train_step(state, batch, alive):
        w = effective_weights(alive, topo)              # (G,)
        B = batch["tokens"].shape[0]
        rpg = B // G
        row_w = jnp.repeat(w, rpg)                      # (B,)
        S = batch["labels"].shape[1]
        mask = jnp.broadcast_to(row_w[:, None], (B, S))

        def grad_of(params, b, m):
            def f(p):
                if tolfl.param_cast_dtype:
                    # one explicit cast of the (sharded) master copy: FSDP
                    # all-gathers then move this dtype, not f32 (sec. Perf)
                    cd = jnp.dtype(tolfl.param_cast_dtype)
                    p = jax.tree.map(
                        lambda q: q.astype(cd)
                        if q.dtype == jnp.float32 else q, p)
                return T.loss_fn(p, mcfg, dict(b, mask=m), use_pallas)
            return jax.value_and_grad(f, has_aux=True)(params)

        mb = tolfl.microbatches
        if mb > 1:
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
            bs = jax.tree.map(split, batch)
            ms = split(mask)

            def acc_step(carry, xs):
                g_acc, l_acc, w_acc = carry
                b_i, m_i = xs
                (lv, metrics), g = grad_of(state["params"], b_i, m_i)
                # weight each microbatch by its mask mass so the
                # accumulated gradient equals the single-batch weighted
                # mean EXACTLY, even when failures skew the masks
                wi = jnp.sum(m_i)
                g_acc = jax.tree.map(
                    lambda a, gi: a + gi * wi.astype(gi.dtype), g_acc, g)
                return (g_acc, l_acc + lv * wi, w_acc + wi), metrics

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (grads, lv, w_tot), ms_all = jax.lax.scan(
                acc_step, (g0, jnp.float32(0), jnp.float32(0)), (bs, ms))
            denom = jnp.maximum(w_tot, 1e-30)
            grads = jax.tree.map(lambda g: g / denom.astype(g.dtype), grads)
            lv = lv / denom
            metrics = jax.tree.map(lambda x: x[-1], ms_all)
        else:
            (lv, metrics), grads = grad_of(state["params"], batch, mask)
        updates, new_opt = opt.update(grads, state["opt"], state["params"])
        new_params = apply_updates(state["params"], updates)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": lv, **metrics}

    return train_step


# ---------------------------------------------------------------------------
# Paper-faithful ring schedule (shard_map)
# ---------------------------------------------------------------------------
def make_ring_train_step(mcfg: ModelConfig, tolfl: TolFLConfig,
                         ocfg: OptimizerConfig, mesh: Mesh,
                         use_pallas: bool = False,
                         state_dtype: Optional[str] = None) -> Callable:
    sizes = L.mesh_axis_sizes(mesh)
    d_sz = sizes.get("data", 1)
    p_sz = sizes.get("pod", 1)
    has_pod = "pod" in sizes and p_sz > 1
    topo_data = Topology(d_sz, min(tolfl.num_clusters, d_sz))
    topo_glob = Topology(p_sz * d_sz, min(tolfl.num_clusters * p_sz,
                                          p_sz * d_sz))
    heads = topo_data.heads
    last_head = heads[-1]
    opt = make_optimizer(ocfg, state_dtype=state_dtype)
    manual = tuple(ax for ax in ("pod", "data") if ax in sizes)

    def tree_ppermute(tree, axis, perm):
        return jax.tree.map(lambda t: jax.lax.ppermute(t, axis, perm), tree)

    # bf16 grad sync (beyond-paper, section Perf): the ppermute chain can
    # carry the narrow dtype on every backend; psum (carries an `add`
    # computation) only on TPU — XLA's CPU emitter rejects non-f32
    # all-reduce computations ("Invalid binary instruction opcode copy"),
    # so the CPU dry-run measures the chain saving and the psum saving is
    # realised on hardware.
    sync_dt = (jnp.dtype(tolfl.grad_sync_dtype)
               if tolfl.grad_sync_dtype else None)
    psum_dt = sync_dt if (sync_dt is not None
                          and jax.default_backend() == "tpu") else None

    def agg_shard(grads, n, loss, di, pi):
        """Runs per shard inside shard_map: hierarchical Tol-FL combine.

        ``di``/``pi`` are the shard's data/pod indices, fed in as a
        sharded iota operand rather than ``jax.lax.axis_index`` — the
        latter lowers to PartitionId, which XLA's SPMD partitioner
        rejects in the partial-manual (auto over "model") region on
        CPU."""
        # ---- intra-cluster FedAvg (parallel psum over member groups) ----
        # normalise BEFORE the reduce: r = n_i / sum n stays in [0, 1], so
        # the psum payload is well-scaled even under bf16 grad sync
        groups = topo_data.psum_index_groups()
        den = jax.lax.psum(n, "data", axis_index_groups=groups)
        r_w = n / jnp.maximum(den, 1e-30)
        g_c = jax.tree.map(
            lambda g_: jax.lax.psum(
                (g_ * r_w.astype(g_.dtype)).astype(psum_dt or g_.dtype),
                "data", axis_index_groups=groups), grads)
        loss_c = jax.lax.psum(loss * r_w, "data", axis_index_groups=groups)
        if sync_dt is not None:
            # the sequential chain payload (k-1 ppermute hops) in bf16
            g_c = jax.tree.map(lambda g_: g_.astype(sync_dt), g_c)
        carry_n, carry_g, carry_l = den, g_c, loss_c
        # ---- sequential SBT chain over cluster heads (Algorithm 1) ----
        for hop, perm in enumerate(topo_data.ring_perms()):
            recv_n = jax.lax.ppermute(carry_n, "data", perm)
            recv_g = tree_ppermute(carry_g, "data", perm)
            recv_l = jax.lax.ppermute(carry_l, "data", perm)
            is_tgt = (di == heads[hop + 1]).astype(jnp.float32)
            n_new, g_new = agg.combine_pair(recv_n, recv_g, carry_n, carry_g)
            _, l_new = agg.combine_pair(recv_n, recv_l, carry_n, carry_l)
            carry_n = is_tgt * n_new + (1 - is_tgt) * carry_n
            carry_g = jax.tree.map(
                lambda new, old: is_tgt.astype(old.dtype) * new
                + (1 - is_tgt).astype(old.dtype) * old, g_new, carry_g)
            carry_l = is_tgt * l_new + (1 - is_tgt) * carry_l
        # ---- outer SBT ring over pods ----
        at_last = (di == last_head).astype(jnp.float32)
        if has_pod and tolfl.pod_ring:
            for hop in range(p_sz - 1):
                perm = [(hop, hop + 1)]
                recv_n = jax.lax.ppermute(carry_n, "pod", perm)
                recv_g = tree_ppermute(carry_g, "pod", perm)
                recv_l = jax.lax.ppermute(carry_l, "pod", perm)
                is_tgt = ((pi == hop + 1).astype(jnp.float32) * at_last)
                n_new, g_new = agg.combine_pair(recv_n, recv_g,
                                                carry_n, carry_g)
                _, l_new = agg.combine_pair(recv_n, recv_l,
                                            carry_n, carry_l)
                carry_n = is_tgt * n_new + (1 - is_tgt) * carry_n
                carry_g = jax.tree.map(
                    lambda new, old: is_tgt.astype(old.dtype) * new
                    + (1 - is_tgt).astype(old.dtype) * old, g_new, carry_g)
                carry_l = is_tgt * l_new + (1 - is_tgt) * carry_l
            is_final = at_last * (pi == p_sz - 1).astype(jnp.float32)
        else:
            is_final = at_last
            if has_pod:
                if sync_dt is not None and psum_dt is None:
                    carry_g = jax.tree.map(
                        lambda g_: g_.astype(jnp.float32), carry_g)
                # no pod ring: weighted psum across pods at the heads
                carry_g = jax.tree.map(
                    lambda g_: jax.lax.psum(
                        g_ * (carry_n * is_final).astype(g_.dtype), "pod"),
                    carry_g)
                nsum = jax.lax.psum(carry_n * is_final, "pod")
                carry_g = jax.tree.map(
                    lambda g_: g_ / jnp.maximum(nsum, 1e-30).astype(g_.dtype),
                    carry_g)
                carry_l = jax.lax.psum(
                    carry_l * carry_n * is_final, "pod") / jnp.maximum(nsum, 1e-30)
                carry_n = nsum
        # ---- broadcast theta_{t+1} (masked all-reduce) ----
        axes = ("data", "pod") if has_pod else ("data",)
        if sync_dt is not None and psum_dt is None:
            # CPU backend: the broadcast psum must be f32 (see note above)
            carry_g = jax.tree.map(lambda g_: g_.astype(jnp.float32),
                                   carry_g)
        g_fin = jax.tree.map(
            lambda g_: jax.lax.psum(g_ * is_final.astype(g_.dtype), axes),
            carry_g)
        l_fin = jax.lax.psum(carry_l * is_final, axes)
        n_fin = jax.lax.psum(carry_n * is_final, axes)
        return g_fin, l_fin, n_fin

    def per_shard(params, batch, alive, gidx):
        with L.manual_axes(manual):
            return _per_shard(params, batch, alive, gidx)

    def _per_shard(params, batch, alive, gidx):
        gi = gidx[0]                  # this shard's global group index
        di = gi % d_sz
        pi = gi // d_sz

        def local_loss(p):
            total, metrics = T.loss_fn(p, mcfg, batch, use_pallas)
            return total, metrics

        if tolfl.local_epochs > 1:
            def sgd_step(p, _):
                (lv, m), g = jax.value_and_grad(local_loss, has_aux=True)(p)
                return jax.tree.map(
                    lambda a, b: a - ocfg.lr * b.astype(a.dtype), p, g), lv

            p_end, lvs = jax.lax.scan(sgd_step, params,
                                      jnp.arange(tolfl.local_epochs))
            grads = jax.tree.map(
                lambda a, b: (a - b).astype(jnp.float32) / ocfg.lr,
                params, p_end)
            lv = lvs[-1]
        elif tolfl.microbatches > 1:
            mb = tolfl.microbatches

            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

            def acc_step(carry, b_i):
                g_acc, l_acc = carry

                def f(p):
                    return T.loss_fn(p, mcfg, b_i, use_pallas)

                (lv_i, _), g_i = jax.value_and_grad(f, has_aux=True)(params)
                return (jax.tree.map(lambda a, gi: a + gi / mb, g_acc, g_i),
                        l_acc + lv_i / mb), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, lv), _ = jax.lax.scan(
                acc_step, (g0, jnp.float32(0)), jax.tree.map(split, batch))
        else:
            (lv, m), grads = jax.value_and_grad(local_loss, has_aux=True)(
                params)
        w = effective_weights(alive, topo_glob)[gi]
        n = w * batch["tokens"].size
        g_fin, l_fin, n_fin = agg_shard(grads, n, lv, di, pi)
        if tolfl.grad_sync_dtype:
            # restore f32 master grads for the optimizer
            g_fin = jax.tree.map(lambda g_: g_.astype(jnp.float32), g_fin)
        return g_fin, l_fin, n_fin

    out_specs = (PS(), PS(), PS())

    def train_step(state, batch, alive):
        # batch fields (tokens/labels/frames/prefix/...) all shard their
        # leading batch dim over the federated axes
        batch_specs = jax.tree.map(
            lambda v: PS(manual, *([None] * (v.ndim - 1))), batch)
        sm = L.manual_shard_map(per_shard, mesh,
                                (PS(), batch_specs, PS(), PS(manual)),
                                out_specs, manual)
        gidx = jnp.arange(p_sz * d_sz, dtype=jnp.int32)
        g, loss, n_tot = sm(state["params"], batch, alive, gidx)
        has_update = (n_tot > 0).astype(jnp.float32)
        g = jax.tree.map(lambda x: x * has_update.astype(x.dtype), g)
        updates, new_opt = opt.update(g, state["opt"], state["params"])
        new_params = apply_updates(state["params"], updates)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1},
                {"loss": loss, "n_effective": n_tot})

    return train_step


def make_train_step(mcfg: ModelConfig, tolfl: TolFLConfig,
                    ocfg: OptimizerConfig, mesh: Mesh,
                    use_pallas: bool = False,
                    state_dtype: Optional[str] = None) -> Callable:
    if tolfl.schedule in ("tolfl_psum", "fedavg"):
        return make_psum_train_step(mcfg, tolfl, ocfg, mesh, use_pallas,
                                    state_dtype)
    if tolfl.schedule in ("tolfl_ring", "sbt_ring"):
        return make_ring_train_step(mcfg, tolfl, ocfg, mesh, use_pallas,
                                    state_dtype)
    raise ValueError(tolfl.schedule)
