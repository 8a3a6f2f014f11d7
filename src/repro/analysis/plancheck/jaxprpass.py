"""Pass 1: plan-time jaxpr analysis of experiment dispatch buckets.

For each :class:`repro.core.experiment.BucketPlan` of a lowered
:class:`ExecutionPlan`, the pass traces the bucket's batched executable
at the plan-predicted abstract shapes (reusing the AOT machinery:
``experiment._bucket_avals`` + the lru-cached ``campaign._executable``
— ZERO execution, and the trace it pays is shared with any later
compile of the same bucket) and walks the jaxpr for the invariants the
engine otherwise enforces only by convention:

``PC-JAX-RETRACE``
    A weak-typed abstract input.  Weak types mean a Python scalar
    reached the traced call as an operand; jit keys on weak-typedness,
    so two call sites spelling the same value differently fork the
    cache ("data as arguments" only amortises when the argument avals
    are canonical).
``PC-JAX-CONST``
    A large array constant captured by value.  The PR-3 contract is
    that data/topology arrays are ARGUMENTS of the cached executable;
    a captured array means the executable is pinned to one dataset and
    retraces per dataset.
``PC-JAX-SYNC``
    A host-callback / infeed-outfeed primitive anywhere in the bucket's
    program.  Inside a vmapped scenario core such a primitive
    serialises the whole batch on host round-trips.
``PC-JAX-BUDGET``
    Recursive equation count vs the bucket kind's named budget
    (:mod:`repro.analysis.plancheck.budgets`) — the formalised
    ``trace_alive_mask`` O(1)-in-max_events guard, applied to whole
    cores.

:func:`check_jaxpr` is the reusable single-program version the fixture
battery (and any future pass) drives directly.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.plancheck import budgets as _budgets
from repro.analysis.plancheck.findings import Finding, finding

#: primitive names that imply a host round-trip inside the program
HOST_SYNC_PRIMS = {"infeed", "outfeed", "host_callback",
                   "debug_print"}     # jax.debug.print on jax >= 0.9
_SYNC_SUBSTRING = "callback"          # pure_callback / io_callback /
#                                       debug_callback / custom variants

#: array constants at or above this element count are "data captured by
#: value" (topology-sized captures are fine; dataset-sized are not)
CONST_ELEMENT_THRESHOLD = 64


def _as_closed(jaxpr):
    """Duck-typed (jaxpr, consts) of a Jaxpr or ClosedJaxpr."""
    if hasattr(jaxpr, "jaxpr"):
        return jaxpr.jaxpr, list(getattr(jaxpr, "consts", ()))
    return jaxpr, []


def iter_jaxprs(closed) -> Iterable[Tuple[object, list]]:
    """Every (jaxpr, consts) pair reachable from ``closed`` — the top
    level plus scan/cond/pjit bodies, recursively."""
    jaxpr, consts = _as_closed(closed)
    yield jaxpr, consts
    for eqn in jaxpr.eqns:
        for sub in _budgets.subjaxprs(eqn):
            yield from iter_jaxprs(sub)


def _aval_weak(aval) -> bool:
    return bool(getattr(aval, "weak_type", False))


def trace_closed_jaxpr(fn, abstract_args):
    """ClosedJaxpr of ``fn`` at ``abstract_args`` without executing or
    compiling anything (works for plain callables and jit wrappers
    alike)."""
    return jax.make_jaxpr(fn)(*abstract_args)


def check_jaxpr(closed, where: str, file: str = "",
                budget: Optional[str] = None,
                const_threshold: int = CONST_ELEMENT_THRESHOLD
                ) -> List[Finding]:
    """All pass-1 findings of one traced program (see module
    docstring).  ``where`` tags the findings (bucket name / fixture
    name); ``budget`` optionally names a :data:`budgets.BUDGETS`
    entry."""
    out: List[Finding] = []
    file = file or where
    top_jaxpr, _ = _as_closed(closed)

    for var in top_jaxpr.invars:
        if _aval_weak(var.aval):
            out.append(finding(
                "PC-JAX-RETRACE", file, 0,
                f"{where}: weak-typed input {var.aval.str_short()} — a "
                f"Python scalar reached the traced call as an operand "
                f"and will fork the jit cache key per spelling",
                hint="wrap the operand in jnp.asarray(..., dtype) "
                     "before the batched call (seeds/epochs must enter "
                     "as canonical int32 arrays)",
                tag=f"{where}:retrace"))
            break                      # one finding per program

    seen_const = seen_sync = False
    total = 0
    for jaxpr, consts in iter_jaxprs(closed):
        total += len(jaxpr.eqns)
        if not seen_const:
            for c in consts:
                size = int(np.size(c)) if hasattr(c, "shape") else 0
                if size >= const_threshold:
                    out.append(finding(
                        "PC-JAX-CONST", file, 0,
                        f"{where}: array of {size} elements captured "
                        f"by value in the jaxpr — the executable is "
                        f"pinned to this data and retraces per "
                        f"dataset",
                        hint="pass data/topology arrays as operands of "
                             "the batched call (the PR-3 'data as "
                             "arguments' contract)",
                        tag=f"{where}:const"))
                    seen_const = True
                    break
        if not seen_sync:
            for eqn in jaxpr.eqns:
                name = eqn.primitive.name
                if name in HOST_SYNC_PRIMS or _SYNC_SUBSTRING in name:
                    out.append(finding(
                        "PC-JAX-SYNC", file, 0,
                        f"{where}: host primitive '{name}' inside the "
                        f"program — an implicit host-device sync "
                        f"serialises the vmapped scenario batch",
                        hint="drop jax.debug.print / callbacks from "
                             "batched cores; surface values through "
                             "the outputs pytree instead",
                        tag=f"{where}:sync"))
                    seen_sync = True
                    break

    if budget is not None:
        b = _budgets.check_budget(budget, total, where=where, file=file)
        if b is not None:
            out.append(b)
    return out


def check_plan(plan, data=None, budgets: bool = True) -> List[Finding]:
    """Pass 1 over every bucket of an ``ExecutionPlan``.

    ``data`` defaults to the plan's own spec data.  Tracing a bucket
    bumps ``campaign.TRACE_COUNT`` exactly like a first compile would
    (the lru trace cache is shared, so a later ``execute()`` of the
    same plan re-traces nothing)."""
    from repro.core import campaign as _c
    from repro.core import experiment as _x

    data = data or plan.spec.data
    # the detector's budget_family picks the named eqn ceilings the
    # buckets are checked against ("ae" = the historical names)
    family = getattr(getattr(data, "model", None), "budget_family", "ae")
    out: List[Finding] = []
    for bucket in plan.buckets:
        cells = [plan.cells[i] for i in bucket.cell_indices]
        avals = _x._bucket_avals(data, bucket, cells)
        jitted = _c._executable(*_x._bucket_exe_args(data, bucket))
        where = (f"bucket {bucket.index} ({bucket.kind}"
                 f"{' fused' if bucket.fused else ''})")
        closed = trace_closed_jaxpr(jitted, avals)
        out.extend(check_jaxpr(
            closed, where, file=f"plan://bucket{bucket.index}",
            budget=(_budgets.bucket_budget_name(bucket.kind,
                                                bucket.fused, family)
                    if budgets else None)))
    return out
