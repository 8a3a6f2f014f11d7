"""What the program records about itself, for the per-layer readers:
the execution registry of ``repro.obs`` (one record per campaign
``execute()``, in completion order).  A program without it reads as
nothing."""
from __future__ import annotations

from typing import Optional


def window_records(ctx) -> Optional[list]:
    """The window's execution records: the last ``executions`` of the
    registry (the warm-up ran before them, and the check does not call
    the program), or None where the program keeps no registry or the
    cell is not a campaign."""
    n = ctx["counters"].get("executions")
    if ctx["kind"] != "campaign" or not n:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    recs = obs.executions()
    return recs[-n:] if len(recs) >= n else None


def window_mean(ctx, field: str) -> Optional[float]:
    """Mean of one field over the window's execution records."""
    recs = window_records(ctx)
    if recs is None:
        return None
    return sum(r[field] for r in recs) / len(recs)
