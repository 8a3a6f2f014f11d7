"""Reduction from a profiler trace to the benchmark's device metrics.

The reduction works on a plain record, so that a small committed trace
can test it (``tests/data/small_trace.json``)::

    {"devices": {plane name: [[op name, start_ns, dur_ns], ...]},
     "host":    [[span name, start_ns, dur_ns], ...],
     "window":  [start_ns, end_ns]}

``devices`` holds each chip's XLA operations, ``host`` the harness's own
spans (``jax.profiler.TraceAnnotation``), and ``window`` the measured
window, all on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


class NoDevicePlane(ValueError):
    """The trace holds no TPU plane (a CPU rehearsal)."""

#: the device plane's line that holds one event per XLA operation
OPS_LINE = "XLA Ops"
#: operations that contain others on the same line (a loop and its
#: body): counted in the busy union, left out of the per-op totals
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(trace_dir: str, span_names: Iterable[str],
                window_span: str = "window") -> dict:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``
    into the plain record above.  TPU planes are ``/device:TPU:<i>``;
    their op line is :data:`OPS_LINE`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane under {trace_dir}, "
                           f"found {paths}")
    names = set(span_names) | {window_span}
    pd = ProfileData.from_file(paths[0])
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([e.name, float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns),
                             float(e.duration_ns)]
                            for e in line.events if e.name in names)
    win = [h for h in host if h[0] == window_span]
    if len(win) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(win)}")
    _, w0, wd = win[0]
    return {"devices": devices,
            "host": [h for h in host if h[0] != window_span],
            "window": [w0, w0 + wd]}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between merged busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _HostIndex:
    """Host spans sorted by start, to name the span around an instant.
    The harness's spans do not nest, so the last span that starts at or
    before ``t`` is the one to test; a few earlier ones are tested too,
    and the shortest that covers ``t`` wins."""

    def __init__(self, spans: Sequence[Sequence]):
        self.spans = sorted((float(s), float(s) + float(d), str(n))
                            for n, s, d in spans)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: float, look_back: int = 8) -> str:
        i = bisect.bisect_right(self.starts, t)
        best: Optional[Tuple[float, str]] = None
        for s, e, name in self.spans[max(0, i - look_back):i]:
            if s <= t < e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "other"


def reduce_trace(rec: dict, top: int = 10,
                 kernels: Sequence[str] = ()) -> dict:
    """Busy time (the union of op intervals, per chip, averaged), the
    idle share, per-op device time, idle time by host span and the
    device time of each kernel in ``kernels``.

    Returns ``{"window_s", "busy_s", "idle_share", "device_ops",
    "idle_gaps", "chips", "kernels"}``; ``device_ops`` and
    ``idle_gaps`` are lists of ``[name, seconds]`` (per chip, averaged),
    longest first; ``kernels`` maps each op-name prefix to
    ``{"seconds", "calls"}``, summed over chips, of the ops named
    ``<prefix>`` or ``<prefix>.<n>``."""
    lo, hi = rec["window"]
    planes = sorted(rec["devices"])
    if not planes:
        raise NoDevicePlane("no device plane in the trace")
    busy_tot = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    idle_by: Dict[str, float] = defaultdict(float)
    host = _HostIndex(rec["host"])
    for p in planes:
        evs = rec["devices"][p]
        ivs = clip(((s, s + d) for _, s, d in evs), lo, hi)
        busy = merge(ivs)
        busy_tot += sum(e - s for s, e in busy)
        for name, s, d in evs:
            name = op_name(name)
            c = clip([(s, s + d)], lo, hi)
            if c and not name.startswith(CONTAINERS):
                op_time[name] += c[0][1] - c[0][0]
                op_calls[name] += 1
        for s, e in gaps(busy, lo, hi):
            idle_by[host.at(0.5 * (s + e))] += e - s
    n = len(planes)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_tot / n * 1e-9
    by_kernel = {}
    for prefix in kernels:
        names = [k for k in op_time
                 if k == prefix or k.startswith(prefix + ".")]
        by_kernel[prefix] = {"seconds": sum(op_time[k] for k in names)
                             * 1e-9,
                             "calls": sum(op_calls[k] for k in names)}

    def ranked(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else
            float("nan"),
            "device_ops": ranked(op_time), "idle_gaps": ranked(idle_by),
            "chips": n, "kernels": by_kernel}
