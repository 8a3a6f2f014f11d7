#!/usr/bin/env python3
"""Where a traced benchmark window's time went, by the program's own
names: device busy time by ``jax.named_scope`` stage, and each idle gap
of the device split by the program spans open on every host thread.

    python3 scripts/trace_breakdown.py <trace dir or .xplane.pb> \
        [--hlo-dir DIR] [--longest serve.tick:20]

The trace is one kept profiler trace of a ``chipbench/run.py --trace 1``
window (it holds the harness's ``window`` span).  Prints one JSON
object:

``busy_s``, ``window_s``
    The device busy union and the window, as the benchmark reduces them
    (``cbench.tracereduce``).
``scopes``
    Device seconds of the XLA operations (loops left out, as the
    benchmark's per-op totals do) by stage scope, per chip, and each as
    a share of their sum; ``(none)`` holds the operations under no
    stage.  An operation's scope is read from the ``op_name`` path its
    event (or the event's metadata) carries; where the trace has none,
    from the compiled HLO texts in ``--hlo-dir`` (``compiled.as_text()``
    of each executable, one ``.txt`` file each), matched to a program by
    its op names, since op names repeat across programs.
``unscoped_top``
    The operations under no stage that took longest, by ``op_name``.
``idle``, ``idle_longest``
    Idle seconds of the device by the program spans open at that moment:
    each thread contributes its innermost open span, so ``campaign.fetch
    x3 + campaign.build`` is three threads waiting on results and one
    building arrays; and the longest idle stretches, each with its
    offset into the window.
``longest``
    With ``--longest NAME:N``, the N longest instances of span NAME,
    each with the seconds of every program span nested inside it, and
    the span's duration quantiles.

Runs anywhere JAX and protobuf import; reads no device.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for p in (os.path.join(CHECKOUT, "src"), os.path.join(CHECKOUT, "chipbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from cbench.tracereduce import (CONTAINERS, OPS_LINE, clip,  # noqa: E402
                                gaps, load_xplane, merge, op_name)
from repro import obs  # noqa: E402

#: the stage scopes of every compiled core
SCOPES = frozenset(obs.SINGLE_SCOPES + obs.MULTI_SCOPES + obs.SERVE_SCOPES)
#: host spans followed per thread: the program's, and the benchmark
#: harness's own (cbench.campaign / cbench.serve)
HOST_SPANS = frozenset(obs.CAMPAIGN_STAGES + obs.SERVE_STAGES + (
    "campaign.plan", "gc", "plan", "execute", "sleep", "submit", "tick"))
NONE = "(none)"


# ---------------------------------------------------------------------------
# The xplane, with event metadata (jax.profiler.ProfileData leaves it out)
# ---------------------------------------------------------------------------
def _xspace_class():
    """A message class for ``tsl/profiler/protobuf/xplane.proto``'s
    XSpace, built from its field numbers (maps as repeated entries,
    which is the same wire format)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="xplane_min.proto",
                                             package="xplane_min")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if tname:
                f.type_name = ".xplane_min." + tname

    I64, U64, STR = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    MSG = F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, I64, 0, None),
        ("double_value", 2, F.TYPE_DOUBLE, 0, None),
        ("uint64_value", 3, U64, 0, None), ("int64_value", 4, I64, 0, None),
        ("str_value", 5, STR, 0, None), ("bytes_value", 6, F.TYPE_BYTES, 0,
                                         None),
        ("ref_value", 7, U64, 0, None))
    msg("XEvent", ("metadata_id", 1, I64, 0, None),
        ("offset_ps", 2, I64, 0, None), ("duration_ps", 3, I64, 0, None),
        ("stats", 4, MSG, 1, "XStat"))
    msg("XLine", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
        ("timestamp_ns", 3, I64, 0, None), ("events", 4, MSG, 1, "XEvent"),
        ("display_name", 11, STR, 0, None))
    msg("XEventMetadata", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
        ("display_name", 4, STR, 0, None), ("stats", 5, MSG, 1, "XStat"))
    msg("XStatMetadata", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None))
    msg("EventMetadataEntry", ("key", 1, I64, 0, None),
        ("value", 2, MSG, 0, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, I64, 0, None),
        ("value", 2, MSG, 0, "XStatMetadata"))
    msg("XPlane", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
        ("lines", 3, MSG, 1, "XLine"),
        ("event_metadata", 4, MSG, 1, "EventMetadataEntry"),
        ("stat_metadata", 5, MSG, 1, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, MSG, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xplane_min.XSpace"))


def xplane_path(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise SystemExit(f"expected one .xplane.pb under {path}, "
                         f"found {found}")
    return found[0]


def read_xspace(path: str):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_values(stats, stat_names: Dict[int, str]) -> Dict[str, object]:
    """{stat name: value}; a ``ref_value`` names an interned string (the
    name of the stat metadata it refers to)."""
    out = {}
    for s in stats:
        name = stat_names.get(s.metadata_id, str(s.metadata_id))
        for field in ("ref_value", "str_value", "int64_value",
                      "uint64_value", "double_value"):
            if s.HasField(field):
                v = getattr(s, field)
                out[name] = stat_names.get(v, "") if field == "ref_value" \
                    else v
                break
    return out


def scope_of(op_path: str) -> Optional[str]:
    """The last stage scope named in an ``op_name`` path, or None.  A
    transformation wraps the names under it (``vmap(final_scoring)``,
    ``transpose(jvp(local_delta))``), so the path is read by words."""
    hit = None
    for word in re.findall(r"\w+", op_path):
        if word in SCOPES:
            hit = word
    return hit


_OP_NAME_IN_TEXT = re.compile(r'op_name="([^"]*)"')


def _path_scope(vals: Dict[str, object]) -> Tuple[bool, Optional[str]]:
    """(whether the stats carry an ``op_name``, its scope): the TPU's
    ``tf_op`` stat, else an ``op_name="..."`` inside a stat's text."""
    if isinstance(vals.get("tf_op"), str):
        return True, scope_of(vals["tf_op"])
    for v in vals.values():
        if isinstance(v, str):
            m = _OP_NAME_IN_TEXT.search(v)
            if m:
                return True, scope_of(m.group(1))
    return False, None


# ---------------------------------------------------------------------------
# Compiled HLO texts, for traces that carry no op_name
# ---------------------------------------------------------------------------
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*op_name="([^"]*)"')


def read_hlo_dir(hlo_dir: str) -> List[Dict[str, str]]:
    """One {instruction name: op_name} map per ``.txt`` file."""
    texts = []
    for path in sorted(glob.glob(os.path.join(hlo_dir, "*.txt"))):
        ops = {}
        with open(path) as f:
            for line in f:
                m = _INSTR.match(line)
                if m:
                    ops[m.group(1)] = m.group(2)
        texts.append(ops)
    return texts


# ---------------------------------------------------------------------------
# Device time by scope
# ---------------------------------------------------------------------------
def device_scopes(space, lo: float, hi: float,
                  hlo: Optional[List[Dict[str, str]]] = None) -> dict:
    """Device seconds per stage scope inside ``[lo, hi]`` (ns), averaged
    over chips, the longest operations under no scope (by op_name), and
    the stat names the op events carry."""
    per_scope: Dict[str, float] = collections.defaultdict(float)
    unscoped: Dict[str, float] = collections.defaultdict(float)
    stat_seen = set()
    unresolved: Dict[object, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    chips = 0
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        chips += 1
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        # per event metadata: (loop, has an op_name path, scope, program)
        info: Dict[int, tuple] = {}
        op_label: Dict[int, str] = {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            base = line.timestamp_ns
            for ev in line.events:
                key = ev.metadata_id
                if key not in info:
                    md = meta.get(key)
                    vals = {}
                    if md is not None:
                        vals = _stat_values(md.stats, stat_names)
                        vals["name"] = md.name
                        vals["display_name"] = md.display_name
                    stat_seen.update(vals)
                    name = op_name(vals.get("name", ""))
                    info[key] = (name.startswith(CONTAINERS), name,
                                 *_path_scope(vals), vals.get("program_id"))
                    op_label[key] = str(vals.get("tf_op") or name)
                loop, name, seen, sc, prog = info[key]
                if loop:
                    continue
                s = base + ev.offset_ps / 1e3
                e = s + ev.duration_ps / 1e3
                if e <= lo or s >= hi:
                    continue
                dur = min(e, hi) - max(s, lo)
                if not seen and ev.stats:
                    evals = _stat_values(ev.stats, stat_names)
                    stat_seen.update("event:" + k for k in evals)
                    seen, sc = _path_scope(evals)
                    prog = evals.get("program_id", prog)
                if seen:
                    per_scope[sc or NONE] += dur
                    if sc is None:
                        unscoped[op_label.get(key, name)] += dur
                else:
                    unresolved[prog][name] += dur
    if hlo:
        for prog, ops in unresolved.items():
            best = max(hlo, key=lambda t: len(set(ops) & set(t)))
            for name, dur in ops.items():
                per_scope[scope_of(best.get(name, "")) or NONE] += dur
    else:
        for ops in unresolved.values():
            per_scope["(no op_name in trace)"] += sum(ops.values())
    n = max(chips, 1)
    total = sum(per_scope.values())
    return {"chips": chips,
            "op_s": total / n * 1e-9,
            "scopes": {k: {"s": v / n * 1e-9,
                           "share": v / total if total else float("nan")}
                       for k, v in sorted(per_scope.items(),
                                          key=lambda kv: -kv[1])},
            "unscoped_top": [[k, v / n * 1e-9] for k, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:10]],
            "op_stats_seen": sorted(stat_seen)}


# ---------------------------------------------------------------------------
# Host spans per thread
# ---------------------------------------------------------------------------
def host_threads(space, names=HOST_SPANS) -> Dict[str, List[tuple]]:
    """{thread: [(start_ns, end_ns, name), ...]} of the followed spans
    on the host plane, sorted by start."""
    out: Dict[str, List[tuple]] = collections.defaultdict(list)
    for plane in space.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        meta = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            thread = line.display_name or line.name or str(line.id)
            for ev in line.events:
                name = meta.get(ev.metadata_id, "")
                if name in names:
                    s = line.timestamp_ns + ev.offset_ps / 1e3
                    out[f"{thread}#{line.id}"].append(
                        (s, s + ev.duration_ps / 1e3, name))
    for spans in out.values():
        spans.sort(key=lambda x: (x[0], -x[1]))
    return dict(out)


def innermost_points(spans: List[tuple]) -> Tuple[List[float], List[str]]:
    """Change points of the innermost open span on one thread (spans on
    one thread nest): times, and the span open from each (or None)."""
    times, states, stack = [], [], []
    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            times.append(end)
            states.append(stack[-1][1] if stack else None)
        stack.append((e, name))
        times.append(s)
        states.append(name)
    while stack:
        end, _ = stack.pop()
        times.append(end)
        states.append(stack[-1][1] if stack else None)
    return times, states


def _state_at(points, t: float) -> Optional[str]:
    times, states = points
    i = bisect.bisect_right(times, t) - 1
    return states[i] if i >= 0 else None


def state_key(names: Iterable[Optional[str]]) -> str:
    c = collections.Counter(n for n in names if n)
    if not c:
        return NONE
    return " + ".join(f"{n} x{k}" if k > 1 else n
                      for n, k in sorted(c.items(), key=lambda kv: (-kv[1],
                                                                    kv[0])))


def idle_by_state(busy: List[tuple], threads: Dict[str, List[tuple]],
                  lo: float, hi: float, top: int = 10) -> Tuple[dict, list]:
    """Idle seconds of one chip's busy union, split at every change of
    any thread's innermost span, by the set of spans open then; and the
    ``top`` longest idle stretches of one state, each as [seconds from
    the window's start, seconds, state]."""
    points = [innermost_points(sp) for sp in threads.values()]
    cuts = sorted({t for tm, _ in points for t in tm})
    out: Dict[str, float] = collections.defaultdict(float)
    stretches = []
    for a, b in gaps(busy, lo, hi):
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        edges = [a] + inner + [b]
        for x, y in zip(edges, edges[1:]):
            key = state_key(_state_at(p, 0.5 * (x + y)) for p in points)
            out[key] += y - x
            stretches.append([(x - lo) * 1e-9, (y - x) * 1e-9, key])
    stretches.sort(key=lambda st: -st[1])
    return ({k: v * 1e-9 for k, v in sorted(out.items(),
                                             key=lambda kv: -kv[1])},
            stretches[:top])


def longest(threads: Dict[str, List[tuple]], name: str, n: int) -> dict:
    """The ``n`` longest instances of span ``name`` with the seconds of
    the followed spans nested inside each, and duration quantiles."""
    found = []
    for spans in threads.values():
        # sorted by (start, -end): what a span holds comes after it
        for i, (s, e, nm) in enumerate(spans):
            if nm != name:
                continue
            inner: Dict[str, float] = collections.defaultdict(float)
            j = i + 1
            while j < len(spans) and spans[j][0] < e:
                s2, e2, nm2 = spans[j]
                if e2 <= e:
                    inner[nm2] += (e2 - s2) * 1e-9
                j += 1
            found.append(((e - s) * 1e-9, s, dict(inner)))
    found.sort(key=lambda x: -x[0])
    durs = sorted(d for d, _, _ in found)

    def q(p):
        return durs[min(len(durs) - 1, int(p * len(durs)))] if durs else None
    return {"count": len(durs), "p50_s": q(0.5), "p99_s": q(0.99),
            "max_s": durs[-1] if durs else None,
            "top": [{"s": d, "start_ns": s, "inside": inner}
                    for d, s, inner in found[:n]]}


def breakdown(path: str, hlo_dir: Optional[str] = None,
              longest_spec: Optional[str] = None, rec: Optional[dict] = None
              ) -> dict:
    """The whole breakdown of one kept trace.  ``rec`` may pass the
    plain record ``cbench.tracereduce.load_xplane`` already read."""
    xp = xplane_path(path)
    if rec is None:
        rec = load_xplane(os.path.dirname(xp), ())
    lo, hi = rec["window"]
    space = read_xspace(xp)
    threads = host_threads(space)
    out = {"window_s": (hi - lo) * 1e-9}
    planes = sorted(rec["devices"])
    busy_by_plane = {p: merge(clip(((s, s + d) for _, s, d in
                                    rec["devices"][p]), lo, hi))
                     for p in planes}
    out["busy_s"] = (sum(e - s for b in busy_by_plane.values() for s, e in b)
                     / max(len(planes), 1) * 1e-9)
    out.update(device_scopes(space, lo, hi,
                             read_hlo_dir(hlo_dir) if hlo_dir else None))
    if planes:
        out["idle"], out["idle_longest"] = idle_by_state(
            busy_by_plane[planes[0]], threads, lo, hi)
    if longest_spec:
        name, _, n = longest_spec.partition(":")
        out["longest"] = {name: longest(threads, name, int(n or 10))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--hlo-dir")
    ap.add_argument("--longest", help="NAME:N, e.g. serve.tick:20")
    args = ap.parse_args(argv)
    print(json.dumps(breakdown(args.trace, args.hlo_dir, args.longest),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
