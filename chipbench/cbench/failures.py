"""Failure-event draws, copied from the program (``repro.core.failure``
and ``repro.core.processes``), and the plain liveness rule.

A trace here is a dict of four numpy arrays of one fixed slot count,
the layout of the program's ``FailureTrace``: ``epochs`` (int32, unused
slots ``PAD_EPOCH``), ``devices`` (int32, -1 unused), ``alive_after``
(float32, the device's state once the event fires) and ``kinds``
(int32: 1 client, 2 server).  The harness hands the program explicit
traces built from these arrays; :func:`alive_rounds` is the reference's
own reading of them.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

PAD_EPOCH = 1 << 30
KIND_CODES = {"none": 0, "client": 1, "server": 2}

Trace = Dict[str, np.ndarray]
Row = Tuple[int, int, float, int]      # (epoch, device, alive_after, kind)


def heads(num_devices: int, num_clusters: int) -> List[int]:
    """Cluster heads: member 0 of each contiguous block."""
    m = num_devices // num_clusters
    return [c * m for c in range(num_clusters)]


def from_rows(rows: Sequence[Row], max_events: int) -> Trace:
    """Stable epoch sort into fixed slots (same-epoch rows keep list
    order, so the last-listed one wins)."""
    if len(rows) > max_events:
        raise ValueError(f"{len(rows)} events over {max_events} slots")
    rows = sorted(rows, key=lambda r: r[0])
    t = {"epochs": np.full((max_events,), PAD_EPOCH, np.int32),
         "devices": np.full((max_events,), -1, np.int32),
         "alive_after": np.ones((max_events,), np.float32),
         "kinds": np.zeros((max_events,), np.int32)}
    for j, (e, d, a, k) in enumerate(rows):
        t["epochs"][j], t["devices"][j] = e, d
        t["alive_after"][j], t["kinds"][j] = a, k
    return t


def single_event(kind: str, epoch: int, num_devices: int,
                 num_clusters: int, max_events: int) -> Trace:
    """One failure at ``epoch`` (``FailureSpec.target``): "server" kills
    the head of cluster 0; "client" the last member of cluster 0, or
    device 0 where every device is a head; "none" is the empty trace."""
    if kind == "none":
        return from_rows([], max_events)
    m = num_devices // num_clusters
    dev = 0 if kind == "server" else (m - 1 if m > 1 else 0)
    return from_rows([(epoch, dev, 0.0, KIND_CODES[kind])], max_events)


def iid_trace(rng: np.random.Generator, num_devices: int,
              num_clusters: int, p: float, rounds: int, max_events: int,
              recover_prob: float = 0.5) -> Trace:
    """``failure.sample_traces`` for one trace: every device fails with
    probability ``p`` at a uniform epoch (a server event if it is a
    head), and with ``recover_prob`` comes back at a later uniform
    epoch; a recovery that no longer fits the slots is dropped."""
    head_set = set(heads(num_devices, num_clusters))
    failed = np.flatnonzero(rng.random(num_devices) < p)
    rng.shuffle(failed)
    rows: List[Row] = []
    for d in failed:
        kind = KIND_CODES["server" if int(d) in head_set else "client"]
        epoch = int(rng.integers(rounds))
        recovers = (rng.random() < recover_prob) and epoch + 1 < rounds
        free = max_events - len(rows)
        if free <= 0:
            continue
        if recovers and free < 2:
            recovers = False
        rows.append((epoch, int(d), 0.0, kind))
        if recovers:
            rows.append((int(rng.integers(epoch + 1, rounds)), int(d), 1.0,
                         kind))
    return from_rows(rows, max_events)


def cascade_trace(rng: np.random.Generator, num_devices: int,
                  num_clusters: int, horizon: int, max_events: int,
                  p_head: float, q: float, recover_prob: float,
                  recovery_lag: int, stagger: int) -> Trace:
    """``processes.ClusterCascadeProcess.sample``, with one change: the
    head's failure epoch is uniform over ``[0, horizon - recovery_lag)``
    instead of ``[0, horizon)``, so that an outage that recovers does so
    inside the horizon.  With ``p_head = recover_prob = 1`` every head
    is then down for exactly ``recovery_lag`` epochs whatever the seed."""
    m = num_devices // num_clusters
    lag, stag = max(1, int(recovery_lag)), max(1, int(stagger))
    if horizon <= lag:
        raise ValueError(f"horizon {horizon} <= recovery lag {lag}")
    order = np.arange(num_clusters)
    rng.shuffle(order)
    rows: List[Row] = []
    for c in order:
        head = int(c) * m
        if rng.random() >= p_head:
            continue
        e = int(rng.integers(horizon - lag))
        g = [(e, head, 0.0, KIND_CODES["server"])]
        fell = []
        for d in range(head + 1, head + m):
            if rng.random() < q:
                g.append((min(e + 1, horizon - 1), d, 0.0,
                          KIND_CODES["client"]))
                fell.append(d)
        rec = e + lag
        if rng.random() < recover_prob:
            g.append((rec, head, 1.0, KIND_CODES["server"]))
            for i, d in enumerate(fell):
                if rec + stag * (i + 1) < horizon:
                    g.append((rec + stag * (i + 1), d, 1.0,
                              KIND_CODES["client"]))
        rows.extend(g[:max_events - len(rows)])
    return from_rows(rows, max_events)


def shift(trace: Trace, offset: int) -> Trace:
    """The same events ``offset`` epochs later."""
    out = dict(trace)
    ep = trace["epochs"].copy()
    ep[ep < PAD_EPOCH] += offset
    out["epochs"] = ep
    return out


def alive_rounds(trace: Trace, num_devices: int, rounds: int,
                 kinds: Sequence[int] = (1, 2)) -> np.ndarray:
    """(rounds, num_devices) float32 liveness: a device is alive until
    an event targeting it fires (at the start of its epoch); the last
    fired event in slot order decides.  Only events of ``kinds`` count."""
    alive = np.ones((rounds, num_devices), np.float32)
    for e, d, a, k in zip(trace["epochs"], trace["devices"],
                          trace["alive_after"], trace["kinds"]):
        if k in kinds and 0 <= d < num_devices and e < rounds:
            alive[e:, d] = a
    return alive
