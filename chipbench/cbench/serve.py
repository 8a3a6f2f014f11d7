"""The service generator: open-loop arrivals into the program's
``AnomalyService``, ticked on a fixed schedule, checked against the
plain reference.

Traffic file keys (``kind: "serve"``):

``rate_windows_per_s``
    Offered load, fixed in the cell.  Arrivals are a Poisson process
    conditioned on its count: ``rate x schedule`` arrival times uniform
    over the schedule, so every seed offers the same number of windows.
``zipf_s``
    Client skew: client c sends a share proportional to 1/(c+1)^s; the
    per-client counts are the same for every seed, their order is not.
``tick_ms``
    ``tick()`` is called every ``tick_ms`` of schedule, or at once when
    the previous one ran late.  The tick index is the failure epoch, so
    failures land at the same scheduled time whatever the speed.
``warmup_ticks``
    Ticks of the same load before the window (set-up).
``buckets``, ``window``
    ``ServiceConfig`` of the service.
``cascade``
    Parameters of the cluster cascade over the window's ticks
    (``failures.cascade_trace``); each head that fails stays down for
    ``down_share`` of the window's ticks, so with ``p_head`` and
    ``recover_prob`` at 1 that share of the windows is served by
    isolated models whatever the seed.
``limits``
    The comparison's limits (see ``PERF.md``).
``not_measured``
    Where present, which of these values are placeholders that no
    reading on a chip has set yet; not read here.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from cbench import data, failures, harness, reference

SPAN_NAMES = ("sleep", "submit", "tick")


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


@functools.partial(jax.jit, static_argnames=("body", "rows"))
def make_bank(key, *, body, rows):
    """Bank weights for ``rows`` models in the body's reference layout,
    made on the device in one call (the body's ``bank``)."""
    mod, model = reference.body_of(body)
    return mod.bank(key, model, rows)


def zipf_counts(n: int, clients: int, s: float) -> np.ndarray:
    """Per-client arrival counts summing to ``n``, shares 1/(c+1)^s
    (largest remainders get the leftover)."""
    w = 1.0 / np.arange(1, clients + 1) ** s
    exact = n * w / w.sum()
    cnt = np.floor(exact).astype(np.int64)
    cnt[np.argsort(-(exact - cnt))[:n - cnt.sum()]] += 1
    return cnt


class Serve:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 spans, seconds: float):
        from repro.api import (AnomalyService, FailureTrace, ModelBank,
                               ServiceConfig, Topology, as_detector)
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        m, topo = config["model"], config["topology"]
        self.model = m
        n_dev, k = int(topo["num_devices"]), int(topo["num_clusters"])
        self.n_dev, self.members = n_dev, n_dev // k
        self.win = int(traffic["window"])
        self.tick_s = float(traffic["tick_ms"]) / 1e3
        self.ticks = max(2, int(round(seconds / self.tick_s)))
        # epochs before the window: one tick per bucket, then the
        # warm-up ticks of the load
        self.offset = len(traffic["buckets"]) + int(traffic["warmup_ticks"])

        # traffic windows: the configuration's test rows, in windows
        fed = data.federation(config["data"], topo, seed)
        n_pool = len(fed.test_x) // self.win
        self.pool = fed.test_x[:n_pool * self.win].reshape(
            n_pool, self.win, -1)
        rng = _rng(seed, 3)
        rate = float(traffic["rate_windows_per_s"])
        sched = (self.ticks - 1) * self.tick_s
        n = int(round(rate * sched))
        self.clients = np.repeat(np.arange(n_dev), zipf_counts(
            n, n_dev, float(traffic["zipf_s"])))
        rng.shuffle(self.clients)
        self.due = np.sort(rng.random(n)) * sched
        self.win_idx = rng.integers(n_pool, size=n)

        # failures: a cascade over the window's ticks, after the warm-up
        c = traffic["cascade"]
        self.trace = failures.shift(failures.cascade_trace(
            rng, n_dev, k, self.ticks, 2 * n_dev, float(c["p_head"]),
            float(c["q"]), float(c["recover_prob"]),
            max(1, int(round(float(c["down_share"]) * self.ticks))),
            int(c["stagger_ticks"])),
            self.offset)
        horizon = self.offset + self.ticks + 1

        # the bank: N + 1 models made from the seed on the device
        body = harness.body(m["kind"])
        key = jax.random.PRNGKey(int(_rng(seed, 4).integers(2**31 - 1)))
        self.ref_rows = make_bank(key, body=reference.body_key(m),
                                  rows=n_dev + 1)
        rows = body.program_params(self.ref_rows, m)
        bank = ModelBank(
            detector=as_detector(body.program_model(m)),
            topology=Topology(n_dev, k),
            input_dim=int(m["input_dim"]),
            global_params=jax.tree.map(lambda p: p[0], rows),
            iso_params=jax.tree.map(lambda p: p[1:], rows),
            row_params=rows)
        t0 = time.perf_counter()
        self.svc = AnomalyService(
            bank, ServiceConfig(bucket_sizes=tuple(traffic["buckets"]),
                                window=self.win),
            failure=FailureTrace(*(jnp.asarray(self.trace[f]) for f in (
                "epochs", "devices", "alive_after", "kinds"))),
            horizon=horizon)
        self.exe_load_s = time.perf_counter() - t0
        self.n = n

    # ------------------------------------------------------------------
    def warm_up(self) -> None:
        """Every bucket once, then ``warmup_ticks`` ticks of the load."""
        rng = _rng(self.seed, 5)
        for b in self.traffic["buckets"]:
            for _ in range(int(b)):
                self.svc.submit(int(rng.integers(self.n_dev)),
                                self.pool[rng.integers(len(self.pool))])
            self.svc.tick()
        per_tick = max(1, int(round(self.n / self.ticks)))
        for _ in range(int(self.traffic["warmup_ticks"])):
            for _ in range(per_tick):
                self.svc.submit(int(self.clients[rng.integers(self.n)]),
                                self.pool[rng.integers(len(self.pool))])
            self.svc.tick()
        if self.svc.epoch != self.offset:
            raise RuntimeError(f"warm-up ended at epoch {self.svc.epoch}")

    def window(self, seconds: float) -> dict:
        svc, n = self.svc, self.n
        batches0 = svc.report().batches
        slot: Dict[tuple, int] = {}
        self.sub_tick = np.full(n, -1, np.int64)
        self.scores = np.full((n, self.win), np.nan, np.float32)
        self.isolated = np.zeros(n, bool)
        self.epoch = np.full(n, -1, np.int64)
        latency = np.full(n, np.nan)
        late = np.zeros(self.ticks)
        j = 0
        t0 = time.perf_counter()
        for k in range(self.ticks):
            due = t0 + k * self.tick_s
            now = time.perf_counter()
            if now < due:
                with self.spans("sleep"):
                    time.sleep(due - now)
                now = time.perf_counter()
            late[k] = now - due
            with self.spans("submit"):
                while j < n and t0 + self.due[j] <= now:
                    c = int(self.clients[j])
                    slot[(c, svc.submit(c, self.pool[self.win_idx[j]]))] = j
                    self.sub_tick[j] = k
                    j += 1
            with self.spans("tick"):
                out = svc.tick()
            done = time.perf_counter()
            for w in out:
                i = slot.pop((w.client, w.seq))
                latency[i] = done - (t0 + self.due[i])
                self.scores[i] = w.scores
                self.isolated[i] = w.served_by == "isolated"
                self.epoch[i] = w.epoch
        t = time.perf_counter() - t0
        answered = np.isfinite(latency)
        lat_ms = latency[answered] * 1e3
        batches = svc.report().batches - batches0
        self.unanswered = int(n - answered.sum())
        return {"seconds": t, "scenarios": n, "failed": self.unanswered,
                "e2e": {"window_p99_ms": float(np.percentile(lat_ms, 99))},
                "counters": {"windows": n, "batches": batches,
                             "exe_load_s": self.exe_load_s,
                             "p50_ms": float(np.percentile(lat_ms, 50)),
                             "windows_per_s": n / t,
                             "tick_late_ms_p99": float(
                                 np.percentile(late, 99) * 1e3),
                             "isolated_share": float(self.isolated.mean())}}

    # ------------------------------------------------------------------
    def expected_rows(self) -> np.ndarray:
        """The reference's routing: the bank row each window should be
        scored against, from liveness at the tick it was submitted."""
        alive = failures.alive_rounds(self.trace, self.n_dev,
                                      self.offset + self.ticks)
        ep = self.offset + self.sub_tick
        head = (self.clients // self.members) * self.members
        dead = alive[ep, head] <= 0
        return np.where(dead, self.clients + 1, 0)

    def compare(self, precision: str = "highest",
                against_reference: bool = False) -> Dict[str, float]:
        """Every window of the window: those never answered, those
        served by another model or at another tick than the reference
        routes them, and the widest relative gap of a served score."""
        rows = self.expected_rows()
        windows = self.pool[self.win_idx]
        ref = reference.service_scores(self.model, self.ref_rows, rows,
                                       windows, "highest")
        if against_reference:
            got = reference.service_scores(self.model, self.ref_rows, rows,
                                           windows, precision)
            misrouted = 0
        else:
            got = self.scores
            ep = self.offset + self.sub_tick
            misrouted = int(np.sum(((rows > 0) != self.isolated)
                                   | (self.epoch != ep)))
        gap = np.nan_to_num(np.abs(got - ref) / np.abs(ref), nan=np.inf)
        return {"unanswered": float(self.unanswered),
                "misrouted": float(misrouted),
                "score_gap": float(np.max(gap))}

    def free(self) -> None:
        self.svc = None
