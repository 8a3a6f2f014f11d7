"""The campaign generator: a traffic file's grid, run as whole
``execute(plan(spec))`` calls of the program, and checked against the
plain reference.

Traffic file keys (``kind: "campaign"``):

``cells``
    ``[{"scheme": s, "k": k}, ...]``: k is the cluster count of a
    single-model scheme and the model count of a multi-model one.
``traces``
    ``{"explicit": [{"kind": "none" | "server" | "client", "epoch": e}],
    "iid_p": [p, ...], "per_p": n, "recover_prob": r}``.  Each cell gets
    the explicit traces against its own topology, then ``per_p`` iid
    draws per rate, fresh for every execution.
``seeds_per_execution``
    Fresh training seeds drawn for every execution; with the traces
    this fixes the scenarios per execution, so shapes never change.
``in_flight``
    How many executions the window keeps running at once, each on a
    thread of its own, so that while one thread's host work (planning,
    array builds, AUROC) stands still the others' buckets keep the chip
    fed.
``check``
    ``{"early_rounds": r}``: how many first entries of each loss curve
    (rounds; local steps for a multi-model scheme) the steady number
    ``loss_gap_early`` covers.  Per cell, scenarios of the window are
    compared with the reference, drawn from the seed: one of all the
    cell's scenarios, and for each kind of event one whose earliest
    event is of that kind and lies inside those first entries (where
    the window has one), so that the masks, the combine under failure
    and FL's isolated fallback reach the steady number.
``limits``
    The comparison's limits (see ``PERF.md``).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from cbench import data, failures, harness, reference

MULTI = ("fedgroup", "ifca", "fesem")
SPAN_NAMES = ("plan", "execute")


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


class Campaign:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 spans, seconds: float):
        from repro.api import DataSpec, ExecPlan, SimConfig
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        m, topo = config["model"], config["topology"]
        self.model = m
        self.body = harness.body(m["kind"])
        self.n_dev = int(topo["num_devices"])
        self.rounds = int(topo["rounds"])
        self.local_steps = int(topo["local_epochs"])
        self.lr = float(topo["lr"])
        self.fed = data.federation(config["data"], topo, seed)
        self.data_spec = DataSpec(
            model=self.body.program_model(m),
            device_x=self.fed.device_x, device_counts=self.fed.counts,
            test_x=self.fed.test_x, test_y=self.fed.test_y)
        self.base = SimConfig(
            scheme="tolfl", num_devices=self.n_dev,
            num_clusters=int(topo["num_clusters"]), rounds=self.rounds,
            lr=self.lr, local_epochs=self.local_steps, dropout=True)
        self.exec_plan = ExecPlan(aot=True, shard=chips > 1)
        self.rng = _rng(seed, 1)
        self.flops_per_scenario = self.body.train_flops_per_scenario(
            m, self.fed.counts, self.local_steps, self.rounds)
        self.records: List[dict] = []
        self.exe_load_s = 0.0
        #: op-name prefixes of the body's kernels, for the trace reduction
        self.kernel_prefixes: tuple = ()

    # ------------------------------------------------------------------
    def _cell_traces(self, scheme: str, k: int) -> List[dict]:
        tr = self.traffic["traces"]
        multi = scheme in MULTI
        rounds = self.rounds * self.local_steps if multi else self.rounds
        clusters = 1 if (multi or scheme == "fl") else (
            self.n_dev if scheme == "sbt" else k)
        slots = 2 * self.n_dev
        out = [failures.single_event(ev["kind"], int(ev.get("epoch", 0)),
                                     self.n_dev, clusters, slots)
               for ev in tr["explicit"]]
        for p in tr["iid_p"]:
            for _ in range(int(tr["per_p"])):
                out.append(failures.iid_trace(
                    self.rng, self.n_dev, clusters, float(p), rounds, slots,
                    float(tr["recover_prob"])))
        return out

    def _spec(self):
        from repro.api import (CellSpec, ExperimentSpec, FailureTrace,
                               SeedSpec)
        cells, traces = [], []
        for c in self.traffic["cells"]:
            ts = self._cell_traces(c["scheme"], int(c["k"]))
            traces.append(ts)
            cells.append(CellSpec(c["scheme"], int(c["k"]), traces=tuple(
                FailureTrace(*(jnp.asarray(t[f]) for f in (
                    "epochs", "devices", "alive_after", "kinds")))
                for t in ts)))
        seeds = tuple(int(s) for s in self.rng.integers(
            0, 2**31 - 1, size=int(self.traffic["seeds_per_execution"])))
        spec = ExperimentSpec(data=self.data_spec, base=self.base,
                              cells=tuple(cells), seeds=SeedSpec(seeds),
                              exec_plan=self.exec_plan)
        return spec, traces

    def execution(self, spec=None, traces=None) -> dict:
        """One whole campaign execution, planned and run by the program
        (of the next spec drawn from the seed where none is given)."""
        from repro.api import execute, plan
        if spec is None:
            spec, traces = self._spec()
        t0 = time.perf_counter()
        with self.spans("plan"):
            p = plan(spec)
        with self.spans("execute"):
            res = execute(p)
        t = time.perf_counter() - t0
        return {"results": res.results, "traces": traces,
                "report": res.compile_report,
                "scenarios": res.num_scenarios, "seconds": t,
                "kernels": self._kernel_work(p)}

    def _kernel_work(self, p) -> Dict[str, dict]:
        """Calls, FLOPs and HBM bytes of each of the body's kernels over
        one execution, from its plan's buckets: a bucket makes one call
        per local step (rounds x E) per chunk of scenarios and per chip
        it is sharded over, each call batching that chip's scenarios of
        the chunk."""
        counts = getattr(self.body, "kernel_counts", None)
        out: Dict[str, dict] = {}
        if counts is None:
            return out
        rows = int(self.fed.device_x.shape[1])
        for b in p.buckets:
            chips = b.devices or 1
            geometry = {"kind": b.kind, "scenarios": b.chunk // chips,
                        "devices": self.n_dev, "rows": rows}
            calls = b.num_chunks * chips * self.rounds * self.local_steps
            for prefix, c in counts(self.model, geometry).items():
                w = out.setdefault(prefix,
                                   {"calls": 0, "flops": 0, "bytes": 0})
                w["calls"] += calls
                w["flops"] += calls * c["flops"]
                w["bytes"] += calls * c["bytes"]
        return out

    def warm_up(self) -> None:
        """One execution: compiles or loads every bucket and warms every
        shape the window uses (each execution has the same shapes)."""
        rec = self.execution()
        self.exe_load_s = sum(b.compile_s for b in rec["report"].buckets
                              if b.cache in ("disk", "compiled"))
        self.warm_report = rec["report"]
        self.kernel_prefixes = tuple(sorted(rec["kernels"]))

    def window(self, seconds: float) -> dict:
        """Whole executions until ``seconds`` have passed, ``in_flight``
        of them at once: once the time is up no execution starts, the
        window waits for every one that did, and only then reads the
        clock.  The rate is over all of them and all that time."""
        lock = threading.Lock()
        records: List[dict] = []
        errors: List[BaseException] = []
        t0 = time.perf_counter()

        def worker():
            while True:
                with lock:
                    if errors or time.perf_counter() - t0 >= seconds:
                        return
                    i = len(records)
                    records.append({})
                    spec, traces = self._spec()
                try:
                    records[i] = self.execution(spec, traces)
                except BaseException as e:   # re-raised after the join
                    with lock:
                        errors.append(e)
                    return

        threads = [threading.Thread(target=worker, name=f"execution-{i}")
                   for i in range(int(self.traffic["in_flight"]))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        t = time.perf_counter() - t0
        if errors:
            raise errors[0]
        self.records.extend(records)
        scen = sum(r["scenarios"] for r in self.records)
        kernels: Dict[str, dict] = {}
        for r in self.records:
            for prefix, w in r["kernels"].items():
                k = kernels.setdefault(prefix, dict.fromkeys(w, 0))
                for f, v in w.items():
                    k[f] += v
        failed = 0
        for r in self.records:
            for res in r["results"]:
                v = getattr(res, "auroc_used", None)
                v = res.best_auroc if v is None else v
                failed += int(np.sum(~np.isfinite(v)))
        return {"seconds": t, "scenarios": scen, "failed": failed,
                "executions": len(self.records),
                "e2e": {"scenarios_per_s": scen / t},
                "counters": {"scenarios": scen,
                             "executions": len(self.records),
                             "plan_s": self.spans.seconds["plan"],
                             "flops": self.flops_per_scenario * scen,
                             "exe_load_s": self.exe_load_s,
                             "kernels": kernels,
                             "execution_s": [r["seconds"]
                                             for r in self.records]}}

    # ------------------------------------------------------------------
    def _first_event(self, ri: int, ci: int, b: int) -> tuple:
        """(epoch, kind) of the scenario's earliest event."""
        rec = self.records[ri]
        trace = rec["traces"][ci][int(rec["results"][ci].trace_index[b])]
        j = int(np.argmin(trace["epochs"]))
        return int(trace["epochs"][j]), int(trace["kinds"][j])

    def sample(self) -> List[tuple]:
        """(record, cell, scenario) triples to compare, drawn from the
        seed: per cell one of all its scenarios of the window, and for
        each kind of event (client, server) one scenario whose earliest
        event is of that kind and falls in the first
        ``check.early_rounds`` entries of the loss curve."""
        rng = _rng(self.seed, 2)
        early = int(self.traffic["check"]["early_rounds"])
        picks = []
        for ci in range(len(self.traffic["cells"])):
            pool = [(ri, b) for ri, r in enumerate(self.records)
                    for b in range(r["results"][ci].num_scenarios)]
            first = {s: self._first_event(s[0], ci, s[1]) for s in pool}
            kinds = [failures.KIND_CODES[k] for k in ("client", "server")]
            for cand in [pool] + [[s for s in pool if first[s][0] < early
                                   and first[s][1] == k] for k in kinds]:
                if cand:
                    ri, b = cand[rng.integers(len(cand))]
                    picks.append((ri, ci, b))
        return picks

    def reference_one(self, ri: int, ci: int, b: int, precision: str
                      ) -> Dict[str, np.ndarray]:
        rec, c = self.records[ri], self.traffic["cells"][ci]
        res = rec["results"][ci]
        trace = rec["traces"][ci][int(res.trace_index[b])]
        seed = int(res.seed[b])
        fed, m = self.fed, self.model
        if c["scheme"] in MULTI:
            if c["scheme"] != "ifca":
                raise NotImplementedError(c["scheme"])
            rounds = self.rounds * self.local_steps
            k = int(c["k"])
            a_dev = failures.alive_rounds(trace, self.n_dev, rounds, (1,))
            grp = dict(trace, devices=np.where(trace["devices"] >= 0, 0,
                                               -1).astype(np.int32))
            a_mod = failures.alive_rounds(grp, k, rounds, (2,))
            return reference.ifca_scenario(
                m, fed.device_x, fed.counts, fed.test_x, fed.test_y, a_dev,
                a_mod, seed, num_models=k, lr=self.lr, precision=precision)
        k = {"fl": 1, "sbt": self.n_dev}.get(c["scheme"], int(c["k"]))
        alive = failures.alive_rounds(trace, self.n_dev, self.rounds)
        return reference.single_scenario(
            m, fed.device_x, fed.counts, fed.test_x, fed.test_y, alive, k,
            seed, rounds=self.rounds, local_steps=self.local_steps,
            lr=self.lr, track_iso=c["scheme"] == "fl", precision=precision)

    @staticmethod
    def program_one(res, b: int) -> Dict[str, np.ndarray]:
        if hasattr(res, "auroc_used"):
            auroc = np.array([res.auroc_used[b]])
        else:
            auroc = np.array([res.best_auroc[b], res.multi_auroc[b]])
        return {"loss": np.asarray(res.loss_curves[b]), "auroc": auroc}

    def compare(self, precision: str = "highest",
                against_reference: bool = False,
                per_round: bool = False) -> Dict[str, float]:
        """The compared numbers over the sampled scenarios: the widest
        relative gap of the reported test loss over the first
        ``check.early_rounds`` rounds and over all rounds, and the
        AUROC gap of the median scenario and of the widest.
        ``against_reference`` puts the reference at ``precision`` in
        the program's place (the control); ``per_round`` adds the
        widest gap of each round."""
        early = int(self.traffic["check"]["early_rounds"])
        gaps, auroc_gaps = [], []
        for ri, ci, b in self.sample():
            ref = self.reference_one(ri, ci, b, "highest")
            if against_reference:
                got = self.reference_one(ri, ci, b, precision)
            else:
                got = self.program_one(self.records[ri]["results"][ci], b)
            both_nan = np.isnan(got["auroc"]) & np.isnan(ref["auroc"])
            da = np.where(both_nan, 0.0, np.abs(got["auroc"] - ref["auroc"]))
            auroc_gaps.append(float(np.max(np.nan_to_num(da, nan=np.inf))))
            dl = np.abs(got["loss"] - ref["loss"]) / np.abs(ref["loss"])
            gaps.append(np.nan_to_num(dl, nan=np.inf))
        out = {"loss_gap_early": max(float(np.max(g[:early]))
                                     for g in gaps),
               "loss_gap": max(float(np.max(g)) for g in gaps),
               "auroc_gap_median": float(np.median(auroc_gaps)),
               "auroc_gap": max(auroc_gaps)}
        if per_round:
            out["per_round"] = [[float(v) for v in g] for g in gaps]
        return out

    def free(self) -> None:
        """Drop the program's compiled executables before the reference
        runs (results are host arrays already)."""
        from repro.api import clear_executable_caches
        clear_executable_caches()
