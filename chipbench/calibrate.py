#!/usr/bin/env python3
"""Readings that set a cell's limits and rate; not part of a run.

    python3 chipbench/calibrate.py readings --workload <name> \
        --seeds <a,b,...> --control-seeds <n> --seconds <s>
    python3 chipbench/calibrate.py faults --workload <name> \
        --seeds <a,b,...> --seconds <s>
    python3 chipbench/calibrate.py knee --workload <serve cell> \
        --rates <r1,r2,...> --seconds <s> --seed <n>

``readings`` runs the cell's window once per seed in one process and
prints, per seed, the compared numbers of the program against the
reference and, for the first ``--control-seeds`` seeds, those of the
control: the reference at the nearest precision below the
configuration's (``cbench.reference.CONTROL``) in the program's
place.  ``faults`` does the same with each fault of
``cbench.faults`` that the cell can have planted in the program in
turn (readings for the upper limits).  ``knee`` offers
the service each rate in turn and prints its latency and how late the
ticks ran.  One JSON object per line on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("readings", "faults", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        CHECKOUT, ".chipbench-cache")
    sys.path[:0] = [os.path.join(CHECKOUT, "src"), HERE]
    import jax
    from cbench import harness
    from cbench.spans import Spans
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl, config, traffic = harness.cell_files(bench, args.workload, CHECKOUT)
    chips = int(wl["chips"])
    devs = harness.devices_for(chips, True)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    from repro.core import compilecache
    compilecache.enable_persistent_cache()
    mod_name, cls_name = harness.GENERATORS[traffic["kind"]]
    cls = getattr(__import__(mod_name, fromlist=[cls_name]), cls_name)

    def make(seed, tr):
        return cls(config, tr, seed, chips, Spans(), args.seconds)

    if args.what == "knee":
        for rate in [float(r) for r in args.rates.split(",")]:
            tr = dict(traffic, rate_windows_per_s=rate)
            gen = make(args.seed, tr)
            gen.warm_up()
            win = gen.window(args.seconds)
            print(json.dumps({"rate": rate, "p99_ms": win["e2e"][
                "window_p99_ms"], **win["counters"]}), flush=True)
        return 0

    if args.what == "faults":
        from cbench.faults import FAULTS
        for name, plant in FAULTS[traffic["kind"]].items():
            with plant():
                for seed in [int(s) for s in args.seeds.split(",")]:
                    gen = make(seed, traffic)
                    gen.warm_up()
                    win = gen.window(args.seconds)
                    print(json.dumps({"fault": name, "seed": seed,
                                      "scenarios": win["scenarios"],
                                      "program": gen.compare()}),
                          flush=True)
        return 0

    from cbench.reference import CONTROL
    control = CONTROL[config["matmul_precision"]]
    extra = {"per_round": True} if traffic["kind"] == "campaign" else {}
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        gen = make(seed, traffic)
        gen.warm_up()
        t1 = time.perf_counter()
        win = gen.window(args.seconds)
        row = {"seed": seed, "setup_s": t1 - t0, "window": {
            k: v for k, v in win.items() if k != "counters"},
            "program": gen.compare(**extra)}
        if i < args.control_seeds:
            row["control"] = gen.compare(control, against_reference=True,
                                         **extra)
        row["check_s"] = time.perf_counter() - t1 - win["seconds"]
        row["memory_peak_bytes"] = harness.memory_peak(devs)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
