#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and
a traffic mix; see ``chipbench/cbench/harness.py``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; ``checks`` comes last and holds every compared number
beside its limit, which are also the last lines on standard error.

Exits 2 without a result where JAX finds no TPU or fewer chips than the
cell asks for, and 3 where the checkout lacks the program or the cell.
JAX's persistent compilation cache is kept at ``<checkout>/.chipbench-
cache``, so only the first run of a cell in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(CHECKOUT, ".chipbench-cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = os.path.join(CHECKOUT, "BENCHMARK.json")
    if not (os.path.isfile(bench_path)
            and os.path.isdir(os.path.join(CHECKOUT, "src", "repro"))):
        print("chipbench: the checkout lacks BENCHMARK.json or src/repro",
              file=sys.stderr)
        return 3
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [os.path.join(CHECKOUT, "src"), HERE]
    from cbench import harness

    with open(bench_path) as f:
        bench = json.load(f)
    try:
        harness.cell_files(bench, args.workload, CHECKOUT)
    except (KeyError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    try:
        out = harness.run_cell(bench, args.workload, args.seed,
                               args.seconds, bool(args.trace), T_START,
                               CHECKOUT)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
