"""Tiny sizes of the benchmark's cells, for CPU tests of the harness."""
import copy
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tiny_config(samples=40, rounds=4):
    def f(config):
        c = copy.deepcopy(config)
        c["data"]["samples_per_class"] = samples
        c["topology"]["rounds"] = rounds
        return c
    return f


def tiny_traffic(**kw):
    def f(traffic):
        t = copy.deepcopy(traffic)
        if t["kind"] == "serve":
            t.update(rate_windows_per_s=200, warmup_ticks=3, tick_ms=20)
        t.update(kw)
        return t
    return f


def run_tiny(bench, workload, seed=7, seconds=0.5, config=None,
             traffic=None):
    import time
    from cbench import harness
    return harness.run_cell(
        bench, workload, seed, seconds, False, time.perf_counter(),
        CHECKOUT, require_tpu=False,
        config_override=config or tiny_config(),
        traffic_override=traffic or tiny_traffic(), log=lambda *a: None)
