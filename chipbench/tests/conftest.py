"""CPU tests of the chip benchmark at tiny sizes.

The harness runs here without its look for a chip
(``run_cell(require_tpu=False)``); every test that compiles the program
gets a compile cache of its own, so a test that breaks the program
underneath never loads an executable compiled from the sound one."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def with_unlisted_cells(bench: dict) -> dict:
    """``bench`` plus the cells whose traffic files are in
    ``chipbench/traffic/`` but which it does not hold yet (not proved on
    the chip), so that their generators are tested all the same: each
    runs on one chip with the configuration named by the traffic
    name's second part (``campaign.cifar10.tolfl-fl`` -> ``cifar10-ae``)."""
    configs = sorted(os.path.splitext(f)[0]
                     for f in os.listdir(os.path.join(BENCH, "configs")))
    listed = {c["name"] for c in bench["configs"]}
    bench["configs"] += [{"name": c, "file": f"chipbench/configs/{c}.json"}
                         for c in configs if c not in listed]
    listed = {w["traffic"] for w in bench["workloads"]}
    for f in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        t = os.path.splitext(f)[0]
        if t not in listed:
            cfg = next(c for c in configs
                       if c.split("-")[0] == t.split(".")[1])
            bench["workloads"].append(
                {"name": t, "config": cfg, "traffic": t, "chips": 1})
    return bench


@pytest.fixture
def bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return with_unlisted_cells(json.load(f))


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """A compile cache of the test's own, and empty in-process caches."""
    from repro.api import clear_executable_caches
    from repro.core import compilecache
    from repro.serving.anomaly import engine
    before = compilecache.persistent_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    compilecache.enable_persistent_cache(str(tmp_path / "jc"))
    clear_executable_caches()
    engine.clear_score_cache()
    yield
    clear_executable_caches()
    engine.clear_score_cache()
    if before is None:
        compilecache.disable_persistent_cache()
    else:
        compilecache.enable_persistent_cache(before)
