"""The readers of the program's own execution records (``repro.obs``):
``build_ms.campaign``, ``post_ms.campaign``, ``gc_ms.campaign`` and
``h2d_mb.campaign``; ``metrics/<name>.py`` holds each, found by name."""
import math

from tinyrun import run_tiny

PROGRAM_READERS = ("build_ms.campaign", "post_ms.campaign",
                   "gc_ms.campaign", "h2d_mb.campaign")


def test_program_metric_readers(bench, fresh_cache):
    """From a tiny CPU run of cell 1 (four executions in flight): each
    reader gives a finite mean over the window's executions, and nothing
    where the registry holds fewer or the cell is no campaign."""
    from cbench import harness
    out = run_tiny(bench, "campaign.commsml.grid")
    ctx = {"kind": "campaign", "counters": out["info"]}
    assert ctx["counters"]["executions"] >= 1
    got = {name: harness.metric_reader(name)(ctx) for name in PROGRAM_READERS}
    for name, v in got.items():
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
    assert got["build_ms.campaign"] > 0 and got["post_ms.campaign"] > 0
    assert got["h2d_mb.campaign"] > 0
    too_many = {"kind": "campaign", "counters": {"executions": 10**9}}
    serve = {"kind": "serve", "counters": {"windows": 10, "batches": 2}}
    for name in PROGRAM_READERS:
        assert harness.metric_reader(name)(too_many) is None
        assert harness.metric_reader(name)(serve) is None
