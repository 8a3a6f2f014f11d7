"""A detector body enters the benchmark by its file alone.

The body file ``data/seq-rglru.py`` (the program's ``SeqDetector`` with
its own plain reference, the RG-LRU as a loop over time) is copied into
a body directory of the test's own, and the harness's lookup is pointed
there; a tiny run of cell 1's grid with ``model.kind`` naming that file
trains the program's sequence detector and compares it with the
reference.  Nothing else of the harness knows the body."""
import os
import re
import shutil

import pytest

from cbench import harness
from tinyrun import run_tiny, tiny_config

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = {"kind": "seq-rglru", "input_dim": 112, "window": 16, "d_model": 8,
       "conv1d_width": 2, "dropout": 0.2, "act": "gelu"}


@pytest.fixture
def body_dir(tmp_path, monkeypatch):
    shutil.copy(os.path.join(HERE, "data", "seq-rglru.py"), tmp_path)
    monkeypatch.setattr(harness, "BODY_DIR", str(tmp_path))
    return tmp_path


def with_model(model):
    """Cell 1's configuration at the tiny size with ``model`` as its
    body, at lr 1e-5: at the cell's 1e-4 the sequence body diverges on
    Comms-ML's unscaled features after some round-1 events (program and
    reference to the same NaN), and a NaN curve compares as no match."""
    tiny = tiny_config(samples=40, rounds=4)

    def f(config):
        c = tiny(config)
        c["model"] = dict(model)
        c["topology"]["lr"] = 1e-5
        return c
    return f


def test_second_body_by_file(bench, fresh_cache, body_dir):
    """Program and reference agree on every sampled scenario of the
    four schemes: the reported test loss (the mean score of the test
    rows) within 1e-5 relative over every round, the AUROC within 1e-3.
    Both run float32 on the CPU; they differ only in the order of
    float32 sums (the program's associative scan against the loop, its
    conv and bias order), which moves a loss by about 1e-7 relative, and
    an AUROC moves only where that reorders two scores within rounding
    of each other."""
    out = run_tiny(bench, "campaign.commsml.grid", config=with_model(SEQ))
    assert out["attempted"] > 0 and out["failed"] == 0
    gaps = dict(out["info"]["not_compared"],
                **{k: c["value"] for k, c in out["checks"].items()})
    assert gaps["loss_gap_early"] < 1e-5 and gaps["loss_gap"] < 1e-5
    assert gaps["auroc_gap_median"] < 1e-3 and gaps["auroc_gap"] < 1e-3
    assert out["correct"] is True


def test_unknown_body_names_the_missing_file(bench, body_dir):
    missing = str(body_dir / "no-such-body.py")
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        run_tiny(bench, "campaign.commsml.grid",
                 config=with_model(dict(SEQ, kind="no-such-body")))
