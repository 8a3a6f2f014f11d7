"""A run whose timed path is broken underneath reads ``correct: false``.

Each test drives the rest of a run (``run_cell`` without its look for a
chip) at a tiny size with one fault of ``cbench.faults`` planted in the
program, once for each fault the cell can have: a step that leaves the
state unchanged, half of the batch left out with the mean taken over
the rest, failure masks ignored, FL's isolated fallback not reported;
for the service, a window routed to the wrong model and a score altered
where it is produced.  The cells run on one chip, so there is no
exchange between chips to leave out.  The traffic is the cell's own
(its early events included), cut to two cells and four rounds."""
import pytest

from cbench import faults
from tinyrun import run_tiny, tiny_config, tiny_traffic

CELLS = [{"scheme": "tolfl", "k": 2}, {"scheme": "fl", "k": 1}]


def _campaign(bench):
    return run_tiny(bench, "campaign.commsml.grid",
                    config=tiny_config(samples=40, rounds=4),
                    traffic=tiny_traffic(cells=CELLS))


@pytest.mark.parametrize("name", ["state_unchanged", "half_batch",
                                  "masks_ignored", "fallback_off"])
def test_campaign_fault_is_not_correct(bench, fresh_cache, name):
    with faults.FAULTS["campaign"][name]():
        out = _campaign(bench)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_campaign_sound_run_is_correct(bench, fresh_cache):
    assert _campaign(bench)["correct"] is True


@pytest.mark.parametrize("name", ["misrouted", "score_altered"])
def test_service_fault_is_not_correct(bench, fresh_cache, name):
    with faults.FAULTS["serve"][name]():
        out = run_tiny(bench, "serve.commsml.cascade", seconds=1.0)
    assert out["correct"] is False
