"""The plain references against the program at tiny sizes on the CPU,
and the control: the reference at the nearest precision below the one
the configuration states (one bfloat16 pass below ``tensorfloat32``)
in the program's place reads wider gaps than the program."""
import os

import numpy as np

from tinyrun import run_tiny, tiny_config, tiny_traffic

def test_campaign_reference_matches_program(bench, fresh_cache):
    """The cell's own traffic at four rounds: its events at round 1
    (FL's isolated fallback, a dead head, a dead client) and iid churn
    all fire inside the run."""
    out = run_tiny(bench, "campaign.commsml.grid")
    assert out["correct"] is True
    c, rest = out["checks"], out["info"]["not_compared"]
    assert c["loss_gap_early"]["value"] < 1e-6
    assert c["auroc_gap_median"]["value"] < 1e-3
    # widest gaps, which the chip's seeds that amplify rounding keep out
    # of the comparison
    assert rest["loss_gap"] < 1e-5 and rest["auroc_gap"] < 1e-3
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_wide_body_reference_matches_program(bench, fresh_cache):
    out = run_tiny(bench, "campaign.cifar10.tolfl-fl",
                   config=tiny_config(samples=12, rounds=3))
    assert out["correct"] is True
    assert out["checks"]["loss_gap_early"]["value"] < 1e-6
    assert out["checks"]["loss_gap"]["value"] < 1e-5


def test_service_reference_matches_program(bench, fresh_cache):
    out = run_tiny(bench, "serve.commsml.cascade", seconds=1.0)
    assert out["correct"] is True
    c = out["checks"]
    assert c["unanswered"]["value"] == 0 and c["misrouted"]["value"] == 0
    assert c["score_gap"]["value"] < 1e-5
    assert 0.1 < out["info"]["isolated_share"] < 0.4


def test_emulated_precisions_are_ordered():
    """float32 (highest) < three bfloat16 passes < one pass, each at its
    own scale of error."""
    import jax.numpy as jnp
    from cbench.reference import CONTROL, matmul
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 256)).astype(np.float32)
    b = rng.standard_normal((256, 32)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)

    def err(p):
        got = np.asarray(matmul(jnp.asarray(a), jnp.asarray(b), p))
        return float(np.max(np.abs(got - exact) / scale))
    assert err("highest") < 5e-7 < err("bf16_3x") < 1e-5 < err("bf16") \
        < 1e-2
    assert CONTROL == {"float32": "bf16_3x", "tensorfloat32": "bf16"}


def test_control_fails_the_committed_limits(bench, fresh_cache):
    """The reference at the precision below the configuration's, put in
    the program's place, is not correct; the program is."""
    import jax

    from cbench import campaign, harness
    from cbench.reference import CONTROL
    from cbench.spans import Spans
    _, config, traffic = harness.cell_files(
        bench, "campaign.commsml.grid", os.path.dirname(harness.bench_dir()))
    config = tiny_config(samples=40, rounds=4)(config)
    traffic = tiny_traffic(cells=[
        {"scheme": "tolfl", "k": 2}, {"scheme": "fl", "k": 1}])(traffic)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    gen = campaign.Campaign(config, traffic, 21, 1, Spans(), 0.1)
    gen.warm_up()
    gen.window(0.1)
    limits = traffic["limits"]
    prog = gen.compare()
    ctrl = gen.compare(CONTROL[config["matmul_precision"]],
                       against_reference=True)
    assert all(prog[k] <= limits[k] for k in limits)
    assert any(ctrl[k] > limits[k] for k in limits)
    assert ctrl["loss_gap_early"] > 100 * prog["loss_gap_early"]
