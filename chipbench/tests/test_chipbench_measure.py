"""The yardstick's arithmetic: the trace reduction on a small committed
trace, the FLOP count, the table of peaks and the metric readers."""
import json
import os

import pytest

from cbench import flops, peaks, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
COMMSML = {"input_dim": 112, "hidden": [128, 64], "code_dim": 32}
CIFAR10 = {"input_dim": 3072, "hidden": [128, 64], "code_dim": 32}


def test_trace_reduction_on_committed_trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        rec = json.load(f)
    r = tracereduce.reduce_trace(rec)
    # chip 0: fusion.9 clipped to [100, 150], union [100, 170], [300,
    # 400], [500, 520] = 190 ns; chip 1 busy the whole 500 ns window,
    # its while loop left out of the per-op totals
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx((190 + 500) / 2 * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - 345 / 500)
    assert [n for n, _ in r["device_ops"]] == [
        "dot.3", "fusion.1", "fusion.9", "fusion.2"]
    assert [v for _, v in r["device_ops"]] == pytest.approx(
        [300e-9, 35e-9, 25e-9, 15e-9])
    # chip 0's gaps: (170, 300) inside execute, (400, 500) at the start
    # of the second plan, (520, 600) inside it
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"plan": 180e-9 / 2, "execute": 130e-9 / 2})


def test_interval_helpers():
    assert tracereduce.merge([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tracereduce.gaps([(1, 4), (5, 7)], 0, 10) == [
        (0, 1), (4, 5), (7, 10)]
    assert tracereduce.clip([(0, 3), (8, 12)], 2, 10) == [(2, 3), (8, 10)]


def test_no_device_plane_is_an_error():
    with pytest.raises(tracereduce.NoDevicePlane):
        tracereduce.reduce_trace({"devices": {}, "host": [],
                                  "window": [0, 1]})


@pytest.mark.parametrize("model,count", [(COMMSML, 49680),
                                         (CIFAR10, 810400)])
def test_param_count_matches_hand_count(model, count):
    assert flops.param_count(model) == count


def test_train_flops_per_scenario():
    # Comms-ML: 450 rows on each of 10 devices, E = 3, 60 rounds
    f = flops.train_flops_per_scenario(COMMSML, [450] * 10, 3, 60)
    assert f == 6 * 49680 * 4500 * 3 * 60
    assert f == pytest.approx(2.414e11, rel=1e-3)


def test_configs_state_their_counts():
    root = os.path.dirname(HERE)
    for name in ("commsml-ae", "cifar10-ae"):
        with open(os.path.join(root, "configs", f"{name}.json")) as f:
            c = json.load(f)
        assert flops.param_count(c["model"]) == c["parameters"]
        assert c["matmul_precision"] == "tensorfloat32"
        for key in ("source", "reduced", "assumed", "widths"):
            assert key in c


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_metric_readers(bench):
    from cbench import harness
    trace = {"busy_s": 2.0, "window_s": 10.0, "idle_share": 0.8,
             "chips": 1}
    camp = {"kind": "campaign", "trace": trace, "window_s": 10.0,
            "chips": 1, "peaks": peaks.peaks("TPU v5 lite"),
            "counters": {"scenarios": 40, "executions": 4, "plan_s": 0.2,
                         "flops": 1.97e13, "exe_load_s": 7.5}}
    serve = {"kind": "serve", "trace": trace, "window_s": 10.0, "chips": 1,
             "peaks": None, "counters": {"windows": 1000, "batches": 50,
                                         "exe_load_s": 1.5}}
    want = {"plan_ms.campaign": (camp, 50.0),
            "device_ms_per_scenario.campaign": (camp, 50.0),
            "device_idle_share.campaign": (camp, 80.0),
            "mfu.campaign": (camp, 1.0),
            "dispatches_per_window.serve": (serve, 0.05),
            "device_idle_share.serve": (serve, 80.0),
            "exe_load_s": (serve, 1.5)}
    assert {m["name"] for m in bench["per_layer"]} <= set(want)
    for name, (ctx, value) in want.items():
        assert harness.metric_reader(name)(ctx) == pytest.approx(value)
    # a reader finds nothing to read in the other kind of cell
    assert harness.metric_reader("mfu.campaign")(serve) is None
    assert harness.metric_reader("dispatches_per_window.serve")(camp) \
        is None
