"""The yardstick's arithmetic: the trace reduction on a small committed
trace, the body's FLOP and kernel counts, the table of peaks and the
metric readers."""
import json
import os

import pytest

from cbench import harness, peaks, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
COMMSML = {"kind": "autoencoder", "input_dim": 112, "hidden": [128, 64],
           "code_dim": 32, "act": "relu", "dropout": 0.2}
CIFAR10 = dict(COMMSML, input_dim=3072)
AE = harness.body("autoencoder")


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
    assert r["kernels"] == {}


def test_trace_reduction_sums_kernels_by_prefix():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        rec = json.load(f)
    k = tracereduce.reduce_trace(rec, kernels=("fusion", "dot", "fus",
                                               "absent"))["kernels"]
    # fusion.9 clipped to 50 ns, fusion.1 twice (50 + 20), fusion.2 30;
    # dot.3 on both chips (100 + 500), summed over chips; "fus" is no
    # op's name or prefix before a dot
    assert k["fusion"]["calls"] == 4
    assert k["fusion"]["seconds"] == pytest.approx(150e-9)
    assert k["dot"] == {"calls": 2, "seconds": pytest.approx(600e-9)}
    assert k["fus"] == k["absent"] == {"calls": 0, "seconds": 0.0}


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
    assert AE.param_count(model) == count


def test_train_flops_per_scenario():
    # Comms-ML: 450 rows on each of 10 devices, E = 3, 60 rounds
    f = AE.train_flops_per_scenario(COMMSML, [450] * 10, 3, 60)
    assert f == 6 * 49680 * 4500 * 3 * 60
    assert f == pytest.approx(2.414e11, rel=1e-3)


def test_recon_out_kernel_counts():
    """One call at the cifar cell's bucket: 6 scenarios x 10 devices x
    1313 padded rows, width 3072, first hidden width 128."""
    geometry = {"kind": "single", "scenarios": 6, "devices": 10,
                "rows": 1313}
    c = AE.kernel_counts(CIFAR10, geometry)["recon_out"]
    assert c["flops"] == 3 * 2 * 60 * 1313 * 128 * 3072
    assert c["flops"] == pytest.approx(1.859e11, rel=1e-3)
    # x once per device (161.3 MB); per scenario and device W and dW,
    # b and db, h and dh, the row weights and the loss tile (271.2 MB)
    x = 10 * 1313 * 3072 * 4
    per = 4 * (2 * 128 * 3072 + 2 * 3072 + 2 * 1313 * 128 + 1313 + 128)
    assert c["bytes"] == x + 60 * per == 432_576_240
    assert AE.kernel_counts(CIFAR10, dict(geometry, kind="multi")) == {}
    # too narrow for the kernel (the program keeps the XLA expression)
    assert AE.kernel_counts(COMMSML, geometry) == {}


def test_configs_state_their_counts():
    root = os.path.dirname(HERE)
    for name in ("commsml-ae", "cifar10-ae"):
        with open(os.path.join(root, "configs", f"{name}.json")) as f:
            c = json.load(f)
        assert harness.body(c["model"]["kind"]).param_count(c["model"]) \
            == c["parameters"]
        assert c["matmul_precision"] == "tensorfloat32"
        for key in ("source", "reduced", "assumed", "widths"):
            assert key in c


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_metric_readers(bench, monkeypatch):
    from repro import obs
    # recon_out: 4 calls of 1.97e11 FLOPs (3 passes: 3 ms each at the
    # bf16 peak) and 0.4095 GB (0.5 ms each) in 20 ms of device time
    trace = {"busy_s": 2.0, "window_s": 10.0, "idle_share": 0.8,
             "chips": 1, "kernels": {"recon_out": {"seconds": 0.02,
                                                   "calls": 4}}}
    camp = {"kind": "campaign", "trace": trace, "window_s": 10.0,
            "chips": 1, "peaks": peaks.peaks("TPU v5 lite"),
            "precision": "tensorfloat32",
            "counters": {"scenarios": 40, "executions": 2, "plan_s": 0.1,
                         "flops": 1.97e13, "exe_load_s": 7.5,
                         "kernels": {"recon_out": {
                             "calls": 4, "flops": 4 * 1.97e11,
                             "bytes": 4 * 4.095e8}}}}
    serve = {"kind": "serve", "trace": trace, "window_s": 10.0, "chips": 1,
             "peaks": None, "precision": "tensorfloat32",
             "counters": {"windows": 1000, "batches": 50,
                          "exe_load_s": 1.5}}
    # the program's registry: the window's two executions after the
    # warm-up's
    records = [{"build_s": 9.0, "post_s": 9.0, "gc_s": 9.0,
                "h2d_bytes": 9e9}]
    records += [{"build_s": b, "post_s": p, "gc_s": g, "h2d_bytes": h}
                for b, p, g, h in ((0.05, 0.03, 0.001, 2e8),
                                   (0.07, 0.05, 0.0, 3e8))]
    monkeypatch.setattr(obs, "executions", lambda: list(records))
    want = {"plan_ms.campaign": (camp, 50.0),
            "device_ms_per_scenario.campaign": (camp, 50.0),
            "device_idle_share.campaign": (camp, 80.0),
            "mfu.campaign": (camp, 1.0),
            "exe_load_s": (serve, 1.5),
            "build_ms.campaign": (camp, 60.0),
            "post_ms.campaign": (camp, 40.0),
            "gc_ms.campaign": (camp, 0.5),
            "h2d_mb.campaign": (camp, 250.0),
            "recon_out_roofline.campaign": (camp, 60.0),
            "dispatches_per_window.serve": (serve, 0.05),
            "device_idle_share.serve": (serve, 80.0)}
    assert {m["name"] for m in bench["per_layer"]} <= set(want)
    for name, (ctx, value) in want.items():
        assert harness.metric_reader(name)(ctx) == pytest.approx(value)
    # a reader finds nothing to read in the other kind of cell
    assert harness.metric_reader("mfu.campaign")(serve) is None
    assert harness.metric_reader("dispatches_per_window.serve")(camp) \
        is None
    # nor the kernel's roofline where the kernel did not run
    roofline = harness.metric_reader("recon_out_roofline.campaign")
    assert roofline(serve) is None
    assert roofline(dict(camp, trace=dict(trace, kernels={
        "recon_out": {"seconds": 0.0, "calls": 0}}))) is None
    assert roofline(dict(camp, trace=dict(trace, kernels={}))) is None


def test_roofline_refuses_a_count_of_other_calls():
    trace = {"kernels": {"recon_out": {"seconds": 0.02, "calls": 5}}}
    ctx = {"kind": "campaign", "trace": trace,
           "peaks": peaks.peaks("TPU v5 lite"), "precision": "float32",
           "counters": {"kernels": {"recon_out": {
               "calls": 4, "flops": 1.0, "bytes": 1.0}}}}
    with pytest.raises(RuntimeError, match="5 calls in the trace"):
        harness.metric_reader("recon_out_roofline.campaign")(ctx)
