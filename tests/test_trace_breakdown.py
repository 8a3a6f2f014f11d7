"""``scripts/trace_breakdown.py`` on a small hand-made trace: device time
by stage scope (from op_name stats, and from compiled HLO texts where
the trace has none) and idle time by the spans open on each thread."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tb():
    spec = importlib.util.spec_from_file_location(
        "trace_breakdown",
        os.path.join(ROOT, "scripts", "trace_breakdown.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _space(tb):
    """One chip and two host threads on one clock (ns):

    device ops   fusion.1 [100, 200) alive_mask (interned op_name)
                 while.1  [300, 450) a loop, left out of the op totals
                 fusion.2 [300, 400) final_scoring
                 copy.3   [400, 450) no scope
                 fusion.9 [460, 470) no op_name, only its program id
    thread A     window [0, 1000), execute [0, 900),
                 campaign.build [0, 100), campaign.fetch [100, 900)
    thread B     execute [50, 1000), campaign.build [450, 1000)
    """
    space = tb._xspace_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    for k, name in [(1, "tf_op"), (2, "program_id"), (10, "alive_mask/add")]:
        dev.stat_metadata.add(key=k).value.name = name
    metas = {1: ("fusion.1", {"ref_value": 10}),
             2: ("fusion.2",
                 {"str_value": "jit(scenario)/vmap(final_scoring)/dot"}),
             3: ("while.1", {"str_value": "jit(scenario)/while"}),
             4: ("copy.3", {"str_value": "jit(scenario)/copy"}),
             5: ("fusion.9", None)}
    for k, (name, stat) in metas.items():
        md = dev.event_metadata.add(key=k).value
        md.id, md.name = k, name
        if stat:
            md.stats.add(metadata_id=1, **stat)
        md.stats.add(metadata_id=2, int64_value=7)
    ops = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=0)
    for k, s, e in [(1, 100, 200), (3, 300, 450), (2, 300, 400),
                    (4, 400, 450), (5, 460, 470)]:
        ops.events.add(metadata_id=k, offset_ps=s * 1000,
                       duration_ps=(e - s) * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    names = ["window", "execute", "campaign.build", "campaign.fetch"]
    for i, n in enumerate(names, 1):
        md = host.event_metadata.add(key=i).value
        md.id, md.name = i, n
    for lid, spans in [(1, [("window", 0, 1000), ("execute", 0, 900),
                            ("campaign.build", 0, 100),
                            ("campaign.fetch", 100, 900)]),
                       (2, [("execute", 50, 1000),
                            ("campaign.build", 450, 1000)])]:
        line = host.lines.add(id=lid, name=f"thread{lid}", timestamp_ns=0)
        for n, s, e in spans:
            line.events.add(metadata_id=names.index(n) + 1,
                            offset_ps=s * 1000, duration_ps=(e - s) * 1000)
    return space


@pytest.fixture
def trace(tb, tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_space(tb).SerializeToString())
    hlo = tmp_path / "hlo"
    hlo.mkdir()
    (hlo / "jit_scenario.0.txt").write_text(
        '  %fusion.9 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, '
        'calls=%c, metadata={op_name="local_delta/mul" stack_frame_id=2}\n'
        '  %copy.3 = f32[2]{0} copy(f32[2]{0} %p)\n')
    return str(tmp_path), str(hlo)


def test_device_time_by_scope(tb, trace):
    path, hlo = trace
    out = tb.breakdown(path, hlo_dir=hlo)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(260e-9)
    sc = {k: v["s"] for k, v in out["scopes"].items()}
    assert sc == pytest.approx({"alive_mask": 100e-9, "final_scoring": 100e-9,
                                tb.NONE: 50e-9, "local_delta": 10e-9})
    assert out["scopes"]["alive_mask"]["share"] == pytest.approx(100 / 260)
    assert "tf_op" in out["op_stats_seen"]
    assert out["unscoped_top"] == [["jit(scenario)/copy",
                                    pytest.approx(50e-9)]]
    # without the HLO texts the op with no op_name stays unresolved
    out = tb.breakdown(path)
    assert out["scopes"]["(no op_name in trace)"]["s"] == pytest.approx(10e-9)


def test_idle_by_spans_open_on_each_thread(tb, trace):
    path, _ = trace
    idle = tb.breakdown(path)["idle"]
    assert idle == pytest.approx({
        "campaign.build + campaign.fetch": 440e-9,
        "campaign.build": 150e-9,
        "campaign.fetch + execute": 100e-9,
        "campaign.build + execute": 50e-9})
    longest = tb.breakdown(path)["idle_longest"]
    assert longest[0] == [pytest.approx(470e-9), pytest.approx(430e-9),
                          "campaign.build + campaign.fetch"]


def test_longest_instances_with_what_they_hold(tb, trace):
    path, _ = trace
    out = tb.breakdown(path, longest_spec="execute:2")["longest"]["execute"]
    assert out["count"] == 2 and out["max_s"] == pytest.approx(950e-9)
    top = out["top"]
    assert [t["s"] for t in top] == pytest.approx([950e-9, 900e-9])
    assert top[0]["inside"] == pytest.approx({"campaign.build": 550e-9})
    assert top[1]["inside"] == pytest.approx({"campaign.build": 100e-9,
                                              "campaign.fetch": 800e-9})


def test_scope_of_reads_through_transformations(tb):
    assert tb.scope_of("jit(scenario)/vmap(final_scoring)/gather") == \
        "final_scoring"
    assert tb.scope_of("local_delta/transpose(jvp(local_loss))/dot") == \
        "local_delta"
    assert tb.scope_of("jit(scenario)/_local_delta_fn.<locals>.delta") is None


def test_state_key_counts_threads(tb):
    assert tb.state_key(["campaign.fetch", None, "campaign.fetch",
                         "campaign.build"]) == \
        "campaign.fetch x2 + campaign.build"
    assert tb.state_key([None, None]) == tb.NONE
