"""The trace reduction, on a trace recorded on a v5e chip (PR 22: two
warm ``mpitest_tpu.sort`` calls of 2^20 NPB keys, each inside a
``sortbench.call`` annotation) and on made-up intervals."""

from pathlib import Path

import pytest

from sortbench import opclass, reduce

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / \
    "is_2p20.1chip.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return reduce.reduce_file(str(FIXTURE))


def test_window_is_the_annotated_calls(trace):
    assert trace.calls == 2
    assert len(trace.window) == 2
    assert (trace.window[0][0], trace.window[-1][1]) == (46440035.0,
                                                        63337084.0)
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]


def test_busy_union_and_idle_share(trace):
    dev = trace.devices[0]
    assert len(dev.ops) == 86
    assert trace.busy_s(dev) == pytest.approx(0.000726896, rel=1e-9)
    assert trace.busy_s_mean == trace.busy_s(dev)
    idle = sum(e - s for s, e in dev.gaps(trace.window)) * 1e-9
    assert idle + trace.busy_s(dev) == pytest.approx(trace.window_s)


def test_op_matching(trace):
    dev = trace.devices[0]
    local = [o for o in dev.ops if opclass.is_local_sort(o.name)]
    # six bitonic stages per call (block sort, 4 cross/merge, fused tail)
    assert len(local) == 12
    assert all(opclass.PALLAS in o.name for o in local)
    assert not any(opclass.is_exchange(o.name) for o in dev.ops)
    assert trace.op_seconds(dev, opclass.is_local_sort) == pytest.approx(
        0.000666725, rel=1e-9)


def test_breakdown_shape(trace):
    b = trace.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "%f.6 custom-call"
    total_idle = sum(v for _, v in b["idle_gaps"])
    assert total_idle <= trace.window_s - trace.busy_s_mean + 1e-12


def test_merge_and_gaps_of_made_up_intervals():
    dev = reduce.Device("d", [reduce.Op(10, 20, "a"), reduce.Op(15, 30, "b"),
                              reduce.Op(40, 50, "c"), reduce.Op(0, 5, "d")])
    assert dev.merged([(2, 45)]) == [(2, 5), (10, 30), (40, 45)]
    assert dev.busy_ns([(2, 45)]) == 3 + 20 + 5
    assert dev.gaps([(0, 60)]) == [(5, 10), (30, 40), (50, 60)]


def test_short_names():
    assert reduce.short_name(
        "%f.27 = s32[2048,512,128]{2,1,0:T(8,128)} custom-call(s32[1]"
        "{0:T(128)} %x), custom_call_target=\"tpu_custom_call\"") == \
        "%f.27 custom-call"
    assert reduce.short_name(
        "%slice-start = ((u32[4]{0:T(1024)}), u32[2]{0}, s32[]{:S(2)}) "
        "async-start(u32[4]{0} %w), calls=%c") == "%slice-start async-start"


def test_self_time_of_nested_frames():
    ev = reduce.HostEvent
    frames = reduce.self_intervals([
        ev(0, 100, "$a.py:1 outer"), ev(10, 40, "$b.py:2 inner"),
        ev(20, 30, "leaf"), ev(60, 70, "$c.py:3 other")])
    assert frames == [(0, 10, "a.py:1 outer"), (10, 20, "b.py:2 inner"),
                      (20, 30, "leaf"), (30, 40, "b.py:2 inner"),
                      (40, 60, "a.py:1 outer"), (60, 70, "c.py:3 other"),
                      (70, 100, "a.py:1 outer")]


def test_idle_time_split_by_frames(trace):
    b = trace.breakdown(top=1000)
    idle = trace.window_s - trace.busy_s(trace.busiest())
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(idle)
    assert b["idle_gaps"][0][0] == "array.py:631 _value"


def test_nothing_between_two_calls_counts():
    """Two calls, [0, 40) and [60, 100) ns, with the harness's check
    between them: the op, the frame and the spans inside the gap count
    toward no reading, and an op across a boundary counts its parts
    inside the calls."""
    from types import SimpleNamespace

    from sortbench import progspans, spec

    ev = reduce.HostEvent
    verify = 'frontend_attributes={layer="verify"}'
    trace = reduce.Trace(
        devices=[reduce.Device("/device:TPU:0", [
            reduce.Op(10, 20, "%a = s32[8] fusion()"),
            reduce.Op(35, 65, "%s = s32[8] copy()"),
            reduce.Op(45, 55, f"%g = s32[8] fusion(), {verify}"),
            reduce.Op(80, 84, f"%v = s32[8] fusion(), {verify}")])],
        host=[ev(0, 40, "$api.py:1 sort"), ev(0, 30, "phase:sort"),
              ev(40, 60, "$harness.py:1 mismatches"),
              ev(42, 58, "phase:verify"), ev(50, 55, "egress.fetch"),
              ev(60, 100, "$api.py:1 sort"), ev(70, 80, "egress.fetch")],
        window=[(0, 40), (60, 100)], calls=2)
    run = SimpleNamespace(trace=trace)
    assert trace.window_s == pytest.approx(80e-9)
    assert trace.busy_s(trace.devices[0]) == pytest.approx(24e-9)
    idle = spec.load_module(spec.HERE / "layers" / "device.idle_pct.py")
    assert idle.read(run) == pytest.approx(70.0)
    assert progspans.annotation_share_pct(run, "egress.fetch") == \
        pytest.approx(12.5)
    assert progspans.layer_share_pct(run, "verify") == pytest.approx(5.0)
    # idle [0,10) [20,35) [65,80) [84,100); phase:sort covers [0,30)
    assert progspans.idle_outside_pct(run) == pytest.approx(45.0)
    b = trace.breakdown()
    assert dict(b["device_ops"]) == pytest.approx(
        {"%a fusion": 10e-9, "%s copy": 10e-9, "%v fusion": 4e-9})
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"phase:sort": 20e-9, "api.py:1 sort": 26e-9, "egress.fetch": 10e-9})
