"""The readers of the program's own marks (``sortbench/progspans.py``
and the four layer files that use it), on made-up intervals and on
traces recorded on a v5e chip: ``is_2p20.1chip.xplane.pb``, from a
program that opens no annotation and tags no op, where every reader
finds nothing, and ``is_2p20.1chip.tagged.xplane.pb``, recorded after
the program put its spans on the trace's clock and tagged its ops."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from sortbench import opclass, progspans, reduce, spec

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"
READERS = ("verify.fingerprint_pct", "egress.fetch_pct", "verify.device_pct",
           "device.idle_outside_phases_pct")


def _reader(name):
    return spec.load_module(spec.HERE / "layers" / f"{name}.py")


def _made_up():
    ev = reduce.HostEvent
    trace = reduce.Trace(
        devices=[reduce.Device("/device:TPU:0", [
            reduce.Op(10, 20, '%a = custom-call(), frontend_attributes='
                              '{layer="local_sort"}'),
            reduce.Op(60, 70, '%v = fusion(), frontend_attributes='
                              '{layer="verify"}'),
            reduce.Op(75, 80, '%w = reduce()')])],
        host=[ev(0, 30, "phase:sort"), ev(30, 70, "phase:verify"),
              ev(35, 45, "verify.fingerprint"), ev(40, 50, "verify.fingerprint"),
              ev(85, 95, "egress.fetch"), ev(80, 100, "phase:decode"),
              ev(0, 100, "$api.py:1 sort")],
        window=[(0, 100)], calls=1)
    return SimpleNamespace(trace=trace)


def test_shares_of_made_up_intervals():
    run = _made_up()
    assert progspans.annotation_share_pct(run, "verify.fingerprint") == \
        pytest.approx(15.0)  # union of [35, 50] over 100 ns
    assert progspans.annotation_share_pct(run, "egress.fetch") == \
        pytest.approx(10.0)
    assert progspans.annotation_share_pct(run, "phase:plan") is None
    assert progspans.layer_share_pct(run, "verify") == pytest.approx(10.0)
    assert progspans.layer_share_pct(run, "exchange") is None
    # idle [0,10) [20,60) [70,75) [80,100); phases cover [0,70) and
    # [80,100): only [70,75) is idle outside every phase
    assert progspans.idle_outside_pct(run) == pytest.approx(5.0)


def test_no_trace_reads_nothing():
    run = SimpleNamespace(trace=None)
    assert [_reader(n).read(run) for n in READERS] == [None] * 4


def test_untagged_trace_reads_nothing():
    """A trace of a program without annotations or layer tags
    (``is_2p20.1chip.xplane.pb``): each reader leaves its metric out."""
    run = SimpleNamespace(trace=reduce.reduce_file(
        str(FIXTURES / "is_2p20.1chip.xplane.pb")))
    assert [_reader(n).read(run) for n in READERS] == [None] * 4


@pytest.fixture(scope="module")
def tagged():
    """Two warm 2^20-key calls on a v5e chip, recorded with
    ``sortbench/tests/record_fixture.py`` after the change."""
    return SimpleNamespace(trace=reduce.reduce_file(
        str(FIXTURES / "is_2p20.1chip.tagged.xplane.pb")))


# Shares of the window, the union of the two calls (15.06297 ms): the
# 80.02 us between them, idle and outside every phase, counts toward none.
@pytest.mark.parametrize("name,value", [
    ("verify.fingerprint_pct", 8.48093038756633),
    ("egress.fetch_pct", 15.148008659646804),
    ("verify.device_pct", 0.15696771619408392),
    ("device.idle_outside_phases_pct", 11.954348976330698)])
def test_readers_on_the_tagged_fixture(tagged, name, value):
    assert _reader(name).read(tagged) == pytest.approx(value, rel=1e-9)


def test_tagged_fixture_names_its_layers(tagged):
    tr = tagged.trace
    assert tr.calls == 2
    names = {e.name for e in tr.host}
    assert {"phase:encode", "phase:device_put", "phase:sort", "phase:verify",
            "phase:decode", "verify.fingerprint", "verify.check",
            "egress.fetch", "egress.decode"} <= names
    dev = tr.devices[0]
    kernels = [o.name for o in dev.ops if opclass.PALLAS in o.name]
    # six bitonic stages per call, each named and tagged; none names a
    # mesh axis, so opclass still counts them all as the local sort
    assert len(kernels) == 12
    assert all(k.startswith("%bitonic.") and 'layer="local_sort"' in k
               and opclass.REMOTE_DMA not in k for k in kernels)
    assert len([o for o in dev.ops if opclass.is_local_sort(o.name)]) == 12
    assert tr.op_seconds(dev, opclass.is_local_sort) == pytest.approx(
        0.000666727, rel=1e-9)
    assert any('layer="verify"' in o.name for o in dev.ops)


def test_annotations_keep_the_breakdown_whole(tagged):
    """The annotations nest among the Python frames, so the idle time
    split by frame adds up to the idle time (within the few µs each
    span's exit frame outlives its annotation)."""
    tr = tagged.trace
    b = tr.breakdown(top=1000)
    idle = tr.window_s - tr.busy_s(tr.busiest())
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(idle, rel=0.01)
    assert b["idle_gaps"][0][0] == "array.py:631 _value"
