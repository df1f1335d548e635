"""The readers of the 64-bit host route's marks
(``local_sort_2w_roofline``, ``encode.words_pct``, ``pair.plan_pct``),
on made-up intervals checked by hand, and on traces without the marks,
where each leaves its metric out."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from sortbench import reduce, spec

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"
READERS = ("local_sort_2w_roofline", "encode.words_pct", "pair.plan_pct")
SORT = '%s = sort(), frontend_attributes={layer="local_sort"}'


def _reader(name):
    return spec.load_module(spec.HERE / "layers" / f"{name}.py")


def _run(trace, keys=819, chips=1):
    cell = SimpleNamespace(config={"keys_per_call": keys, "dtype": "int64"},
                           chips=chips)
    return SimpleNamespace(trace=trace, cell=cell,
                           device={"kind": "TPU v5 lite"})


def _made_up():
    ev = reduce.HostEvent
    return reduce.Trace(
        devices=[
            reduce.Device("/device:TPU:0", [
                reduce.Op(10, 40, SORT),
                reduce.Op(60, 94, SORT),
                reduce.Op(94, 98, '%v = fusion(), frontend_attributes='
                                  '{layer="verify"}'),
                reduce.Op(98, 99, "%c = copy()")]),
            reduce.Device("/device:TPU:1", [reduce.Op(0, 20, SORT)])],
        host=[ev(0, 20, "phase:encode"), ev(5, 15, "encode.words"),
              ev(15, 20, "pair.plan"), ev(40, 55, "phase:encode"),
              ev(40, 50, "encode.words"), ev(50, 55, "pair.plan"),
              ev(0, 100, "$api.py:1 sort")],
        window=[(0, 100)], calls=2)


def test_roofline_of_made_up_ops():
    """Floor: 2 calls x 2 x 819 keys x 8 B / 819 GB/s = 32 ns; time: the
    busier chip's 30 + 34 ns of ops tagged local_sort (the verify and
    untagged ops do not count) = 64 ns; 50 %."""
    assert _reader("local_sort_2w_roofline").read(_run(_made_up())) == \
        pytest.approx(50.0)
    # two chips: each holds half the keys, so the floor halves
    assert _reader("local_sort_2w_roofline").read(
        _run(_made_up(), chips=2)) == pytest.approx(25.0)


@pytest.mark.parametrize("name,value", [
    ("encode.words_pct", 20.0),   # [5, 15) and [40, 50) of 100 ns
    ("pair.plan_pct", 10.0)])     # [15, 20) and [50, 55)
def test_span_shares_of_made_up_intervals(name, value):
    assert _reader(name).read(_run(_made_up())) == pytest.approx(value)


def test_no_trace_reads_nothing():
    assert [_reader(n).read(_run(None)) for n in READERS] == [None] * 3


def test_trace_without_the_marks_reads_nothing():
    tr = _made_up()
    tr.host = [e for e in tr.host if e.name.startswith(("phase:", "$"))]
    for d in tr.devices:
        d.ops = [o for o in d.ops if "local_sort" not in o.name]
    assert [_reader(n).read(_run(tr)) for n in READERS] == [None] * 3


def test_untagged_recorded_trace_reads_nothing():
    """A chip trace of a program that opens no annotation and tags no op
    (``is_2p20.1chip.xplane.pb``): each reader leaves its metric out."""
    run = _run(reduce.reduce_file(str(FIXTURES / "is_2p20.1chip.xplane.pb")))
    assert [_reader(n).read(run) for n in READERS] == [None] * 3


def test_int32_recorded_trace_opens_no_host_encode():
    """The tagged chip trace of int32 calls: the raw keys encode on the
    device, so neither host-encode span is there to read."""
    run = _run(reduce.reduce_file(
        str(FIXTURES / "is_2p20.1chip.tagged.xplane.pb")))
    assert _reader("encode.words_pct").read(run) is None
    assert _reader("pair.plan_pct").read(run) is None
