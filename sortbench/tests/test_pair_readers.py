"""The readers of the pair engine's marks (``pair.residual_pct``,
``pair_fixup_roofline``), on made-up intervals checked by hand, and on
traces without the marks, where each leaves its metric out; and the
cell that reads them, resolved by name to its files."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from sortbench import reduce, spec

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"
READERS = ("pair.residual_pct", "pair_fixup_roofline")
FIX = ('%s = custom-call(), frontend_attributes={kernel_metadata={},'
       'layer="local_sort",stage="pair_fixup"}')
NET = ('%s = custom-call(), frontend_attributes={kernel_metadata={},'
       'layer="local_sort"}')


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
                reduce.Op(0, 10, NET),
                reduce.Op(10, 40, FIX),
                reduce.Op(50, 60, NET),
                reduce.Op(60, 78, FIX),
                reduce.Op(78, 80, '%v = fusion(), frontend_attributes='
                                  '{layer="verify"}')]),
            reduce.Device("/device:TPU:1", [reduce.Op(0, 20, FIX)])],
        host=[ev(0, 30, "phase:sort"), ev(20, 30, "pair.residual"),
              ev(50, 85, "phase:sort"), ev(70, 85, "pair.residual"),
              ev(0, 100, "$api.py:1 sort")],
        window=[(0, 100)], calls=2)


def test_fixup_roofline_of_made_up_ops():
    """Floor: 2 calls x 3 x 819 keys x 4 B / 819 GB/s = 24 ns; time: the
    busier chip's 30 + 18 ns of ops tagged pair_fixup (the network's
    ops, tagged local_sort alone, and the verify op do not count) =
    48 ns; 50 %."""
    assert _reader("pair_fixup_roofline").read(_run(_made_up())) == \
        pytest.approx(50.0)
    # two chips: each holds half the keys, so the floor halves
    assert _reader("pair_fixup_roofline").read(
        _run(_made_up(), chips=2)) == pytest.approx(25.0)


def test_fixup_floor_is_three_words_a_key():
    """The floor alone: 2^28 keys a call read two 4-byte words and write
    one, 3 x 2^30 B at 819 GB/s; ops that take twice that read 50 %."""
    floor_ns = 3 * 4 * (1 << 28) / 819e9 * 1e9
    tr = reduce.Trace(
        devices=[reduce.Device("/device:TPU:0",
                               [reduce.Op(0, 2 * floor_ns, FIX)])],
        host=[], window=[(0, 4 * floor_ns)], calls=1)
    assert _reader("pair_fixup_roofline").read(_run(tr, keys=1 << 28)) == \
        pytest.approx(50.0)


def test_residual_share_of_made_up_intervals():
    """[20, 30) and [70, 85) of a 100 ns window: 25 %."""
    assert _reader("pair.residual_pct").read(_run(_made_up())) == \
        pytest.approx(25.0)


def test_no_trace_reads_nothing():
    assert [_reader(n).read(_run(None)) for n in READERS] == [None] * 2


def test_trace_without_the_marks_reads_nothing():
    tr = _made_up()
    tr.host = [e for e in tr.host if e.name != "pair.residual"]
    for d in tr.devices:
        d.ops = [o for o in d.ops if "pair_fixup" not in o.name]
    assert [_reader(n).read(_run(tr)) for n in READERS] == [None] * 2


@pytest.mark.parametrize("fixture", ["is_2p20.1chip.xplane.pb",
                                     "is_2p20.1chip.tagged.xplane.pb"])
def test_recorded_int32_traces_read_nothing(fixture):
    """Chip traces of int32 calls: no pair engine ran, so neither the
    span nor the stage tag is there to read."""
    run = _run(reduce.reduce_file(str(FIXTURES / fixture)))
    assert [_reader(n).read(run) for n in READERS] == [None] * 2


def test_uniform_i64_cell_resolves_to_its_files():
    """The CUB full-entropy int64 cell: its generator, the plain
    reference, and the pair engine's metrics beside the 64-bit route's."""
    c = spec.resolve("uniform_i64.1chip")
    assert c.config["name"] == "cub_radix_keys.i64"
    assert c.config["keys_per_call"] == 1 << 28
    assert c.config["reduced"] == {}
    assert c.generator.__file__.endswith("gen/cub_bit_entropy.py")
    assert c.reference.__file__.endswith("reference/np_sort.py")
    assert c.traffic["pool"] == 2
    names = {m.name for m in c.per_layer}
    assert {"pair.residual_pct", "pair_fixup_roofline",
            "local_sort_2w_roofline", "pair.plan_pct",
            "encode.words_pct"} <= names
    assert "local_sort_roofline" not in names
    assert [m.name for m in c.end_to_end] == ["sort_mkeys_per_s", "setup_s"]
