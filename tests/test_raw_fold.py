"""Raw-key ingest on one device: 32- and 64-bit integer host keys ship
as they are and encode on the device, and the input fingerprint is
folded from the raw keys.

The fold must equal the fingerprint of the host-encoded words bit for
bit (the device verifier compares it with the fold of the sorted
words), the route must engage only for the constant-XOR codecs, the
64-bit route's device plan must be the host plan, and the host fold
must still cover the transfer."""

from __future__ import annotations

import numpy as np
import pytest

from mpitest_tpu.models import api, local
from mpitest_tpu.models import verify as vfy
from mpitest_tpu.models.supervisor import SortIntegrityError
from mpitest_tpu.ops.keys import KeyCodec, codec_for
from mpitest_tpu.utils import knobs
from mpitest_tpu.utils.trace import Tracer

I32 = np.iinfo(np.int32)
#: INT32_MIN, -1, 0, INT32_MAX: the sign-bit and all-ones edges
SPECIALS = np.array([I32.min, -1, 0, I32.max], np.int32)


def _keys(case: str, dtype) -> np.ndarray:
    """The edge cases of the key's width (INTn_MIN, -1, 0, INTn_MAX),
    alone and with a random bulk of odd or even length, as ``dtype``."""
    signed = np.int64 if np.dtype(dtype).itemsize == 8 else np.int32
    info = np.iinfo(signed)
    specials = np.array([info.min, -1, 0, info.max], signed)
    rng = np.random.default_rng(11)
    bulk = rng.integers(info.min, info.max, 4097, dtype=np.int64,
                        endpoint=True).astype(signed)
    x = {"empty": specials[:0],
         "min": specials[:1], "minus_one": specials[1:2],
         "zero": specials[2:3], "max": specials[3:],
         "three": specials[:3], "four": specials,
         "even": np.concatenate([specials, bulk[:4092]]),
         "odd": np.concatenate([specials, bulk])}[case]
    return x.view(dtype)


@pytest.mark.parametrize("case", ["empty", "min", "minus_one", "zero", "max",
                                  "three", "four", "even", "odd"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.int64, np.uint64])
def test_raw_fold_is_the_encoded_words_fingerprint(dtype, case):
    x = _keys(case, dtype)
    codec = codec_for(dtype)
    assert codec.raw_bias is not None
    assert vfy.fingerprint_raw(x, codec.raw_bias) == \
        vfy.fingerprint_host(codec.encode(x))


#: the 10 codec dtypes
DTYPES = (np.int8, np.uint8, np.int16, np.uint16, np.int32, np.uint32,
          np.int64, np.uint64, np.float32, np.float64)
#: 2-word cells on the pair engine (at least 2^13 keys) sort as many
#: keys as the 64-bit plan cells below, so they share their programs
N_PAIR = 1 << 14

#: what _finish_local adds after every route: verify, then decode
FINISH = ["phase:verify", "verify.check", "verify_contig",
          "phase:decode", "egress.fetch", "egress.decode"]
#: ... and for the constant-XOR codecs: the device decode's dispatch,
#: the verifier on the decoded keys, then the fetch alone
DEVICE_FINISH = ["phase:decode", "egress.decode", "decode",
                 "phase:verify", "verify.check", "verify_keys",
                 "phase:decode", "egress.fetch"]
#: the dtypes whose one-device results decode on the device
DEVICE_DECODED = (np.int32, np.uint32, np.int64, np.uint64)
#: the route's span and program order, before FINISH (program calls
#: appear as their traced_call labels)
SPANS = {
    "raw": ["phase:device_put", "phase:sort", "local_device",
            "phase:verify", "verify.fingerprint"],
    "raw_pair": ["phase:device_put", "phase:verify", "verify.fingerprint",
                 "phase:encode", "encode.words", "pair_split",
                 "phase:encode", "pair.plan", "phase:sort", "pair_sort",
                 "pair.residual"],
    "host": ["phase:encode", "encode.words", "phase:verify",
             "verify.fingerprint", "phase:device_put", "phase:sort",
             "local"],
    "host_pair": ["phase:encode", "encode.words", "pair.plan",
                  "phase:verify", "verify.fingerprint", "phase:device_put",
                  "phase:sort", "pair_sort", "pair.residual"],
    "device": ["verify_encode_fp", "phase:sort", "local_device"],
    "device_pair": ["verify_encode_fp", "phase:sort", "pair_fused"],
    "staged": ["phase:sort", "local"],
}
#: the children of the phases, as the span log names them
CHILDREN = {"verify.fingerprint", "verify.check", "encode.words",
            "pair.plan", "pair.residual", "egress.fetch", "egress.decode"}


def _route_cells():
    """(dtype, site, engine, route, local_engine, encode_site): every
    codec dtype at every input site under the default engine, plus the
    2-word routes a forced bitonic engine opens: raw and host planning
    for each 2-word dtype, and the fused device program once (each
    dtype compiles its own, ~30 s under the CPU interpreter)."""
    cells = []
    for dt in DTYPES:
        raw = dt in (np.int32, np.uint32)
        cells.append((dt, "host", None, "raw" if raw else "host", "lax",
                      "device" if raw else "host"))
        cells.append((dt, "device", None, "device", "lax", "device"))
        cells.append((dt, "staged", None, "staged", "lax", "host"))
    for dt in (np.int64, np.uint64, np.float64):
        raw = dt != np.float64
        cells.append((dt, "host", "bitonic", "raw_pair" if raw else
                      "host_pair", "bitonic_pair",
                      "device" if raw else "host"))
    cells.append((np.int64, "device", "bitonic", "device_pair",
                  "bitonic_pair", "device"))
    return cells


def _route_keys(dtype, n: int) -> np.ndarray:
    """``n`` keys of ``dtype`` (finite floats: np.sort is the oracle)."""
    rng = np.random.default_rng(5)
    if np.dtype(dtype) == np.float64:  # spread wide: few equal hi words
        return rng.standard_normal(n) * 10.0 ** rng.integers(-200, 200, n)
    if np.dtype(dtype).kind == "f":
        return (rng.standard_normal(n) * 1e6).astype(dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)


def _span_order(tr: Tracer) -> list[str]:
    """The phases, their children and the program calls of one sort, in
    the order they started."""
    out = []
    for s in sorted(tr.spans.spans, key=lambda s: s.id):
        if s.name in ("jit_execute", "jit_compile_execute"):
            out.append(str(s.attrs["label"]))
        elif s.name.startswith("phase:") or s.name in CHILDREN:
            out.append(s.name)
    return out


def _sort_at(x: np.ndarray, site: str, tr: Tracer) -> np.ndarray:
    import jax

    from mpitest_tpu.parallel.mesh import make_mesh

    one = make_mesh(1)
    if site == "staged":
        return api.sort(api.ingest_to_mesh(x, mesh=one), mesh=one,
                        tracer=tr)
    if site == "device":
        with jax.enable_x64(x.dtype.itemsize == 8):
            return api.sort(jax.device_put(x, one.devices.flat[0]),
                            mesh=one, tracer=tr)
    return api.sort(x, mesh=one, tracer=tr)


@pytest.mark.parametrize(
    "dtype,site,engine,route,local_engine,encode_site", _route_cells(),
    ids=lambda v: getattr(v, "__name__", v))
def test_one_device_host_input_encode_site(dtype, site, engine, route,
                                           local_engine, encode_site,
                                           monkeypatch):
    """The one-device route table: each codec dtype at each input site
    takes its route, in its order of spans and programs (the raw routes
    are the benchmark's: int32 keys put, sorted, then folded; int64 keys
    put, folded, then split and planned), with its engine and encode
    site, and sorts exactly."""
    n = N_PAIR if engine == "bitonic" else 3001
    x = _route_keys(dtype, n)
    if route.startswith("raw"):
        def refuse(self, keys):
            raise AssertionError("host encode on the raw route")
        monkeypatch.setattr(KeyCodec, "encode", refuse)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE=engine):
        got = _sort_at(x, site, tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["encode_site"] == encode_site
    assert tr.counters["local_engine"] == local_engine
    device = dtype in DEVICE_DECODED
    assert tr.counters["decode_site"] == ("device" if device else "host")
    assert _span_order(tr) == SPANS[route] + (DEVICE_FINISH if device
                                              else FINISH)


def test_local_module_imports_point_one_way():
    """``models/local.py`` imports nothing of the distributed driver:
    ``api`` calls it, never the other way round."""
    import ast
    from pathlib import Path

    src = Path(local.__file__).read_text()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names.update(f"{node.module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
    banned = {f"mpitest_tpu.models.{m}" for m in
              ("api", "planner", "radix_sort", "sample_sort", "supervisor")}
    assert not names & banned


def _f64_plan_class(case: str) -> np.ndarray:
    """N_PAIR finite float64 keys of one 64-bit plan class."""
    rng = np.random.default_rng(29)
    lo = rng.integers(0, 2**32, N_PAIR, dtype=np.uint64)
    hi = rng.integers(0x0010_0000, 0x7FE0_0000, N_PAIR, dtype=np.uint64)
    if case == "all_equal":
        hi, lo = hi[:1], lo[:1]
    elif case == "const_hi":
        hi = hi[:1]
    elif case == "const_lo":
        lo = lo[:1]
    elif case == "dup_hi":
        hi = rng.choice(hi[:8], N_PAIR)
    u = (hi << np.uint64(32)) | lo
    return np.broadcast_to(u, (N_PAIR,)).copy().view(np.float64)


@pytest.mark.parametrize("case,engine,sort", [
    ("all_equal", "constant", []),
    ("const_hi", "bitonic_1w1", ["phase:sort", "local_1w"]),
    ("const_lo", "bitonic_1w0", ["phase:sort", "local_1w"]),
    ("dup_hi", "lax", ["phase:sort", "local_2w_lax"]),
    ("uniform", "bitonic_pair", ["phase:sort", "pair_sort",
                                 "pair.residual"]),
])
def test_host_pair_plan_picks_the_engine(case, engine, sort):
    """Host-planned 2-word keys (float64 on the pair engine) take the
    engine their plan names, after the host route's encode, plan, fold
    and put."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = _f64_plan_class(case)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"):
        got = api.sort(x, mesh=make_mesh(1), tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["local_engine"] == engine
    assert tr.counters.get("pair_dup_reroute", 0) == (engine == "lax")
    assert _span_order(tr) == SPANS["host_pair"][:-3] + sort + FINISH


@pytest.mark.parametrize("hit", [True, False])
def test_planner_passthrough_on_one_device(hit):
    """The planner's passthrough takes the host route: one verify of the
    put words, then the host decode of those verified words on a hit, or
    the sort and the device decode, verified as keys, on a miss."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = np.arange(1 << 13, dtype=np.int32)
    if not hit:
        x[5], x[6] = x[6], x[5]  # hidden between the profile's samples
    tr = Tracer()
    with knobs.scoped_env(SORT_PLANNER="on"):
        got = api.sort(x, mesh=make_mesh(1), tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    assert tr.counters["encode_site"] == "host"
    check = ["phase:verify", "verify.check", "verify_contig"]
    host = SPANS["host"][:-2]
    if hit:
        assert tr.counters["planner_passthrough"] == 1
        assert tr.plan.decisions["ladder"].chosen == "passthrough"
        assert tr.counters["decode_site"] == "host"
        assert _span_order(tr) == host + check + FINISH[3:]
    else:
        assert tr.counters["planner_passthrough_miss"] == 1
        assert tr.plan.decisions["ladder"].chosen == "local"
        assert tr.counters["decode_site"] == "device"
        assert _span_order(tr) == (host + check + SPANS["host"][-2:]
                                   + DEVICE_FINISH)


def test_raw_route_fold_covers_the_transfer(monkeypatch):
    """A key changed in flight (after the host fold's source, before the
    device program) must fail verification: the fold reads the host
    array, never the transferred copy."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = np.random.default_rng(9).integers(-1000, 1000, 2048,
                                          dtype=np.int64).astype(np.int32)
    real, hits = local.checked_device_put, []

    def corrupt_once(v, target):
        out = real(v, target)
        if not hits:
            hits.append(target)
            out = out.at[17].add(1)
        return out

    monkeypatch.setattr(local, "checked_device_put", corrupt_once)
    tr = Tracer()
    with pytest.raises(SortIntegrityError):
        api.sort(x, mesh=make_mesh(1), tracer=tr)
    assert len(hits) == 1 and tr.counters["encode_site"] == "device"


N64 = 1 << 14


def _plan_class(case: str, dtype) -> np.ndarray:
    """2^14 keys of one 64-bit plan class, as ``dtype``."""
    rng = np.random.default_rng(23)
    lo = rng.integers(0, 2**32, N64, dtype=np.uint64)
    hi = rng.integers(0, 2**32, N64, dtype=np.uint64)
    if case == "all_equal":
        u = np.full(N64, 0x8000_0007_1234_5678, np.uint64)
    elif case == "const_hi":
        u = (np.uint64(0x7FFF_FFFF) << np.uint64(32)) | lo
    elif case == "const_lo":
        u = (hi << np.uint64(32)) | np.uint64(0x9ABC_DEF0)
    elif case == "ycsb":
        from sortbench import spec

        gen = spec.load_module(spec.HERE / "gen" / "ycsb_scrambled_zipfian.py")
        params = spec.resolve("ycsb_zipf_i64.1chip").config[
            "distribution_params"]
        u = gen.generate(rng, N64, "int64", params).view(np.uint64)
    else:
        u = (hi << np.uint64(32)) | lo
    return u.view(dtype)


def _host_plan(x: np.ndarray) -> tuple[str, int]:
    """The engine and sniff count the host-encoded words give."""
    hi, lo = codec_for(x.dtype).encode(x)
    dups = local._host_hi_dup_sniff(hi)
    const = (hi.min() == hi.max(), lo.min() == lo.max())
    return {(True, True): "constant", (True, False): "bitonic_1w1",
            (False, True): "bitonic_1w0"}.get(
                const, "lax" if dups else "bitonic_pair"), dups


@pytest.mark.parametrize("case,engine", [
    ("all_equal", "constant"), ("const_hi", "bitonic_1w1"),
    ("const_lo", "bitonic_1w0"), ("ycsb", "lax"), ("uniform", "bitonic_pair"),
])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_64bit_host_input_splits_and_plans_on_device(dtype, case, engine,
                                                     monkeypatch):
    """One-device 64-bit host keys never meet the host encode, and the
    device split's plan is the host plan: same engine, same sniff."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = _plan_class(case, dtype)
    want_engine, want_dups = _host_plan(x)
    assert want_engine == engine

    real = KeyCodec.encode

    def refuse_64bit(self, keys):
        if self.dtype.itemsize == 8 and self.dtype.kind in "iu":
            raise AssertionError("host encode on the device route")
        return real(self, keys)

    monkeypatch.setattr(KeyCodec, "encode", refuse_64bit)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"):
        got = api.sort(x, mesh=make_mesh(1), tracer=tr)
    np.testing.assert_array_equal(got, np.sort(x))
    c = tr.counters
    assert c["encode_site"] == "device"
    assert (c["local_engine"], c["pair_sniff_dups"]) == (engine, want_dups)
    assert c.get("pair_dup_reroute", 0) == (engine == "lax")


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_64bit_raw_route_fold_covers_the_transfer(dtype, monkeypatch):
    """As for 32-bit keys: a 64-bit key changed in flight fails
    verification, since the fold reads the host array."""
    from mpitest_tpu.parallel.mesh import make_mesh

    x = np.random.default_rng(9).integers(
        -1000, 1000, 1 << 13, dtype=np.int64).view(dtype)
    real, hits = local.checked_device_put, []

    def corrupt_once(v, target):
        out = real(v, target)
        if not hits:
            hits.append(target)
            out = out.at[17].add(1)
        return out

    monkeypatch.setattr(local, "checked_device_put", corrupt_once)
    tr = Tracer()
    with knobs.scoped_env(SORT_LOCAL_ENGINE="bitonic"), \
            pytest.raises(SortIntegrityError):
        api.sort(x, mesh=make_mesh(1), tracer=tr)
    assert len(hits) == 1 and tr.counters["encode_site"] == "device"
