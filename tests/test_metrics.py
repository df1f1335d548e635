"""Metrics sidecar + pass-planner regression tests."""

import json

import numpy as np

from mpitest_tpu.models.api import _passes_from_diffs, _word_diffs
from mpitest_tpu.ops.keys import codec_for
from mpitest_tpu.utils.metrics import Metrics


def _needed_passes(words, digit_bits):
    """Pass count for host words — the composition sort() itself uses."""
    return _passes_from_diffs(_word_diffs(words), digit_bits)


def test_metrics_roundtrip(tmp_path):
    m = Metrics(config={"algo": "radix", "n": 1024})
    m.throughput("sort", 1_000_000, 0.5)
    m.bandwidth("all_to_all", 8_000_000_000, 1.0)
    m.record_phases({"sort": 0.25})
    p = tmp_path / "metrics.jsonl"
    m.dump(str(p))
    obj = json.loads(p.read_text().strip())
    assert obj["config"]["algo"] == "radix"
    assert obj["metrics"]["sort"] == {"value": 2.0, "unit": "Mkeys/s"}
    assert obj["metrics"]["all_to_all"] == {"value": 8.0, "unit": "GB/s"}
    assert obj["metrics"]["phase_sort_ms"] == {"value": 250.0, "unit": "ms"}


def test_needed_passes_word_alignment():
    """digit_bits ∤ 32: passes restart at word boundaries, so keys differing
    only in the high word must still cover the full low word (regression:
    contiguous bit-count undercounts and leaves the high word unsorted)."""
    codec = codec_for(np.int64)
    words = codec.encode(np.array([2**32, 0], np.int64))
    per_word = -(-32 // 12)  # 3
    assert _needed_passes(words, 12) == per_word + 1  # low word fully + 1 digit

    # 8-bit digits, int32: small range needs 1 pass (the sign-bias flip
    # cancels in max^min for same-sign keys); mixed signs span bit 31 → 4.
    c32 = codec_for(np.int32)
    assert _needed_passes(c32.encode(np.array([0, 200], np.int32)), 8) == 1
    assert _needed_passes(c32.encode(np.array([-1, 1], np.int32)), 8) == 4
    assert _needed_passes(c32.encode(np.array([5, 5], np.int32)), 8) == 0


def test_needed_passes_digit12_sorts_correctly(mesh8):
    """End-to-end: non-divisor digit width on 64-bit keys (the bug case)."""
    from mpitest_tpu.models.api import sort

    x = np.array([2**32, 0, -(2**40), 7, 2**33 + 1, -1], np.int64)
    got = sort(x, algorithm="radix", mesh=mesh8, digit_bits=12)
    np.testing.assert_array_equal(got, np.sort(x))


def test_bench_canonical_host_provenance_gate(monkeypatch, capsys, mesh8):
    """ADVICE r5 satellite: a pinned CANONICAL_NATIVE_MKEYS row only
    yields vs_canonical_native on the host class it was measured on;
    elsewhere the row carries the skip reason instead of a silently
    cross-host ratio."""
    import bench
    from mpitest_tpu.utils.platform import host_fingerprint

    monkeypatch.setenv("BENCH_LOG2N", "12")
    monkeypatch.setenv("BENCH_REPEATS", "1")
    monkeypatch.setenv("BENCH_NATIVE_RANKS", "0")
    key = ("radix", 12, "int32", 0)

    from mpitest_tpu.utils import knobs

    # bench.main() pins SORT_FALLBACK=0 / SORT_MAX_RETRIES=0 /
    # SORT_EXCHANGE_ENGINE=lax / SORT_PLANNER=off via
    # os.environ.setdefault — correct for its normal subprocess life,
    # but an IN-PROCESS call here would leak the pins into every later
    # test in the suite (observed: the whole supervisor-ladder family
    # failing "retry budget exhausted", and the exchange-engine knob
    # test seeing default "lax", in full runs while passing
    # standalone).  scoped_env restores the pre-call state.
    _BENCH_PINS = dict(SORT_FALLBACK=None, SORT_MAX_RETRIES=None,
                       SORT_EXCHANGE_ENGINE=None, SORT_PLANNER=None)
    monkeypatch.setitem(bench.CANONICAL_NATIVE_MKEYS, key,
                        {"mkeys": 1.0, "host": "someone-elses-box/64c"})
    with knobs.scoped_env(**_BENCH_PINS):
        bench.main()
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "vs_canonical_native" not in row
    assert "someone-elses-box/64c" in row["vs_canonical_native_skipped"]

    monkeypatch.setitem(bench.CANONICAL_NATIVE_MKEYS, key,
                        {"mkeys": 1.0, "host": host_fingerprint()})
    with knobs.scoped_env(**_BENCH_PINS):
        bench.main()
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["vs_canonical_native"] > 0
    assert "vs_canonical_native_skipped" not in row


def test_bench_driver_contract(tmp_path):
    """The driver scrapes exactly ONE JSON line from bench.py stdout with
    the metric/value/unit/vs_baseline fields.  Runs tiny on a 2-device
    virtual CPU mesh (BENCH_PLATFORM hook) so no TPU is needed."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(
        os.environ,
        BENCH_PLATFORM="cpu:2",
        BENCH_LOG2N="14",
        BENCH_REPEATS="1",
        BENCH_NATIVE_RANKS="0",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
    )
    r = subprocess.run(
        [sys.executable, str(repo / "bench.py")],
        capture_output=True, text=True, env=env, timeout=600, cwd=str(repo),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    obj = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= obj.keys()
    assert obj["unit"] == "Mkeys/s" and obj["value"] > 0
