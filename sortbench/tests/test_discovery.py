"""Every cell resolves by name to its files, and a made-up configuration,
traffic mix, metric and cell are found without a code edit."""

import json
import shutil

import pytest

from sortbench import spec


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.config["keys_per_call"] > 0
    assert hasattr(c.generator, "generate")
    assert hasattr(c.reference, "reference")
    assert hasattr(c.reference, "control")
    names = [m.name for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer and all(hasattr(m.reader, "read")
                               for m in c.end_to_end + c.per_layer)


def test_new_entries_found_by_name(tmp_path):
    shutil.copytree(spec.HERE, tmp_path / "sortbench")
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "made_up", "source": "https://example.org/made-up",
        "file": "sortbench/configs/made_up.json", "reduced": [],
        "why": "test"})
    cfg = dict(json.loads((spec.HERE / "configs" / "npb_is.C.json")
                          .read_text()), name="made_up",
               distribution="made_up_keys", keys_per_call=1024)
    (tmp_path / "sortbench/configs/made_up.json").write_text(json.dumps(cfg))
    (tmp_path / "sortbench/gen/made_up_keys.py").write_text(
        "import numpy as np\n"
        "def generate(rng, n, dtype, params):\n"
        "    return np.zeros(n, dtype)\n")
    (tmp_path / "sortbench/traffic/made_up_mix.json").write_text(
        json.dumps({"pool": 2, "why": "test"}))
    (tmp_path / "sortbench/layers/made_up.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["per_layer"].append({
        "name": "made_up.metric", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "test", "moves": "setup_s",
        "workloads": ["made_up.cell"]})
    bench["workloads"].append({"name": "made_up.cell", "config": "made_up",
                               "traffic": "made_up_mix", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    copy = spec.load_module(tmp_path / "sortbench" / "spec.py")
    cell = copy.resolve("made_up.cell")
    assert cell.config["name"] == "made_up"
    assert cell.traffic["pool"] == 2
    assert [m.name for m in cell.per_layer] == ["made_up.metric"]
    assert cell.per_layer[0].reader.read(None) == 42.0
    assert cell.generator.generate(None, 3, "int32", {}).tolist() == [0, 0, 0]
    # metrics listed for other cells only are not this cell's
    assert "host.prep_pct" not in [m.name for m in cell.per_layer]


def test_benchmark_json_keeps_the_contract():
    import re

    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert any(w["config"] == c["name"] for w in cells.values())
    four = 0
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        four += w["chips"] == 4
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= set(cells) if "workloads" in m else True
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")

    def reports(cell, metric):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert reports(cell, e2e[m["moves"]])
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        got = [m for m in b["end_to_end"] if reports(cell, m)]
        assert "setup_s" in [m["name"] for m in got] and len(got) >= 2
        assert any(reports(cell, m) for m in b["per_layer"])
