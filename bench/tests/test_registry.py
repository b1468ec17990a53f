"""The registry finds every configuration, traffic mix and metric reader
that BENCHMARK.json names, by name; the file keeps to its own shape."""
import json
import re

import pytest

from bench import registry

BM = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    cell = registry.cell(w["name"])
    assert cell.cfg["name"] == w["config"]
    assert cell.traffic["name"] == w["traffic"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    registry.reference(cell.cfg["reference"])
    for m in cell.per_layer:
        assert callable(registry.reader(m["name"]).read)


def test_names_units_and_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for section, allowed in keys.items():
        for e in BM[section]:
            assert set(e) == allowed
            assert NAME.match(e["name"])
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(BM)) < 64 * 1024


def test_config_files_hold_what_they_reduce():
    for c in BM["configs"]:
        cfg = registry.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert cfg["published"][key] != cfg[key]


def test_unknown_device_kind_raises():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("cpu")


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        registry.cell("no-such-cell")
    with pytest.raises(ModuleNotFoundError):
        registry.reader("no_such_metric.steady")
