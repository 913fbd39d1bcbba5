"""BENCHMARK.json is well formed, and everything it names is a file the
harness finds by that name."""
import json
import os
import re

from .conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units():
    b = _bench()
    assert set(b) == KEYS["top"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[section]]
        assert len(names) == len(set(names))
        for e in b[section]:
            assert set(e) - {"workloads"} == KEYS[section], e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for e in b["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" for e in b["end_to_end"])


def test_every_name_resolves_to_a_file():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {e["name"] for e in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    used = set()
    for w in b["workloads"]:
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        used.add(w["config"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
    assert used == set(configs)
    for c in configs.values():
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.exists(path)
        with open(path) as f:
            cfg = json.load(f)
        for key in ("rows", "dims", "queries", "data", "reference", "cam",
                    "limits", "controls"):
            assert key in cfg, (c["name"], key)
        assert os.path.exists(os.path.join(
            ROOT, "bench", "data", cfg["data"]["generator"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "refs", cfg["reference"] + ".py"))
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", m["name"].split(".")[0] + ".py"))
    for cell in cells:          # every cell reports a per-layer metric
        assert any(cell in m["workloads"] for m in b["per_layer"])


def test_a_roofline_share_is_a_percentage():
    for m in _bench()["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
