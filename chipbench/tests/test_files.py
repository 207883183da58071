"""Every file the harness finds by name parses and matches BENCHMARK.json."""
import importlib.util
import json
import os
import re

import pytest

from chipbench import reference, run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names(kind):
    return [e["name"] for e in SPEC[kind]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"][1].startswith("chipbench/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_are_unique_and_plain():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = _names(kind)
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    metrics = _names("end_to_end") + _names("per_layer")
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=_names("configs"))
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cfg = json.load(open(os.path.join(run.ROOT, entry["file"])))
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert cfg[key] != cfg["published"][key]
    for key, val in cfg["published"].items():
        if key in cfg and key not in entry["reduced"]:
            assert cfg[key] == val, key
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=_names("workloads"))
def test_cell_files(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    cell = run.load_cell(entry["name"])
    assert set(cell["cell"]["limits"]) == set(reference.CHECKS)
    assert cell["cell"]["limits"]["gap"] == cell["cell"]["target_gap"]
    assert cell["config"]["n"] % 256 == 0
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert pairs.count((entry["config"], entry["traffic"])) == 1


def test_four_chip_cells_are_few():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=_names("end_to_end") + _names("per_layer"))
def test_metric_entries(m):
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = set(_names("workloads"))
    assert set(m.get("workloads", [])) <= cells
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        return
    assert m["moves"] in _names("end_to_end")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    path = os.path.join(HERE, "metrics", f"{m['name']}.py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_every_metric_file_is_named_in_the_benchmark():
    files = {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
             if f.endswith(".py")}
    assert files == set(_names("per_layer"))


def _json_files(sub):
    return {f[:-5] for f in os.listdir(os.path.join(HERE, sub))
            if f.endswith(".json")}


def test_every_cell_file_is_named_in_the_benchmark():
    assert _json_files("workloads") == set(_names("workloads"))
    assert _json_files("traffic") >= {w["traffic"] for w in SPEC["workloads"]}
    for name in _json_files("traffic"):
        traffic = json.load(open(os.path.join(HERE, "traffic",
                                              f"{name}.json")))
        assert len(traffic["why"]) <= 200


def test_layers_are_spelled_alike():
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
