"""BENCHMARK.json as the harness reads it: allowed names and units, and
every cell's files found by name."""
import importlib
import json
import os
import re

import pytest

from bench import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def _names():
    out = [("config", c["name"]) for c in BENCH["configs"]]
    out += [("workload", w["name"]) for w in BENCH["workloads"]]
    out += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    out += [("metric", m["name"])
            for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    out += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_name_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and metric["layer"]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for word in BENCH["command"][1:]:
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    spec = run.load_cell(cell)
    assert spec["cell"]["config"] == spec["entry"]["config"]
    assert spec["cell"]["traffic"] == spec["entry"]["traffic"]
    assert spec["cell"]["chips"] == spec["entry"]["chips"]
    assert spec["model"]["name"] == spec["entry"]["config"]
    assert set(spec["cell"]["limits"]) <= set(run.CHECK_NUMBERS)
    family = run.family(spec)
    for name in ("model_config", "make_inputs", "flops_per_sample"):
        assert callable(getattr(family, name))
    reference = run.reference(spec)
    for name in ("init_params", "encode", "views"):
        assert callable(getattr(reference, name))
    for m in spec["per_layer"]:
        reader = importlib.import_module("bench.metrics." + m["name"])
        assert callable(reader.read)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec["per_layer"]


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    with open(os.path.join(ROOT, config["file"])) as f:
        model = json.load(f)
    assert model["name"] == config["name"]
    assert model["reduced"] == config["reduced"]
    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
