"""BENCHMARK.json is well-formed and every name in it has its files."""
import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark", "tests/bench_harness"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_names_are_unique_and_setup_s_is_there():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and "setup_s" in names


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_has_its_files(w):
    assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    config = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "guarantees", "engine", "tolerance",
                "reference", "weights", "costs", "rehearse"):
        assert key in cfg, key
    assert cfg["source"] == config["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    base = os.path.join(ROOT, "benchmark", "layer_metrics", m["name"])
    with open(base + ".json") as f:
        meta = json.load(f)
    for key in ("layer", "unit", "source", "moves"):
        assert meta[key] == m[key]
    # the metric's json names its reader: "<file>.py:<function>"
    file, _, func = meta["reader"].partition(":")
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(os.path.dirname(base), file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(getattr(mod, func))
