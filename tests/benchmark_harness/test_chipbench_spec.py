"""BENCHMARK.json and the data files it names: everything exists, parses and
keeps to the contract's characters; the harness resolves things by name, so a
later PR adds a cell, a configuration, a mix or a per-layer metric with new
files and one entry each, editing none."""

import json
import os
import re

import pytest
from chipbench_util import ROOT, bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(doc["paths"]) <= 16 and len(doc["command"]) <= 32
    for p in doc["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and not p.startswith("/") and ".." not in p
    prog = [w for w in doc["command"] if "/" in w]
    assert prog and all(any(w.startswith(p + "/") for p in doc["paths"]) for w in prog)


def test_harness_self_check_is_clean():
    assert bench().problems() == []


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys(doc, group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[group]
    names = [e["name"] for e in doc[group]]
    assert len(set(names)) == len(names)
    for e in doc[group]:
        assert set(e) <= allowed, (e["name"], set(e) - allowed)
        assert NAME.match(e["name"])
        for k in {"why", "layer"} & set(e):
            assert LINE.match(e[k]), (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            assert e["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if group == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace") and 0 < e["bound"] <= 0.1
        if group == "workloads":
            assert NAME.match(e["config"]) and NAME.match(e["traffic"]) and e["chips"] in (1, 4)
        if group == "configs":
            assert LINE.match(e["source"]) and len(e["reduced"]) <= 16 and all(NAME.match(k) for k in e["reduced"])
    if group == "end_to_end":
        assert "setup_s" in names
    if group == "workloads":
        four = sum(w["chips"] == 4 for w in doc[group])
        assert four <= max(1, len(doc[group]) // 4)
