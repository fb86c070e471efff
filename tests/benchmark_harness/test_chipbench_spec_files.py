"""Every file BENCHMARK.json names exists and parses, each per-layer metric moves a
metric its cells report, and a cell, a configuration, a mix and a metric are
added by files alone."""

import json
import os
import re
import shutil

import pytest
from chipbench_util import CHIP, ROOT, bench, load_run



@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_named_file_exists_and_parses(doc):
    b = bench()
    used = set()
    for c in doc["configs"]:
        assert c["file"] not in used
        used.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:  # never a width
            assert not re.search(r"(_dim|_rank|_size|head_dim|per_tok)$", k), k
    for w in doc["workloads"]:
        cell = b.cell(w["name"])
        assert cell["params"]["kind"] and b.cell_kind(cell["params"]["kind"]).run
        assert cell["traffic"]["generator"]
    for m in doc["per_layer"]:
        lm = b.layer_metric(m["name"])
        assert callable(b.reader(lm["reader"]).read)
        for k in ("unit", "layer", "moves", "source", "better"):
            assert lm[k] == m[k], (m["name"], k)
    for dirpath, _, files in os.walk(CHIP):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), os.path.join(dirpath, fn)


def test_each_layer_metric_moves_a_metric_its_cells_report(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = [w["name"] for w in doc["workloads"]]
    for m in doc["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        mine = [m for m in doc["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(cell in m.get("workloads", cells) for m in doc["per_layer"])


def test_added_by_files_alone(tmp_path):
    """A throwaway configuration, mix, cell and per-layer metric in a copy of
    the benchmark: new files plus one BENCHMARK.json entry each, no edit."""
    from benchlib import spec

    root = tmp_path / "checkout"
    shutil.copytree(CHIP, root / "benchmarks" / "chip", ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    extra = root / "benchmarks" / "chip"
    (extra / "configs" / "throwaway.json").write_text(
        json.dumps({**json.load(open(os.path.join(CHIP, "configs", "qwen2.5-1.5b.json"))), "num_hidden_layers": 2, "reduced": ["num_hidden_layers"]})
    )
    (extra / "traffic" / "throwaway-mix.json").write_text(json.dumps({"generator": "grpo_groups", "group_size": 2}))
    (extra / "workloads" / "throwaway-cell.json").write_text(json.dumps({"kind": "rollout", "server": {"slots": 4}}))
    (extra / "layer_metrics" / "throwaway_pct.json").write_text(
        json.dumps({"reader": "throwaway_reader", "layer": "device", "unit": "%", "better": "higher", "moves": "rollout_tok_s", "source": "program_counter"})
    )
    (extra / "layer_metrics" / "readers" / "throwaway_reader.py").write_text("def read(metric, facts):\n    return 42.0\n")
    doc["configs"].append({"name": "throwaway", "source": "https://example.org/x", "file": "benchmarks/chip/configs/throwaway.json", "reduced": ["num_hidden_layers"], "why": "test"})
    doc["workloads"].append({"name": "throwaway-cell", "config": "throwaway", "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] in ("rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms"):
            m["workloads"].append("throwaway-cell")
    doc["per_layer"].append({"name": "throwaway_pct", "unit": "%", "better": "higher", "source": "program_counter", "layer": "device", "moves": "rollout_tok_s", "workloads": ["throwaway-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = spec.Bench(str(root))
    assert b.problems() == []
    cell = b.cell("throwaway-cell")
    assert cell["model"]["num_hidden_layers"] == 2 and cell["traffic"]["group_size"] == 2
    assert cell["params"]["server"]["slots"] == 4
    assert [m["name"] for m in cell["per_layer"]] == ["throwaway_pct"]
    assert b.reader(b.layer_metric("throwaway_pct")["reader"]).read({}, {}) == 42.0
    assert b.cell_kind(cell["params"]["kind"]).run


def test_unknown_workload_is_an_error():
    from benchlib import spec

    with pytest.raises(spec.SpecError):
        load_run().run_cell(bench(), "no-such-cell", 1, 1.0, False)
