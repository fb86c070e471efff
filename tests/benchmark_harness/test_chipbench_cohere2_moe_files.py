"""What PR 51 adds to the benchmark as files: the configuration
``command-a-plus-ep16-d4`` (the published ``config.json`` of
command-a-plus-05-2026 cut to one period, 8 of 128 experts and an eighth of
the vocabulary), the cell ``rollout-command-a-plus-ep16-d4-longctx-grpo`` of
the kind ``rollout_family`` on the traffic ``grpo-long-context`` as it was,
four per-layer metrics and two new readers (the rings' bytes a decode step,
the band's operations a prompt pass, each against the device time of the
window layers' scope). Every assertion is about THIS cell's own entries and
their order: none counts a list, takes an entry by its place, or says that a
list holds one name."""

import json
import os

import pytest
from chipbench_cohere2_moe_util import CELL, CONFIG
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json"
NEW = ["decode_moe_shared_pct", "decode_window_ring_roofline", "prefill_window_attn_roofline", "scope_coverage_pct.rollout-cohere"]
MOE = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean"}  # the expert block's, as cells 5, 7, 8, 10
WINDOW = {"decode_window_attn_pct", "prefill_window_attn_pct"}  # the window layers', as cell 9
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-long-context" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family" and callable(b.cell_kind("rollout_family").run) and p["dtype"] == "bfloat16"
    assert (p["clients"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 8, 4) and p["warm_seconds"] >= 10
    assert p["server"] == {"slots": 64, "max_seq_len": 20480, "page_size": 128, "kv_hbm_gb": 1.5, "decode_steps": 32, "attn_window_step": 20480}
    mix = cell["traffic"]
    assert mix == b.cell("rollout-glm-5-ep16-d6-longctx-grpo")["traffic"]  # the file as it was: cell 8's
    assert (mix["generator"], mix["group_size"], mix["max_total"], mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == ("grpo_groups", 8, 19456, 4096, 16384)
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and MOE | WINDOW <= set(per_layer)  # this PR's four, in this order
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert set(per_layer) == qwen | set(NEW) | MOE | WINDOW
    assert "decode_shared_kv_roofline" not in per_layer  # its reader asks for ``phi4flash`` by a check of its own
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    for m in b.doc["per_layer"]:
        if m["name"] in NEW:  # the entry and the metric's file say the same, and the cell is the one this PR adds
            lm = b.layer_metric(m["name"])
            assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves")) and m["workloads"][0] == CELL
            assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        elif CELL in m.get("workloads", []):  # an older metric: this cell's name was appended, nothing put before it
            before = [w for w in m["workloads"] if w in ("rollout-solar-open2-ep16-d8-longctx-grpo", "rollout-phi-4-mini-flash-longctx-grpo", "rollout-granite-h-micro-grpo")]
            assert all(m["workloads"].index(CELL) > m["workloads"].index(w) for w in before)
    for m in b.doc["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            assert CELL in m["workloads"] and m["workloads"].index(CELL) > m["workloads"].index("rollout-solar-open2-ep16-d8-longctx-grpo")
    shared, ring, band, cover = (b.layer_metric(n) for n in NEW)
    assert (shared["reader"], shared["scopes"], shared["moves"], shared["within_module"]) == ("scope_share_named", ["moe_shared"], "tpot_p95_ms", "^jit_chunk\\(")
    assert (ring["reader"], ring["scopes"], ring["layer"], ring["moves"], ring["tokens_counter"], ring["chunks_counter"], ring["within_module"]) == (
        "window_ring_roofline", ["attn_window"], "kernels", "tpot_p95_ms", "areal_decode_window_tokens_read_total", "areal_decode_chunks_total", "^jit_chunk\\(")
    assert (band["reader"], band["scopes"], band["layer"], band["moves"], band["pairs_counter"], band["within_module"]) == (
        "window_prompt_roofline", ["attn_window"], "kernels", "ttft_p50_ms", "areal_decode_window_prompt_pairs_total", "^jit_prefill\\(")
    plain = set(b.layer_metric("scope_coverage_pct.rollout-gdn")["scopes"]) - {"gdn_proj", "gdn_conv", "gdn_state", "state_write"}
    assert set(cover["scopes"]) == plain | {"attn_window", "attn_rope", "block_sum", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"} and cover["base"] == "busy"
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert "state" not in fam and "server" not in fam["control"]  # no recurrent state to probe; no int8 pages beside rings
    assert {"wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"} == set(fam["control"]["round_int8"])
    assert fam["counters"][:2] == ["areal_decode_window_tokens_read_total", "areal_decode_window_prompt_pairs_total"] and {
        "areal_decode_moe_experts_touched_total", "areal_decode_moe_assignments_total", "areal_decode_chunks_total"} <= set(fam["counters"])
    assert fam["status"] == ["moe", "kv_pools"] and set(fam["not_the_programs"]) <= set(cell["model"]["assumed"])
    chk = p["check"]
    assert chk["sample"] == 8 and 0 < chk["limit"] < 0.5 and "state_probe" not in chk
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("4k-16k", "window", "rings", "experts", "8 x"))
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("solar-open2-250b-ep16-d8") and cells.index(CELL) > cells.index("rollout-solar-open2-ep16-d8-longctx-grpo")  # appended after what was there
    # the reference imports nothing from the program and sets the precision itself
    with open(os.path.join(CHIP, "benchlib", fam["reference"] + ".py")) as f:
        text = f.read()
    assert "areal_tpu" not in text.split('"""', 2)[2] and 'default_matmul_precision("highest")' in text


def test_the_parents_entries_are_all_still_there_and_only_appended_to():
    """Against the parent commit's BENCHMARK.json where git has it: every
    entry it had is there unchanged but for this cell's name appended to
    ``workloads`` lists, in the parent's order; no file the parent's
    benchmark had is edited."""
    import subprocess

    b = bench()
    parent = "e6c8ae88fe755a68e4f5725e4aad572dd4ccfff9"
    try:
        old = json.loads(subprocess.run(["git", "show", f"{parent}:BENCHMARK.json"], cwd=b.root, capture_output=True, check=True, text=True).stdout)
        changed = subprocess.run(["git", "diff", "--name-status", parent, "--", *b.doc["paths"]], cwd=b.root, capture_output=True, check=True, text=True).stdout.split("\n")
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    new = b.doc
    assert {k: new[k] for k in ("command", "paths", "run_seconds")} == {k: old[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in new[group]]
        assert names[: len(old[group])] == [x["name"] for x in old[group]]  # the parent's entries first, in its order
        for was in old[group]:
            now = new[group][names.index(was["name"])]
            if "workloads" in was and now != was:
                assert {k: v for k, v in now.items() if k != "workloads"} == {k: v for k, v in was.items() if k != "workloads"}
                # the parent's cells first, in its order; what follows is this PR's cell or a later PR's
                assert now["workloads"][: len(was["workloads"])] == was["workloads"] and len(now["workloads"]) > len(was["workloads"])
            else:
                assert now == was
    assert [c["name"] for c in new["configs"] if c["name"] not in {x["name"] for x in old["configs"]}][0] == CONFIG
    assert [w["name"] for w in new["workloads"] if w["name"] not in {x["name"] for x in old["workloads"]}][0] == CELL
    added = [m["name"] for m in new["per_layer"] if m["name"] not in {x["name"] for x in old["per_layer"]}]
    assert added[: len(NEW)] == NEW
    # under the benchmark's paths nothing the parent had was modified or deleted (a later PR may add more)
    assert all(line.split("\t")[0] == "A" for line in changed if line)  # (git lists them once they are staged or committed)


def test_configuration_file_is_the_published_config_cut_as_it_says():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == REDUCED and cfg["source"] == entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 32, "layer_types": period * 8, "num_experts": 128, "vocab_size": 262144}
    assert (cfg["num_hidden_layers"], cfg["layer_types"], cfg["num_experts"], cfg["vocab_size"]) == (4, period, 8, 32768)
    if os.path.isfile(CATALOG):  # key for key the catalog row's config, but what ``reduced`` names; no width among those
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "command-a-plus-05-2026")
        assert row["source_url"] == SOURCE and set(row["config"]) <= set(cfg)
        assert {k: cfg[k] for k in row["config"] if k not in REDUCED} == {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert cfg["reduced_from"] == {k: row["config"][k] for k in REDUCED}
        assert set(cfg) - set(row["config"]) == {"moe_intermediate_size", "source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for"}
    assumed = cfg["assumed"]
    assert cfg["moe_intermediate_size"] == assumed["moe_intermediate_size"] == cfg["intermediate_size"] == 4096  # the ADDED key: the catalog's own inference
    assert (assumed["router_experts"], assumed["expert_first"], assumed["block_form"], assumed["norm_form"]) == (128, 0, "parallel", "layernorm_no_bias")
    assert (assumed["shared_expert_average"], assumed["router_form"], assumed["window_counts_own_token"], assumed["kv_dtype"]) == (
        "mean_of_shared_added", "sigmoid_no_bias", True, "bfloat16")
    assert assumed["initializer_range"] == 0.02 and 0 < assumed["expert_own_share"] <= 1 and assumed["vision_tower"] == "absent"
    assert assumed["attn_out_init_scale"] == 0.125  # no selection bias to settle: what unbalances a seeded router is cured at its source (the weights' docstring)
    assert set(assumed) == set(cfg["assumed_notes"])  # every assumption with its reason
    assert "vision tower" in cfg["stands_for"] and "NOT served" in cfg["assumed_notes"]["vision_tower"]
    assert all(s in cfg["stands_for"] for s in ("128", "8 pipeline stages", "16 chips sharing EACH layer", "rank 0", "Left out", "8 x"))
    # the program reads the file through the cell's kind: the published keys and the assumed ones that are its own
    load_run()
    from benchlib.cells import rollout_family

    mcfg = rollout_family.model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert mcfg.layer_types == ("swa", "swa", "swa", "attention") and (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (8, 128, 0)
    assert (mcfg.block_form, mcfg.norm_kind, mcfg.norm_bias, mcfg.moe_shared_mean_of, mcfg.sliding_window) == ("parallel", "layer", False, 4, 4096)
    assert mcfg.ring_shapes(64, 128)["ring_k"][0] == (3, 8, 65, 32, 128, 128) and mcfg.state_shapes(64) == {}
    # 65 ring blocks x 3 layers x (K, V) x 8 heads x 4,096 tokens x 128 lanes x 2 B = 3.27 GB; 3,072 pages of 512 KB
    assert 2 * 3 * 8 * 65 * 4096 * 128 * 2 == 3_271_557_120 and 1.5 * 2**30 / (2 * 8 * 128 * 128 * 2) == 3072


def _recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_ring_readers_bytes_of_a_token_and_its_silence_on_another_program():
    """4,096 B a (ring token, window layer) at the published sizes, by hand
    against a synthetic trace's seconds; on a program without the counter or
    the scope (the parent's, any other family's) the reader returns nothing
    and does not raise."""
    b = bench()
    reader = b.reader("window_ring_roofline")
    cfg = b.cell(CELL)["model"]
    assert reader.ring_token_bytes(cfg) == 2 * 8 * 128 * 2 == 4096
    metric = b.layer_metric("decode_window_ring_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    recorded = _recorded()
    stand_in = {**metric, "scopes": ["mlp"]}  # the recorded Qwen program has no attn_window: ``mlp`` stands in for it
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], metric["within_module"])
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]
    counters = {"areal_decode_window_tokens_read_total": 5 * 32 * 3 * 22 * 4096.0, "areal_decode_chunks_total": 5.0}
    facts = {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "status": ({}, {})}}
    want = 100.0 * (32 * 3 * 22 * 4096.0 * runs * 4096 / 819e9) / secs
    assert reader.read(stand_in, facts) == pytest.approx(want, rel=1e-9) and want > 0
    assert reader.read(metric, facts) is None  # no such scope in that program
    assert reader.read(stand_in, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None  # a program without the counter
    assert reader.read(stand_in, {**facts, "extra": None}) is None and reader.read(stand_in, {**facts, "trace": None}) is None
    assert reader.read(stand_in, {**facts, "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # a cell without a window
    named = b.reader("scope_share_named")
    assert named.read(b.layer_metric("decode_moe_shared_pct"), recorded) is None  # the recorded program has no such scope
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-cohere"), recorded) == pytest.approx(old)


def test_the_band_reader_counts_the_bands_work_whatever_computes_it():
    """65,536 operations a (query, key) pair at the published sizes (4 x 128
    heads x 128); the share is the BAND's operations over the chip's peak
    against the scope's device seconds: a launch that does twice the band's
    work in twice the time reads 50, not 100; silence on another program."""
    b = bench()
    reader = b.reader("window_prompt_roofline")
    cfg = b.cell(CELL)["model"]
    assert reader.pair_ops(cfg) == 4 * 128 * 128 == 65536
    metric = b.layer_metric("prefill_window_attn_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    recorded = _recorded()
    stand_in = {**metric, "scopes": ["mlp"], "within_module": "^jit_chunk\\("}  # the recorded trace's chunk programs stand in for a prompt program
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], stand_in["within_module"])
    at_peak = secs * 197e12 / 65536  # the pairs a launch at the chip's peak would have attended in those seconds
    facts = lambda pairs: {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {"trace_counters": {metric["pairs_counter"]: pairs}, "status": ({}, {})}}  # noqa: E731
    assert reader.read(stand_in, facts(at_peak)) == pytest.approx(100.0, rel=1e-9)
    # the same seconds spent on twice the band's pairs (tiles outside the band visited too): the BAND's work is half of them
    assert reader.read(stand_in, facts(at_peak / 2)) == pytest.approx(50.0, rel=1e-9)
    # a 16k prompt's three window layers: 58.7 M pairs each, 3.8 TFLOP a layer as ISSUE 51 reckons
    pairs_16k = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert round(pairs_16k / 1e6, 1) == 58.7 and round(pairs_16k * 65536 / 1e12, 1) == 3.8
    assert reader.read(metric, facts(at_peak)) is None  # no such scope in that program
    assert reader.read(stand_in, facts(0.0)) is None  # a program without the counter, or no prompt pass in the span
    assert reader.read(stand_in, {**facts(at_peak), "extra": None}) is None and reader.read(stand_in, {**facts(at_peak), "trace": None}) is None
    assert reader.read(stand_in, {**facts(at_peak), "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # a cell without a window
