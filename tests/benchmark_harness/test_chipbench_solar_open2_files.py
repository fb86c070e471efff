"""What PR 47 adds to the benchmark as files: the configuration
``solar-open2-250b-ep16-d8`` (the published ``config.json`` of
Solar-Open2-250B cut to two periods, 20 of 320 experts and an eighth of the
vocabulary), the cell ``rollout-solar-open2-ep16-d8-longctx-grpo`` of the
kind ``rollout_family_probe`` on the traffic ``grpo-long-context`` as it
was, four per-layer metrics and one new reader (the kda state's bytes against
the device time of the recurrence's scope). Every assertion is about THIS
cell's own entries and their order: none counts a list, takes an entry by its
place, or says that a list holds one name."""

import json
import os

import pytest
from chipbench_solar_open2_util import CELL, CONFIG
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
NEW = ["decode_kda_pct", "prefill_kda_pct", "decode_kda_state_roofline", "scope_coverage_pct.rollout-kda"]
MOE = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean"}  # the expert block's, as cells 5, 7, 8
KDA = ["kda_proj", "kda_conv", "kda_state", "state_write"]
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-long-context" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family_probe" and callable(b.cell_kind("rollout_family_probe").run) and p["dtype"] == "bfloat16"
    # 50 s of warm traffic where the other cells take 10 (the file says why 50 and not 30): the eight clients' first groups arrive at once and their eight
    # prompt passes (1.4 s each) take 17 s; at 10 s their tail lay inside the window and `tpot_p95_ms` read 36-126 ms
    assert (p["clients"], p["warm_seconds"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 52, 8, 4)
    server = dict(p["server"])
    assert server.pop("kv_hbm_gb") in (2.75, 3.0)
    assert server == {"slots": 64, "max_seq_len": 20480, "page_size": 128, "decode_steps": 32, "attn_window_step": 20480}
    mix = cell["traffic"]
    assert mix == b.cell("rollout-glm-5-ep16-d6-longctx-grpo")["traffic"]  # the file as it was: cell 8's
    assert (mix["generator"], mix["group_size"], mix["max_total"], mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == ("grpo_groups", 8, 19456, 4096, 16384)
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and MOE <= set(per_layer)  # this PR's four, in this order
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert set(per_layer) == qwen | set(NEW) | MOE
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    for m in b.doc["per_layer"]:
        if m["name"] in NEW:  # the entry and the metric's file say the same, and the cell is the one this PR adds
            lm = b.layer_metric(m["name"])
            assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves")) and m["workloads"][0] == CELL
            assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        elif CELL in m.get("workloads", []):  # an older metric: this cell's name was appended, nothing put before it
            before = [w for w in m["workloads"] if w in ("rollout-glm-5-ep16-d6-longctx-grpo", "rollout-phi-4-mini-flash-longctx-grpo", "rollout-granite-h-micro-grpo")]
            assert all(m["workloads"].index(CELL) > m["workloads"].index(w) for w in before)
    for m in b.doc["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            assert CELL in m["workloads"] and m["workloads"].index(CELL) > m["workloads"].index("rollout-phi-4-mini-flash-longctx-grpo")
    dec, pre, roof, cover = (b.layer_metric(n) for n in NEW)
    assert dec["scopes"] == pre["scopes"] == KDA and dec["reader"] == pre["reader"] == "scope_share_named"
    assert (dec["moves"], dec["within_module"], pre["moves"], pre["within_module"]) == ("tpot_p95_ms", "^jit_chunk\\(", "ttft_p50_ms", "^jit_prefill\\(")
    assert (roof["reader"], roof["scopes"], roof["layer"], roof["updates_counter"], roof["chunks_counter"]) == (
        "kda_state_roofline", ["kda_state"], "kernels", "areal_decode_kda_state_updates_total", "areal_decode_chunks_total")
    plain = set(b.layer_metric("scope_coverage_pct.rollout-gdn")["scopes"]) - {"gdn_proj", "gdn_conv", "gdn_state"}
    assert set(cover["scopes"]) == plain | set(KDA) | {"attn_gate", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"} and cover["base"] == "busy"
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert fam["state"] == {"leaf": "kda", "dtype_key": "kda_state_dtype", "control_dtype": "bfloat16"}
    assert fam["control"]["server"] == {"kv_quantization": "int8"}
    assert {"we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down", "wq", "wk", "wv", "wo", "wg", "q_proj", "k_proj", "v_proj", "o_proj", "f_a", "f_b", "g_a", "g_b"} == set(fam["control"]["round_int8"])
    assert fam["counters"][0] == "areal_decode_kda_state_updates_total" and {
        "areal_decode_moe_experts_touched_total", "areal_decode_moe_assignments_total", "areal_decode_chunks_total",
        "areal_decode_state_copies_total", "areal_decode_state_prefills_total"} <= set(fam["counters"])
    assert fam["status"] == ["moe"] and set(fam["not_the_programs"]) <= set(cell["model"]["assumed"])
    chk = p["check"]
    assert chk["sample"] == 8 and chk["state_probe"] == {"requests": 8, "prompt_len": 256, "new_tokens": 768}
    assert 0 < chk["limit"] < 0.5 and 0 < chk["limit_state_rel"] < 0.1
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("4k-16k", "KDA state", "experts", "6 x"))
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("phi-4-mini-flash-reasoning") and cells.index(CELL) > cells.index("rollout-phi-4-mini-flash-longctx-grpo")  # appended after what was there


def test_the_parents_entries_are_all_still_there_and_only_appended_to():
    """Against the parent commit's BENCHMARK.json where git has it: every
    entry it had is there unchanged but for this cell's name appended to
    ``workloads`` lists, in the parent's order; no file the parent's
    benchmark had is edited."""
    import subprocess

    b = bench()
    parent = "c606b4c4877ba4c935d437292b6c9150c7a120e8"
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
                # the parent's cells first, in its order; what follows is this PR's cell or a later PR's (a later one may
                # append to a list this cell is not in: nothing here says that the tail holds this cell's name)
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
    assert cfg["reduced_from"] == {"num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)), "n_routed_experts": 320, "vocab_size": 196608}
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (8, [0, 4], 20, 24576)
    if os.path.isfile(CATALOG):  # key for key the catalog row's config, but what ``reduced`` names; no width among those
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
        assert row["source_url"] == SOURCE and set(row["config"]) <= set(cfg)
        assert {k: cfg[k] for k in row["config"] if k not in REDUCED} == {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert cfg["reduced_from"] == {k: row["config"][k] for k in REDUCED}
    assumed = cfg["assumed"]
    assert (assumed["router_experts"], assumed["expert_first"], assumed["kda_state_dtype"], assumed["conv_state_dtype"]) == (320, 0, "float32", "bfloat16")
    assert (assumed["initializer_range"], assumed["router_balance_tokens"]) == (0.02, 16384) and 0 < assumed["expert_own_share"] <= 1
    assert set(assumed) == set(cfg["assumed_notes"])  # every assumption with its reason
    for form in ("block_form", "gqa_gate_form", "kda_form", "router_form", "kda_init"):
        assert form in assumed
    assert all(s in cfg["stands_for"] for s in ("96", "6 pipeline stages", "16 chips sharing EACH layer", "rank 0", "Left out", "6 x"))
    # the program reads the file through the cell's kind: the published keys and the assumed ones that are its own
    load_run()
    from benchlib.cells import rollout_family

    mcfg = rollout_family.model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert mcfg.layer_types == ("attention", "kda", "kda", "kda") * 2 and (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (20, 320, 0)
    assert mcfg.state_shapes(64)["kda"][0] == (6, 64, 64, 128, 128) and mcfg.state_shapes(64)["conv"][0] == (6, 64, 3 * 24576)


def test_the_roofline_readers_bytes_of_an_update_and_its_silence_on_another_program():
    """2 x 4,194,304 B a (slot, layer) update at the published sizes, by hand
    against a synthetic trace's seconds; on a program without the counter or
    the scope (the parent's, any other family's) the reader returns nothing
    and does not raise."""
    b = bench()
    reader = b.reader("kda_state_roofline")
    cfg = b.cell(CELL)["model"]
    assert reader.state_bytes(cfg) == 64 * 128 * 128 * 4 == 4_194_304
    assert reader.state_bytes({**cfg, "assumed": {**cfg["assumed"], "kda_state_dtype": "bfloat16"}}) == 2_097_152
    metric = b.layer_metric("decode_kda_state_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    recorded = {"trace": trace_reduce.load(path), "xplane": path}
    stand_in = {**metric, "scopes": ["mlp"]}  # the recorded Qwen program has no kda_state: ``mlp`` stands in for it
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], metric["within_module"])
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]
    counters = {"areal_decode_kda_state_updates_total": 5 * 32 * 6 * 18.0, "areal_decode_chunks_total": 5.0}
    facts = {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "status": ({}, {})}}
    want = 100.0 * (32 * 6 * 18.0 * runs * 2 * 4_194_304 / 819e9) / secs
    assert reader.read(stand_in, facts) == pytest.approx(want, rel=1e-9) and want > 0
    assert reader.read(metric, facts) is None  # no such scope in that program
    assert reader.read(stand_in, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None  # a program without the counter
    assert reader.read(stand_in, {**facts, "extra": None}) is None and reader.read(stand_in, {**facts, "trace": None}) is None
    assert reader.read(stand_in, {**facts, "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # another family's cell
    assert reader.read(stand_in, {**facts, "cfg": b.cell("rollout-olmo-hybrid-7b-d16-grpo")["model"]}) is None
    named = b.reader("scope_share_named")
    for name in ("decode_kda_pct", "prefill_kda_pct"):
        assert named.read(b.layer_metric(name), recorded) is None  # the recorded program has no such scope
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-kda"), recorded) == pytest.approx(old)
