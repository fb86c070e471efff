"""What PR 58 adds to the benchmark as files: the configuration
``sdar-30b-a3b-d7`` (the published ``config.json`` of SDAR-30B-A3B-Chat cut
to its first seven layers and nothing else), the cell
``rollout-sdar-30b-a3b-d7-block4-grpo`` of the new kind
``rollout_family_trace`` on the traffic ``grpo-reasoning`` as it was, five
per-layer metrics and one new reader (the in-block attention launch against
the tokens it fetched). Every assertion is about THIS cell's own entries and
their order: none counts a list, takes an entry by its place, or says that a
list holds one name."""

import json
import os

import pytest
from chipbench_sdar_util import CELL, CONFIG, PARAMS
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
NEW = ["block_passes_per_token_pct", "block_commit_pass_pct", "decode_block_select_pct", "scope_coverage_pct.rollout-block", "decode_block_attn_roofline"]
MOE = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean"}  # the expert block's, as cells 5-12
PASSES = ["areal_decode_block_denoise_passes_total", "areal_decode_block_commit_passes_total"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-reasoning" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family_trace" and callable(b.cell_kind("rollout_family_trace").run) and p["dtype"] == "bfloat16"
    assert (p["clients"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 8, 4) and p["warm_seconds"] >= 10
    assert p["server"] == {"slots": 64, "max_seq_len": 4096, "page_size": 128, "kv_hbm_gb": 3.0, "decode_steps": 32, "attn_window_step": 4096}
    mix = cell["traffic"]
    assert mix == b.cell("rollout-7b-d14-grpo")["traffic"]  # the file as it was: cell 2's
    assert (mix["generator"], mix["group_size"], mix["max_total"], mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == ("grpo_groups", 8, 4000, 128, 1024)
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and MOE <= set(per_layer)  # this PR's five, in this order
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA decode kernel's roofline and its coverage
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
            before = [w for w in m["workloads"] if w in ("rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo", "rollout-kanana-2-30b-a3b-ep8-grpo", "rollout-granite-h-micro-grpo")]
            assert all(m["workloads"].index(CELL) > m["workloads"].index(w) for w in before)
    for m in b.doc["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            assert CELL in m["workloads"] and m["workloads"].index(CELL) > m["workloads"].index("rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo")
    per_token, commit, select, cover, roof = (b.layer_metric(n) for n in NEW)
    assert (per_token["reader"], per_token["part"], per_token["whole"], per_token["moves"]) == ("extra_counter_share", PASSES, ["areal_decode_generated_tokens_total"], "tpot_p95_ms")
    assert (commit["reader"], commit["part"], commit["whole"]) == ("extra_counter_share", PASSES[1:], PASSES)
    assert (select["reader"], select["scopes"], select["moves"], select["within_module"]) == ("scope_share_named", ["block_select"], "tpot_p95_ms", "^jit_chunk\\(")
    assert set(cover["scopes"]) == set(b.layer_metric("scope_coverage_pct.rollout-moe")["scopes"]) | {"block_select"} and cover["base"] == "busy"
    assert (roof["reader"], roof["scopes"], roof["op_pattern"], roof["layer"], roof["moves"], roof["tokens_counter"], roof["within_module"]) == (
        "block_attn_roofline", ["attn"], "paged_suffix_attn", "kernels", "tpot_p95_ms", "areal_decode_block_attn_tokens_read_total", "^jit_chunk\\(")
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert "server" not in fam["control"] and set(fam["control"]["round_int8"]) == {"wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down"}
    # the counters the two shares read are named, beside the expert counters the moe readers take
    assert set(PASSES) | {"areal_decode_generated_tokens_total", "areal_decode_block_attn_tokens_read_total", "areal_decode_blocks_total"} <= set(fam["counters"])
    assert {"areal_decode_moe_experts_touched_total", "areal_decode_moe_assignments_total", "areal_decode_chunks_total"} <= set(fam["counters"])
    assert fam["status"] == ["moe"] and set(fam["not_the_programs"]) == {"initializer_range", "expert_own_share"} <= set(cell["model"]["assumed"])
    chk = p["check"]
    assert chk["sample"] >= 6 and 0 < chk["limit"] < 0.5 and 0 < chk["limit_trace"] < 0.5
    probe = chk["trace_probe"]
    assert probe["rules"] == ["sequential", "low_confidence_static", "low_confidence_dynamic"] and probe["requests"] % 4 == 0 and probe["denoising_steps"] == 2
    assert probe["prompt_len"] + 3 + probe["new_tokens"] <= probe["pad_to"] <= p["server"]["max_seq_len"] and probe["pad_to"] % 4 == 0
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("8 GRPO", "64 slots", "block pass", "2 denoise + 1 commit", "128 experts", "7 of 48", "6.9x"))
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("xing4.0-29b-a4b-ep4-d10") and cells.index(CELL) > cells.index("rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo")  # appended after what was there
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
    parent = "5a964fd354059524ff2317ca47b914292df894e8"
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
                assert now["workloads"][: len(was["workloads"])] == was["workloads"] and now["workloads"][len(was["workloads"])] == CELL
            else:
                assert now == was
    assert [c["name"] for c in new["configs"] if c["name"] not in {x["name"] for x in old["configs"]}][0] == CONFIG
    assert [w["name"] for w in new["workloads"] if w["name"] not in {x["name"] for x in old["workloads"]}][0] == CELL
    added = [m["name"] for m in new["per_layer"] if m["name"] not in {x["name"] for x in old["per_layer"]}]
    assert added[: len(NEW)] == NEW
    # under the benchmark's paths nothing the parent had was modified or deleted (a later PR may add more)
    assert all(line.split("\t")[0] == "A" for line in changed if line)  # (git lists them once they are staged or committed)


def test_configuration_file_is_the_published_config_cut_in_depth_alone():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"] and cfg["source"] == entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and cfg["reduced_from"] == {"num_hidden_layers": 48}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_experts_per_tok"], cfg["vocab_size"], cfg["max_window_layers"]) == (7, 128, 8, 151936, 48)
    if os.path.isfile(CATALOG):  # key for key the catalog row's config, but the depth; no width among what is reduced
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == SOURCE and set(row["config"]) <= set(cfg)
        assert {k: cfg[k] for k in row["config"] if k != "num_hidden_layers"} == {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 48
        assert set(cfg) - set(row["config"]) == {"source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for"}
    assumed = cfg["assumed"]
    assert (assumed["qk_norm"], assumed["block_length"], assumed["mask_token_id"], assumed["denoising_steps"]) == (True, 4, 151669, 2)
    assert (assumed["remasking_strategy"], assumed["confidence_threshold"], assumed["initializer_range"], assumed["expert_own_share"]) == ("sequential", 0.9, 0.02, 0.25)
    # every assumption with its reason; three more notes for what the program reads no key for
    assert set(cfg["assumed_notes"]) == set(assumed) | {"logits_at_own_position", "blocks_by_absolute_position", "commit_pass"}
    assert all(s in cfg["stands_for"] for s in ("first of seven pipeline stages", "128 experts", "whole vocabulary", "4,984,176,384", "6.9 x"))
    # the parameter count by hand: a layer's attention, router, norms and experts; embedding, head and the last norm
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    layer = attn + 2048 * 128 + (2 * 2048 + 2 * 128) + 128 * 3 * 2048 * 768
    assert (attn, layer) == (18_874_368, 623_120_640) and 7 * layer + 2 * 151936 * 2048 + 2048 == PARAMS
    load_run()
    from benchlib import sdar_weights
    from benchlib.cells import rollout_family

    assert sdar_weights.param_count(cfg) == PARAMS and round(PARAMS * 2 / 1e9, 3) == 9.968
    # the program reads the file through the cell's kind: the published keys and the assumed ones that are its own
    mcfg = rollout_family.model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert (mcfg.num_layers, mcfg.num_experts, mcfg.num_experts_per_tok, mcfg.moe_intermediate_size, mcfg.qk_norm, mcfg.attention_bias) == (7, 128, 8, 768, True, False)
    assert (mcfg.block_length, mcfg.mask_token_id, mcfg.denoising_steps, mcfg.remasking_strategy, mcfg.confidence_threshold) == (4, 151669, 2, "sequential", 0.9)
    assert not mcfg.tie_word_embeddings and mcfg.dtype == "bfloat16" and mcfg.kv_pools == {"k": (4, 128), "v": (4, 128)}
    assert mcfg.moe_count_shapes == {"moe_load": (7, 128), "moe_touched": (7,), "moe_streamed": (7,)}
    assert list(mcfg.count_shapes)[:4] == ["blk_denoise_passes", "blk_commit_passes", "blk_blocks", "blk_attn_tokens_read"]
    # a token leaves 14,336 B over seven layers; 3 GB hold 1,755 pages of 128 = 224.6k tokens, over the traffic's worst case
    from areal_tpu.inference import paged_kv

    assert 7 * 2 * 4 * 128 * 2 == 14336 and paged_kv.n_pages_for_budget(int(3.0 * 2**30), 7, 4, 128, 128, 2, pools=mcfg.kv_pools) * 128 > 8 * 1024 + 64 * 3072 == 204800


def _recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_block_attention_reader_counts_what_the_launch_fetched_against_its_own_seconds():
    """2,048 B and 65,536 operations a fetched token and layer at the published
    sizes, by hand; the share is those bytes over the chip's bytes a second
    against the device seconds of the launch alone (the ops whose path names
    the kernel), or of every op under the scope where none does; silence on a
    program without the counter or the scope (the parent's, any other
    family's)."""
    b = bench()
    reader = b.reader("block_attn_roofline")
    cfg = b.cell(CELL)["model"]
    assert reader.token_layer_cost(cfg) == (2 * 2 * 4 * 32 * 128, 2 * 4 * 128 * 2) == (65536, 2048)
    assert 2048 / 819e9 > 65536 / 197e12  # bytes bind: 2.5 ns against 0.33 ns a token and layer
    metric = b.layer_metric("decode_block_attn_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    recorded = _recorded()
    # the recorded Qwen trace's decode launch stands in for the in-block launch: its ops are named by the kernel's name under ``attn``
    stand_in = {**metric, "op_pattern": "paged_decode_attn"}
    secs, how = reader.launch_seconds(recorded, ["attn"], "paged_decode_attn", metric["within_module"])
    whole, how_whole = reader.launch_seconds(recorded, ["attn"], "no_such_kernel", metric["within_module"])
    assert how == "kernel" and how_whole == "scope" and 0 < secs < whole  # the launch alone; else every op under the scope
    _, by_scope = b.reader("scope_share_named").scope_seconds(recorded, ["attn"], metric["within_module"])
    assert whole == pytest.approx(by_scope)
    import re
    import statistics

    runs = [d for n, _, d in recorded["trace"].devices[0].modules if re.search(metric["steps_module_pattern"], n)]
    n_calls = sum(runs) / statistics.median(runs)
    at_peak = secs * 819e9 / (2048 * 7 * n_calls)  # tokens a chunk a launch at the chip's bytes a second would have fetched in those seconds
    facts = lambda tokens, chunks=1.0: {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {  # noqa: E731
        "trace_counters": {metric["tokens_counter"]: tokens, metric["chunks_counter"]: chunks}, "status": ({}, {})}}
    assert reader.read(stand_in, facts(at_peak)) == pytest.approx(100.0, rel=1e-9)
    assert reader.read(stand_in, facts(at_peak, 2.0)) == pytest.approx(50.0, rel=1e-9)  # half the tokens a chunk
    assert reader.read(metric, facts(at_peak)) == pytest.approx(100.0 * secs / whole, rel=1e-9)  # no op named paged_suffix_attn there: the scope's seconds
    assert reader.read(stand_in, facts(0.0)) is None  # a program without the counter
    assert reader.read({**stand_in, "scopes": ["block_select"]}, facts(at_peak)) is None  # no such scope in that program
    assert reader.read(stand_in, {**facts(at_peak), "extra": None}) is None and reader.read(stand_in, {**facts(at_peak), "trace": None}) is None
    assert reader.read(stand_in, {**facts(at_peak), "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # a cell whose model has no blocks
    named = b.reader("scope_share_named")
    assert named.read(b.layer_metric("decode_block_select_pct"), recorded) is None
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-block"), recorded) == pytest.approx(old)
    shares = b.reader("extra_counter_share")
    window = {"extra": {"window_counters": {PASSES[0]: 200.0, PASSES[1]: 100.0, "areal_decode_generated_tokens_total": 400.0}}}
    assert shares.read(b.layer_metric("block_passes_per_token_pct"), window) == 75.0 and shares.read(b.layer_metric("block_commit_pass_pct"), window) == pytest.approx(100 / 3)
    assert shares.read(b.layer_metric("block_passes_per_token_pct"), {"extra": {"window_counters": {}}}) is None
