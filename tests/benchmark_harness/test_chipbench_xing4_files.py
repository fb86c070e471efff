"""What PR 53 adds to the benchmark as files: the configuration
``xing4.0-29b-a4b-ep4-d10`` (the published ``config.json`` of Xing4.0-29B-A4B
cut to its first ten layers, 16 of 64 experts and a quarter of the
vocabulary, its prediction layer absent), the cell
``rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo`` of the kind ``rollout_family``
on the traffic ``grpo-long-context`` as it was, four per-layer metrics and one
new reader (the streams' bytes a prompt pass against the device time of the
five ``mhc_*`` scopes). Every assertion is about THIS cell's own entries and
their order: none counts a list, takes an entry by its place, or says that a
list holds one name."""

import json
import os

import pytest
from chipbench_util import CHIP, bench, load_run
from chipbench_xing4_util import CELL, CONFIG

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
NEW = ["decode_mhc_pct", "prefill_mhc_pct", "prefill_mhc_stream_roofline", "scope_coverage_pct.rollout-mhc"]
MOE = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean", "decode_moe_shared_pct"}  # the expert block's, as cells 5-11
MLA = {"decode_mla_pct", "prefill_mla_pct", "decode_mla_cache_roofline"}  # the latent block's, as cell 7
MHC = ["mhc_coeff", "mhc_sinkhorn", "mhc_pre", "mhc_post", "mhc_merge"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-long-context" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family" and callable(b.cell_kind("rollout_family").run) and p["dtype"] == "bfloat16"
    assert (p["clients"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 8, 4) and p["warm_seconds"] >= 10
    assert p["server"] == {"slots": 64, "max_seq_len": 20480, "page_size": 128, "kv_hbm_gb": 4.5, "decode_steps": 32, "attn_window_step": 20480}
    mix = cell["traffic"]
    assert mix == b.cell("rollout-glm-5-ep16-d6-longctx-grpo")["traffic"]  # the file as it was: cell 8's
    assert (mix["generator"], mix["group_size"], mix["max_total"], mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == ("grpo_groups", 8, 19456, 4096, 16384)
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and MOE | MLA <= set(per_layer)  # this PR's four, in this order
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert set(per_layer) == qwen | set(NEW) | MOE | MLA
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    for m in b.doc["per_layer"]:
        if m["name"] in NEW:  # the entry and the metric's file say the same, and the cell is the one this PR adds
            lm = b.layer_metric(m["name"])
            assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves")) and m["workloads"][0] == CELL
            assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        elif CELL in m.get("workloads", []):  # an older metric: this cell's name was appended, nothing put before it
            before = [w for w in m["workloads"] if w in ("rollout-command-a-plus-ep16-d4-longctx-grpo", "rollout-kanana-2-30b-a3b-ep8-grpo", "rollout-granite-h-micro-grpo")]
            assert all(m["workloads"].index(CELL) > m["workloads"].index(w) for w in before)
    for m in b.doc["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            assert CELL in m["workloads"] and m["workloads"].index(CELL) > m["workloads"].index("rollout-command-a-plus-ep16-d4-longctx-grpo")
    decode, prefill, streams, cover = (b.layer_metric(n) for n in NEW)
    assert (decode["reader"], decode["scopes"], decode["moves"], decode["within_module"]) == ("scope_share_named", MHC, "tpot_p95_ms", "^jit_chunk\\(")
    assert (prefill["reader"], prefill["scopes"], prefill["moves"], prefill["within_module"]) == ("scope_share_named", MHC, "ttft_p50_ms", "^jit_prefill\\(")
    assert (streams["reader"], streams["scopes"], streams["layer"], streams["moves"], streams["mixes_counter"], streams["within_module"]) == (
        "mhc_stream_roofline", MHC, "kernels", "ttft_p50_ms", "areal_prefill_mhc_token_sublayers_total", "^jit_prefill\\(")
    assert set(cover["scopes"]) == set(b.layer_metric("scope_coverage_pct.rollout-mla")["scopes"]) | {"mla_q_lora"} | set(MHC) and cover["base"] == "busy"
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert "state" not in fam and "server" not in fam["control"]  # no recurrent state to probe; no int8 latent pages
    assert {"w_qa", "w_qb", "w_kva", "w_kvb", "wo", "w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"} == set(fam["control"]["round_int8"])
    assert not [leaf for leaf in fam["control"]["round_int8"] if leaf.startswith("hc_")]  # Phi and the coefficients as they are
    assert fam["counters"][:3] == ["areal_decode_latent_tokens_read_total", "areal_decode_mhc_row_sublayers_total", "areal_prefill_mhc_token_sublayers_total"] and {
        "areal_decode_moe_experts_touched_total", "areal_decode_moe_assignments_total", "areal_decode_chunks_total"} <= set(fam["counters"])
    assert fam["status"] == ["moe"] and set(fam["not_the_programs"]) <= set(cell["model"]["assumed"])
    chk = p["check"]
    assert chk["sample"] == 8 and 0 < chk["limit"] < 0.5 and "state_probe" not in chk
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("4k-16k", "latent", "no index", "streams", "experts 1/4", "4x"))
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("command-a-plus-ep16-d4") and cells.index(CELL) > cells.index("rollout-command-a-plus-ep16-d4-longctx-grpo")  # appended after what was there
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
    parent = "1585a99cc403889d566af6085f4f67d70034ca4f"
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
    assert cfg["reduced_from"] == {"num_hidden_layers": 40, "n_routed_experts": 64, "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"], cfg["num_nextn_predict_layers"], cfg["first_k_dense_replace"]) == (10, 16, 32768, 0, 2)
    if os.path.isfile(CATALOG):  # key for key the catalog row's config, but what ``reduced`` names; no width among those
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
        assert row["source_url"] == SOURCE and set(row["config"]) <= set(cfg)
        assert {k: cfg[k] for k in row["config"] if k not in REDUCED} == {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert cfg["reduced_from"] == {k: row["config"][k] for k in REDUCED}
        assert set(cfg) - set(row["config"]) == {"source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for"}
    assumed = cfg["assumed"]
    assert (assumed["router_experts"], assumed["expert_first"], assumed["latent_row_lanes"], assumed["latent_page_dtype"]) == (64, 0, 640, "bfloat16")
    assert (assumed["stream_init"], assumed["stream_merge"], assumed["hc_per_sublayer"], assumed["hc_norm_weight"], assumed["hc_eps_in"], assumed["hc_coeff_dtype"]) == (
        "embedding_copied", "sum", True, "none", "both_denominators", "float32")
    assert (assumed["rope_interleave"], assumed["yarn_form"], assumed["initializer_range"], assumed["hc_seeded"]) == (True, "deepseek_v3", 0.02, True)
    assert 0 < assumed["expert_own_share"] <= 1 and assumed["router_balance_tokens"] == 4096
    assert set(assumed) == set(cfg["assumed_notes"])  # every assumption with its reason
    for key in ("stream_init", "stream_merge", "hc_per_sublayer", "hc_norm_weight", "hc_eps_in", "yarn_form"):
        assert "other reading" in cfg["assumed_notes"][key], key  # ... and the reading it was chosen over
    assert all(s in cfg["stands_for"] for s in ("v5e-16", "4 pipeline stages", "4 chips share EACH layer", "rank 0", "Left out", "4 x", "multi-token-prediction"))
    # the program reads the file through the cell's kind: the published keys and the assumed ones that are its own
    load_run()
    from benchlib.cells import rollout_family

    mcfg = rollout_family.model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert mcfg.layer_types == ("mla",) * 10 and mcfg.ffns == ("dense",) * 2 + ("moe",) * 8
    assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first, mcfg.num_experts_per_tok) == (16, 64, 0, 4)
    assert (mcfg.residual_form, mcfg.hc_mult, mcfg.hc_sinkhorn_iters, mcfg.stream_width, mcfg.kv_pools) == ("mhc", 4, 20, 14336, {"k": (1, 640)})
    # a token leaves 1,280 B a layer, 12,800 B over ten; a page of 128 tokens 1.64 MB; 4.5 GiB hold 2,949 of them = 377k tokens
    assert 10 * 640 * 2 == 12800 and int(4.5 * 2**30) // (128 * 12800) == 2949 and 2949 * 128 > 8 * 16384 + 64 * 3072 == 327680


def _recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_stream_reader_counts_the_least_bytes_whatever_moves_them():
    """86,016 B and 0.86 M operations a (token, sublayer) mix at the published
    sizes, by hand; the share is the LEAST bytes over the chip's bytes a second
    against the scopes' device seconds: an implementation that moves twice the
    least bytes in twice the time reads 50, not 100; silence on a program
    without the counter or the scopes (the parent's, any other family's)."""
    b = bench()
    reader = b.reader("mhc_stream_roofline")
    cfg = b.cell(CELL)["model"]
    assert reader.mix_cost(cfg) == (2 * 4 * 3584 * 24 + 2 * 4 * 6 * 3584, 12 * 3584 * 2) == (860160, 86016)
    metric = b.layer_metric("prefill_mhc_stream_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    recorded = _recorded()
    stand_in = {**metric, "scopes": ["mlp"], "within_module": "^jit_chunk\\("}  # the recorded Qwen trace's chunk programs stand in for a prompt program
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], stand_in["within_module"])
    at_peak = secs * 819e9 / 86016  # the mixes an implementation at the chip's bytes a second would have made in those seconds
    facts = lambda mixes: {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {"trace_counters": {metric["mixes_counter"]: mixes}, "status": ({}, {})}}  # noqa: E731
    assert reader.read(stand_in, facts(at_peak)) == pytest.approx(100.0, rel=1e-9)
    # the same seconds spent moving every stream twice over: the LEAST bytes are half of what moved
    assert reader.read(stand_in, facts(at_peak / 2)) == pytest.approx(50.0, rel=1e-9)
    # bytes bind: 86 KB take 105 ns at 819 GB/s, 0.86 M operations 4.4 ns at 197 TFLOP/s; a 16k prompt's 20 sublayers 28 GB = 34 ms
    assert 86016 / 819e9 > 20 * 860160 / 197e12 and round(16384 * 20 * 86016 / 1e9, 1) == 28.2 and round(16384 * 20 * 86016 / 819e9 * 1e3) == 34
    assert reader.read(metric, facts(at_peak)) is None  # no such scope in that program
    assert reader.read(stand_in, facts(0.0)) is None  # a program without the counter, or no prompt pass in the span
    assert reader.read(stand_in, {**facts(at_peak), "extra": None}) is None and reader.read(stand_in, {**facts(at_peak), "trace": None}) is None
    assert reader.read(stand_in, {**facts(at_peak), "cfg": b.cell("rollout-glm-5-ep16-d6-longctx-grpo")["model"]}) is None  # a cell with one residual vector
    named = b.reader("scope_share_named")
    assert named.read(b.layer_metric("decode_mhc_pct"), recorded) is None and named.read(b.layer_metric("prefill_mhc_pct"), recorded) is None
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-mhc"), recorded) == pytest.approx(old)
