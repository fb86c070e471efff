"""What PR 43 adds to the benchmark as files: the configuration
``phi-4-mini-flash-reasoning`` (the published ``config.json`` of
Phi-4-mini-flash-reasoning, nothing reduced), the cell
``rollout-phi-4-mini-flash-longctx-grpo`` of the kind ``rollout_family_probe``
on the traffic ``grpo-long-context`` as it was, six per-layer metrics and one
new reader (the shared K/V rows' bytes against the device time of the layers
that read them). Every assertion is about THIS cell's own entries and their
order: none counts a list, takes an entry by its place, or says that a list
holds one name."""

import json
import os

import pytest
from chipbench_phi4flash_util import CELL, CONFIG
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
NEW = [
    "decode_cross_attn_pct", "decode_window_attn_pct", "decode_gmu_pct", "prefill_window_attn_pct", "decode_shared_kv_roofline",
    "scope_coverage_pct.rollout-sambay",
]
SHARED = {"decode_ssm_pct", "prefill_ssm_pct"}  # the selective scan reuses the state-space mixer's scopes
SAMBAY = ["attn_window", "attn_cross", "attn_diff", "gmu"]
COUNTERS = [
    "areal_decode_shared_kv_tokens_read_total", "areal_decode_window_tokens_read_total", "areal_decode_s6_state_updates_total",
    "areal_decode_prefill_last_token_rows_total",
]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-long-context" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family_probe" and callable(b.cell_kind("rollout_family_probe").run) and p["dtype"] == "bfloat16"
    assert (p["clients"], p["warm_seconds"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 10, 8, 4)
    server = dict(p["server"])
    assert 2.5 <= server.pop("kv_hbm_gb") <= 4.0
    assert server == {"slots": 64, "max_seq_len": 20480, "page_size": 128, "decode_steps": 32, "attn_window_step": 20480}
    mix = cell["traffic"]
    assert mix == b.cell("rollout-glm-5-ep16-d6-longctx-grpo")["traffic"]  # the file as it was: cell 8's
    assert (mix["generator"], mix["group_size"], mix["max_total"]) == ("grpo_groups", 8, 19456) and mix["prompt_len"]["lo"] >= 8 * cell["model"]["sliding_window"]  # every request is windows past the window at its first step
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and SHARED <= set(per_layer)  # this PR's six, in this order
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert set(per_layer) == qwen | set(NEW) | SHARED
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    for m in b.doc["per_layer"]:
        if m["name"] in NEW:  # the entry and the metric's file say the same, and the cell is the one this PR adds
            lm = b.layer_metric(m["name"])
            assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves")) and m["workloads"] == [CELL]
            assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        elif CELL in m.get("workloads", []):  # an older metric: this cell's name was appended, nothing put before it
            others = [w for w in m["workloads"] if w != CELL]
            assert m["workloads"].index(CELL) > max(m["workloads"].index(w) for w in others if w in ("rollout-glm-5-ep16-d6-longctx-grpo", "rollout-granite-h-micro-grpo"))
    for m in b.doc["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tok_s":
            assert CELL in m["workloads"] and m["workloads"].index(CELL) > m["workloads"].index("rollout-glm-5-ep16-d6-longctx-grpo")
    cross, window, gmu, pre = (b.layer_metric(n) for n in NEW[:4])
    assert (cross["scopes"], window["scopes"], gmu["scopes"], pre["scopes"]) == (["attn", "attn_cross"], ["attn_window"], ["gmu"], ["attn_window"])
    assert all(m["moves"] == "tpot_p95_ms" and m["within_module"] == "^jit_chunk\\(" and m["reader"] == "scope_share_named" for m in (cross, window, gmu))
    assert pre["moves"] == "ttft_p50_ms" and pre["within_module"] == "^jit_prefill\\(" and "attn alone" in cross["note"]
    roof = b.layer_metric("decode_shared_kv_roofline")
    assert (roof["reader"], roof["scopes"], roof["tokens_counter"], roof["chunks_counter"]) == ("shared_kv_roofline", ["attn", "attn_cross"], COUNTERS[0], "areal_decode_chunks_total")
    cover = b.layer_metric("scope_coverage_pct.rollout-sambay")
    assert set(cover["scopes"]) == set(b.layer_metric("scope_coverage_pct.rollout-hybrid")["scopes"]) | set(SAMBAY) and cover["base"] == "busy"
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert fam["state"] == {"leaf": "ssm", "dtype_key": "ssm_state_dtype", "control_dtype": "bfloat16"}
    assert "server" not in fam["control"] and {"w_gate_up", "w_down", "in_proj", "out_proj", "wq", "wk", "wv", "wo", "gmu_in", "gmu_out"} == set(fam["control"]["round_int8"])
    assert fam["counters"][:4] == COUNTERS and {"areal_decode_chunks_total", "areal_decode_state_copies_total", "areal_decode_state_prefills_total"} <= set(fam["counters"])
    assert fam["status"] == ["kv_pools"] and set(fam["not_the_programs"]) <= set(cell["model"]["assumed"])
    chk = p["check"]
    assert chk["sample"] == 8 and chk["state_probe"] == {"requests": 8, "prompt_len": 256, "new_tokens": 768}
    assert 0 < chk["limit"] < 0.2 and 0 < chk["limit_state_rel"] < 0.1
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("4k-16k", "8 layers read one layer's pages", "rings", "17 of 32"))
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("glm-5-ep16-d6") and cells.index(CELL) > cells.index("rollout-glm-5-ep16-d6-longctx-grpo")  # appended after what was there


def test_the_parents_entries_are_all_still_there_and_only_appended_to():
    """Against the parent commit's BENCHMARK.json where git has it: every
    entry it had is there unchanged but for this cell's name appended to
    ``workloads`` lists, in the parent's order; no file the parent's
    benchmark had is edited."""
    import subprocess

    b = bench()
    parent = "85010f8aa0bf3696a92fa2d2c12bb6bafad5d18b"
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
                assert now["workloads"][: len(was["workloads"])] == was["workloads"] and CELL in now["workloads"][len(was["workloads"]) :]
            else:
                assert now == was
    assert [c["name"] for c in new["configs"] if c["name"] not in {x["name"] for x in old["configs"]}][0] == CONFIG
    assert [w["name"] for w in new["workloads"] if w["name"] not in {x["name"] for x in old["workloads"]}][0] == CELL
    added = [m["name"] for m in new["per_layer"] if m["name"] not in {x["name"] for x in old["per_layer"]}]
    assert added[: len(NEW)] == NEW
    # under the benchmark's paths this PR's own files were added; none the parent had was modified or deleted (a later PR may add more)
    mine = [line.split("\t") for line in changed if line and any(s in line for s in ("phi4flash", "phi-4-mini-flash", "shared_kv_roofline", "sambay", "decode_cross_attn", "decode_window_attn", "decode_gmu", "prefill_window_attn"))]
    assert all(status == "A" for status, _ in mine)  # (git lists them once they are staged or committed)


def test_configuration_file_is_the_published_config_with_nothing_reduced():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == [] and cfg["source"] == entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064,
    }
    assert {k: cfg[k] for k in published} == published
    if os.path.isfile(CATALOG):  # key for key the catalog row's config
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["source_url"] == SOURCE and {k: cfg[k] for k in row["config"]} == row["config"]
    assumed = cfg["assumed"]
    assert (assumed["head_dim"], assumed["mamba_d_state"], assumed["mamba_d_conv"], assumed["mamba_expand"], assumed["mamba_dt_rank"]) == (64, 16, 4, 2, 160)
    assert assumed["attn_bias"] is True and assumed["mamba_conv_bias"] is True and assumed["mamba_proj_bias"] is False
    assert (assumed["ssm_state_dtype"], assumed["conv_state_dtype"]) == ("float32", "bfloat16")
    assert set(assumed) - {"layer_pattern", "mamba_conv_bias", "mamba_proj_bias", "mamba_expand", "conv_state_dtype", "window_counts_own_token", "positional_embedding"} <= set(cfg["assumed_notes"])
    assert "one chip" in cfg["stands_for"] and "200,064" in cfg["stands_for"]


def test_the_roofline_readers_cost_of_a_token_and_its_silence_on_another_program():
    """5,120 B and 15,360 operations a (cached token, reading layer) at the
    published sizes: memory binds by two orders. On a program without the
    counter (the parent's, any other family's) the reader returns nothing and
    does not raise."""
    b = bench()
    reader = b.reader("shared_kv_roofline")
    with open(os.path.join(b.root, b.config_entry(CONFIG)["file"])) as f:
        cfg = json.load(f)
    ops, byts = reader.token_layer_cost(cfg)
    assert (ops, byts) == (2 * 40 * 64 + 2 * 40 * 128, 2 * 20 * 64 * 2) == (15360, 5120)
    assert byts / 819e9 > 50 * ops / 197e12
    metric = b.layer_metric("decode_shared_kv_roofline")
    assert reader.read(metric, {"trace": None, "cfg": cfg}) is None
    load_run()
    from benchlib import trace_reduce  # noqa: F401  (the reader's imports resolve)

    class _Trace:
        devices = [object()]

    none_moved = {"trace": _Trace(), "cfg": cfg, "extra": {"trace_counters": {"areal_decode_chunks_total": 9.0}}, "device_kind": "TPU v5 lite"}
    assert reader.read(metric, none_moved) is None
    assert reader.read(metric, {**none_moved, "cfg": {"model_type": "qwen2"}}) is None
