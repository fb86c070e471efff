"""What PR 39 adds to the benchmark as files: the configuration
``glm-5-ep16-d6`` (the published ``config.json`` of GLM-5 cut to one chip's
share of a v5e-256: 6 layers of one pipeline stage, 16 of 256 experts, an
eighth of the vocabulary, no MTP layer), the traffic ``grpo-long-context``,
the cell ``rollout-glm-5-ep16-d6-longctx-grpo`` of the new kind
``rollout_family_select``, six per-layer metrics and two new readers. Every
assertion is about THIS cell's own entries and their order: none counts a
list, takes an entry by its place, or says that a list holds one name."""

import json
import os

import pytest
from chipbench_glm5_util import CELL, CONFIG
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["decode_dsa_pct", "prefill_dsa_pct", "decode_dsa_index_roofline", "decode_dsa_cache_roofline", "dsa_selected_pct", "scope_coverage_pct.rollout-dsa"]
SHARED = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean", "decode_mla_pct", "prefill_mla_pct"}
DSA = ["mla_q_lora", "dsa_index_proj", "dsa_index_score", "dsa_select"]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-long-context" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family_select" and callable(b.cell_kind("rollout_family_select").run)
    assert (p["clients"], p["warm_seconds"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 10, 8, 4)
    server = dict(p["server"])
    assert server.pop("kv_hbm_gb") in (2.5, 3.0, 3.5)
    # ONE decode window: every chunk program has the whole table (the launches visit the pages that hold tokens, whatever its width)
    assert server == {"slots": 64, "max_seq_len": 20480, "page_size": 128, "decode_steps": 32, "attn_window_step": 20480}
    mix = cell["traffic"]
    assert (mix["generator"], mix["group_size"], mix["max_total"]) == ("grpo_groups", 8, 19456)
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 4096, "hi": 16384}
    assert mix["output_len"] == b.traffic("grpo-reasoning")["output_len"] and mix["sampling"] == b.traffic("grpo-reasoning")["sampling"]
    assert mix["prompt_len"]["lo"] > cell["model"]["index_topk"]  # every request is past index_topk at its first decode step
    assert 8 * mix["prompt_len"]["hi"] + 64 * 3072 == 327_680  # the traffic's worst case in tokens, against the pool below
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in cell["per_layer"]]
    assert [n for n in per_layer if n in NEW] == NEW and SHARED <= set(per_layer)  # this PR's six, in this order
    assert "decode_mla_cache_roofline" not in per_layer  # its counter counts rows FETCHED: every cached row here, not the selected
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert set(per_layer) == qwen | set(NEW) | SHARED
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    for m in b.doc["per_layer"]:
        if m["name"] in NEW:  # the entry and the metric's file say the same, and the cell is the one this PR adds
            lm = b.layer_metric(m["name"])
            assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves")) and CELL in m["workloads"]
        elif CELL in m.get("workloads", []):  # an older metric: this cell's name was appended, nothing put before it
            assert m["workloads"].index(CELL) > m["workloads"].index("rollout-kanana-2-30b-a3b-ep8-grpo")
    assert b.layer_metric("decode_dsa_pct")["scopes"] == b.layer_metric("prefill_dsa_pct")["scopes"] == DSA
    assert b.layer_metric("decode_dsa_pct")["moves"] == "tpot_p95_ms" and b.layer_metric("prefill_dsa_pct")["moves"] == "ttft_p50_ms"
    idx, row = b.layer_metric("decode_dsa_index_roofline"), b.layer_metric("decode_dsa_cache_roofline")
    assert (idx["reader"], idx["what"], idx["scopes"], idx["tokens_counter"]) == ("dsa_roofline", "index_key", ["dsa_index_score"], "areal_decode_index_tokens_scored_total")
    assert (row["reader"], row["what"], row["scopes"], row["tokens_counter"]) == ("dsa_roofline", "latent_row", ["attn"], "areal_decode_latent_tokens_selected_total")
    share = b.layer_metric("dsa_selected_pct")
    assert (share["reader"], share["part"], share["whole"]) == ("extra_counter_share", ["areal_decode_latent_tokens_selected_total"], ["areal_decode_index_tokens_scored_total"])
    cover = set(b.layer_metric("scope_coverage_pct.rollout-dsa")["scopes"])
    assert cover == set(b.layer_metric("scope_coverage_pct.rollout-mla")["scopes"]) | set(DSA)
    fam = p["family"]
    for piece in ("weights", "reference"):
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert "server" not in fam["control"] and {"w_qa", "w_qb", "w_kva", "w_kvb", "wo", "we_gate", "ws_down"} <= set(fam["control"]["round_int8"])
    assert not {n for n in fam["control"]["round_int8"] if n.startswith("wi_")}  # the index's matrices as they are
    assert {"areal_decode_index_tokens_scored_total", "areal_decode_latent_tokens_selected_total", "areal_decode_latent_tokens_read_total", "areal_decode_chunks_total"} <= set(fam["counters"])
    assert fam["status"] == ["moe", "sparse_attention"]
    chk = p["check"]
    assert 0 < chk["limit"] and 0 < chk["limit_key_rel"] < 1 and 0.5 < chk["limit_selected_common"] < 1
    probe = chk["select_probe"]
    assert probe["prompt_len"] > cell["model"]["index_topk"] and probe["min_new_tokens"] < probe["new_tokens"]
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("4k-16k", "2,048", "16 of 256 experts", "1/16", "prefill pass holds"))
    entry = next(w for w in b.doc["workloads"] if w["name"] == CELL)
    assert b.doc["workloads"].index(entry) > b.doc["workloads"].index(b.workload_entry("rollout-kanana-2-30b-a3b-ep8-grpo"))  # appended after what was there


def test_the_parents_entries_are_all_still_there_and_only_appended_to():
    """Against the parent commit's BENCHMARK.json where git has it: every
    entry it had is there unchanged but for this cell's name appended to
    ``workloads`` lists, in the parent's order; what comes after them names
    this cell or this configuration."""
    import subprocess

    b = bench()
    try:
        old = json.loads(subprocess.run(["git", "show", "ef8144c1eb2d352f57e7bed7a1789517fa9e50ce:BENCHMARK.json"], cwd=b.root, capture_output=True, check=True, text=True).stdout)
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


def test_configuration_file_is_the_published_config_with_five_keys_reduced():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == REDUCED and cfg["source"] == entry["source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    assert cfg["reduced_from"] == {"num_hidden_layers": 78, "first_k_dense_replace": 3, "n_routed_experts": 256, "vocab_size": 154880, "num_nextn_predict_layers": 1}
    assert [cfg[k] for k in REDUCED] == [6, 1, 16, 19360, 0] and 19360 / 128 == 151.25 and 154880 // 8 == 19360
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_attention_heads"]) == (6144, 12288, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (2048, 512, 192, 64, 256)
    assert (cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"], cfg["indexer_rope_interleave"]) == (32, 128, 2048, True)
    assert (cfg["n_shared_experts"], cfg["num_experts_per_tok"], cfg["routed_scaling_factor"], cfg["n_group"], cfg["topk_group"]) == (1, 8, 2.5, 1, 1)
    a = cfg["assumed"]
    assert a == {
        "router_experts": 256, "expert_first": 0, "latent_row_lanes": 640, "latent_page_dtype": "bfloat16", "index_key_dtype": "bfloat16",
        "index_key_lanes": 128, "index_norm_eps": 1e-6, "initializer_range": 0.02, "attn_query_gain": 4.0, "expert_own_share": 0.25,
        "router_balance_tokens": 4096,
    }
    assert set(a) == set(cfg["assumed_notes"])  # every assumed key has its note
    for words in ("v5e-256", "13 pipeline stages of 6 layers", "16 chips share EACH layer", "rank 0 of the first stage", "experts 0-15 of 256", "rows 0-19,359", "exchange", "MTP"):
        assert words in cfg["stands_for"], words
    kind = b.cell_kind("rollout_family")
    fam = b.cell(CELL)["params"]["family"]
    assert fam["not_the_programs"] == ["initializer_range", "attn_query_gain", "expert_own_share", "router_balance_tokens", "latent_page_dtype", "index_key_dtype", "index_key_lanes"]
    mcfg = kind.model_config(cfg, fam, "bfloat16")  # the program builds the family from it, at the published widths
    assert (mcfg.model_type, mcfg.count("mla"), mcfg.num_moe_layers, mcfg.num_heads, mcfg.vocab_size, mcfg.tie_word_embeddings) == ("glm_moe_dsa", 6, 5, 64, 19360, False)
    assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first, mcfg.num_experts_per_tok, mcfg.moe_shared_intermediate_size) == (16, 256, 0, 8, 2048)
    assert (mcfg.q_lora_rank, mcfg.latent_dim, mcfg.latent_lanes, mcfg.sm_scale, mcfg.rope_theta) == (2048, 576, 640, 256**-0.5, 1e6)
    assert mcfg.kv_pools == {"k": (1, 640), "idx": (1, 128)} and (mcfg.index_n_heads, mcfg.index_topk, mcfg.index_norm_eps) == (32, 2048, 1e-6)
    if os.path.exists(CATALOG):  # the guide's catalog row, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set(REDUCED)
        assert set(cfg) == set(row["config"]) | set(kind.NOTES)


def test_bytes_by_hand_at_the_published_sizes():
    import numpy as np

    b = bench()
    cfg = b.cell(CELL)["model"]
    load_run()
    from benchlib import glm5_reference, glm5_weights

    count = lambda tree: sum(int(np.prod(s)) for s in tree.values())  # noqa: E731
    shp = glm5_weights.shapes(cfg)
    per_layer = lambda stack: sum(int(np.prod(s[1:])) for s in shp[stack].values())  # noqa: E731
    attention = 12_582_912 + 33_554_432 + 3_538_944 + 14_680_064 + 100_663_296 + 2_560  # W_qa, W_qb, W_kva, W_kvb, W_o, the two norms
    index = 8_388_608 + 786_432 + 196_608 + 256  # W^I_qb, W^I_k, W^I_w, the key's norm: weight and bias
    assert (attention, index) == (165_022_208, 9_371_904)
    assert per_layer("mla") == attention + index + 12_288 + 226_492_416 == 400_898_816  # the leading dense layer
    # an expert layer here: attention, index, two norms, the shared expert, router + bias at 256, 16 experts of 37,748,736
    outside = attention + index + 12_288 + 37_748_736 + 1_573_120
    assert outside == 213_728_256 and per_layer("mla_moe") == outside + 16 * 37_748_736 == 817_708_032
    top = {k: v for k, v in shp.items() if not isinstance(v, dict)}
    assert count(top) == 2 * 19360 * 6144 + 6144 == 237_895_680 + 6_144
    total = count(top) + sum(count(v) for v in shp.values() if isinstance(v, dict))
    assert total == 400_898_816 + 5 * 817_708_032 + 237_895_680 + 6_144 == 4_727_340_800 and 2 * total == 9_454_681_600
    whole = {**cfg, **cfg["reduced_from"], "num_nextn_predict_layers": 0, "assumed": {"initializer_range": 0.02}}
    shp = glm5_weights.shapes(whole)
    uncut = count({k: v for k, v in shp.items() if not isinstance(v, dict)}) + sum(count(v) for v in shp.values() if isinstance(v, dict))
    assert uncut == 3 * 400_898_816 + 75 * (outside + 256 * 37_748_736) + 2 * 154880 * 6144 + 6144 == 743_911_218_432  # 743.9B without MTP
    # a token leaves a latent row of 576 values stored 640 wide AND an index key of 128 behind in every layer: 1,536 B, 9,216 B over 6
    mcfg = b.cell_kind("rollout_family").model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert sum(heads * lanes * 2 for heads, lanes in mcfg.kv_pools.values()) == 1280 + 256 == 1536
    from areal_tpu.inference import paged_kv

    assert paged_kv.kv_token_bytes(mcfg.kv_pools, mcfg.num_kv_layers, 2) == 9216
    pages = {gb: paged_kv.n_pages_for_budget(int(gb * 2**30), 6, 1, 128, 640, 2, pools=mcfg.kv_pools) for gb in (3.5, 3.0, 2.5)}
    assert pages == {3.5: 3185, 3.0: 2730, 2.5: 2275} and 2730 * 128 == 349_440 > 327_680  # the traffic's worst case fits: nothing is preempted
    reader = b.reader("dsa_roofline")
    assert reader.token_layer_cost(cfg, "index_key") == (2 * 32 * 128, 256) == (8_192, 256)
    assert reader.token_layer_cost(cfg, "latent_row") == (2 * 64 * (576 + 512), 1_152) == (139_264, 1_152)
    assert b.reader("moe_weight_roofline").expert_bytes(cfg) == 3 * 6144 * 2048 * 2 == 75_497_472
    d = glm5_reference.dims(cfg)
    assert (d["E"], d["E_all"], d["e0"], d["Fs"], d["dense"], d["topk"], d["q_rank"], d["theta"]) == (16, 256, 0, 2048, 1, 2048, 2048, 1e6)


@pytest.fixture(scope="module")
def recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_new_readers_on_a_recorded_trace_by_hand(recorded):
    """The least time by hand for both reads: tokens a chunk x the chunk
    programs in the trace (by device seconds over a whole run's) x
    max(bytes / 819 GB/s, operations / 197 TFLOP/s), against the device
    seconds of the named scope (``attn`` of the recorded Qwen trace stands
    in for both scopes: a name the trace has). Without the counter, the
    scope, the trace or an index in the configuration the readers give
    nothing and do not raise: what the parent commit gives."""
    b = bench()
    cfg = b.cell(CELL)["model"]
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["attn"], "^jit_chunk\\(")
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]
    scored, selected = 32 * 6 * 600_000.0, 32 * 6 * 64 * 2048.0  # a chunk: 32 steps x 6 layers x 600k cached tokens; 64 slots x 2,048
    counters = {
        "areal_decode_index_tokens_scored_total": 5 * scored, "areal_decode_latent_tokens_selected_total": 5 * selected,
        "areal_decode_chunks_total": 5.0,
    }
    facts = {**recorded, "cfg": cfg, "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "window_counters": counters, "status": ({}, {})}}
    reader = b.reader("dsa_roofline")
    assert 256 / 819e9 > 8_192 / 197e12 and 1_152 / 819e9 > 139_264 / 197e12  # both bound by memory: 32 and 121 operations a byte
    for name, n, bytes_1 in (("decode_dsa_index_roofline", scored, 256), ("decode_dsa_cache_roofline", selected, 1_152)):
        metric = {**b.layer_metric(name), "scopes": ["attn"]}
        want = 100.0 * (n * runs * bytes_1 / 819e9) / secs
        assert reader.read(metric, facts) == pytest.approx(want, rel=1e-9) and want > 0
        assert reader.read(b.layer_metric(name), facts) is None or name == "decode_dsa_cache_roofline"  # no dsa_index_score scope in that program
        assert reader.read(metric, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None
        assert reader.read(metric, {**facts, "extra": None}) is None and reader.read(metric, {**facts, "trace": None}) is None
        assert reader.read(metric, {**facts, "cfg": b.cell("rollout-kanana-2-30b-a3b-ep8-grpo")["model"]}) is None  # latent attention without an index
    share = b.reader("extra_counter_share")
    assert share.read(b.layer_metric("dsa_selected_pct"), facts) == pytest.approx(100.0 * 64 * 2048 / 600_000)
    assert share.read(b.layer_metric("dsa_selected_pct"), {**facts, "extra": None}) is None
    assert share.read(b.layer_metric("dsa_selected_pct"), {**facts, "extra": {"window_counters": {}}}) is None
    named = b.reader("scope_share_named")
    assert named.read(b.layer_metric("decode_dsa_pct"), recorded) is None  # the recorded program has none of the four scopes
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-dsa"), recorded) == pytest.approx(named.read(b.layer_metric("scope_coverage_pct.rollout-mla"), recorded))


def test_seeded_weights_have_the_layout_the_program_reads():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench_glm5_util import make_params, model_config, tiny_model

    from areal_tpu.models import hybrid

    cfg = tiny_model()
    p = make_params(cfg, 5)
    want = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), model_config(cfg), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(lambda a: a.shape, want)
    m = {k: np.asarray(v, np.float64) for k, v in p["mla_moe"].items()}
    assert m["we_gate"].shape == (2, 4, 64, 32) and m["w_router"].shape == (2, 64, 8) and m["wi_qb"].shape == (2, 48, 4 * 128)
    assert abs(m["w_qa"].std() / 0.05 - 1) < 0.05 and abs(m["w_qb"].std() / 0.2 - 1) < 0.05  # W_qb alone is drawn 4 x wider
    assert abs(m["wi_qb"].std() / 0.05 - 1) < 0.05 and abs(m["q_a_norm"].mean() - 1) < 0.05 and 0.05 < m["wi_k_norm_bias"].std() < 0.15
    assert not np.array_equal(np.asarray(make_params(cfg, 2**31 + 5)["mla"]["w_kvb"]), np.asarray(p["mla"]["w_kvb"]))  # the seed decides, above 2**31 too


def test_the_settled_router_bias_loads_every_expert_alike():
    """``router_balance_tokens``: the seeded router's bias is what the
    balancing rule leaves, layer after layer, each layer settled under the
    settled layers before it. Held on the sequence it settled on: at this
    width (64) a sequence's own attention outputs make most of what its tokens
    share, so another sequence is loaded 1.7 x at worst (2.2 x unsettled); at
    GLM-5's widths the shared part is the weights' and another sequence reads
    1.45-1.65 x where unsettled reads 2.6-3.2 x (PERF.md section 6)."""
    import numpy as np
    from chipbench_glm5_util import load_run, make_params, tiny_model

    load_run()
    from benchlib import glm5_reference, glm5_weights

    cfg = tiny_model(held=8)
    settled_cfg = {**cfg, "assumed": {**cfg["assumed"], "router_balance_tokens": 1024}}
    ids = np.asarray(glm5_weights.balance_tokens(cfg, 7, 1024))
    assert ids.shape == (1024,) and ids.min() >= 0 and ids.max() < cfg["vocab_size"] and len(set(ids.tolist())) > 400

    def busiest(params):  # the busiest expert's load over the mean, every expert layer
        picks, _ = glm5_reference.routing_of(params, cfg, ids, pad_to=len(ids))
        return [np.bincount(layer.reshape(-1), minlength=8).max() * 8 / layer.size for layer in picks]

    plain, settled = make_params(cfg, 7), make_params(settled_cfg, 7)
    assert max(busiest(settled)) < 1.02 < 1.5 < min(busiest(plain))
    bias = np.asarray(settled["mla_moe"]["router_bias"])
    assert bias.shape == (2, 8) and np.abs(bias.mean(-1)).max() < 1e-6 and np.abs(bias).max() < 0.5
    for stack in plain:  # nothing else moved
        if isinstance(plain[stack], dict):
            assert all(np.array_equal(np.asarray(plain[stack][k]), np.asarray(settled[stack][k])) for k in plain[stack] if k != "router_bias")
    # the rule by hand: two experts, top-1, two tokens in three score the first higher by 0.1-0.15, one the second by 0.2:
    # the settled bias hands each expert half of the tokens
    scores = np.tile(np.array([[0.5, 0.4], [0.5, 0.4], [0.4, 0.6]], np.float32), (100, 1)) + np.linspace(0, 0.05, 300, dtype=np.float32)[:, None] * [[1, 0]]
    b = np.asarray(glm5_weights.settle_bias(scores, 1))
    assert abs(b.sum()) < 1e-6 and abs(np.bincount(np.argmax(scores + b, -1), minlength=2)[0] - 150) <= 2
