"""What PR 37 adds to the benchmark as files: the configuration
``kanana-2-30b-a3b-ep8`` (the published ``config.json`` of
kanana-2-30b-a3b-instruct-2601 with the experts held and the vocabulary cut
to one chip's share of a v5e-8), the cell ``rollout-kanana-2-30b-a3b-ep8-grpo``
of kind ``rollout_family`` (the file unchanged), four per-layer metrics and
one new reader (the latent rows' bytes and operations against the latent
kernel's device time). Every assertion names what it is about: none counts a
list or takes an entry by its place."""

import json
import os

import pytest
from chipbench_kanana2_util import CELL, CONFIG
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"decode_mla_pct", "prefill_mla_pct", "decode_mla_cache_roofline", "scope_coverage_pct.rollout-mla"}
SHARED_MOE = {"decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean"}
MLA = ["mla_proj", "attn", "kv_write"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-reasoning" and cell["config_name"] == CONFIG
    p = cell["params"]
    assert p["kind"] == "rollout_family" and callable(b.cell_kind("rollout_family").run)
    assert (p["clients"], p["warm_seconds"], p["trace_seconds"], p["ttft_grace_seconds"], p["check"]["sample"]) == (8, 10, 8, 4, 12)
    server = dict(p["server"])
    assert server.pop("kv_hbm_gb") in (3.0, 3.5)
    assert server == {"slots": 64, "max_seq_len": 4096, "page_size": 128, "decode_steps": 32, "attn_window_step": 4096}
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert NEW <= per_layer and SHARED_MOE <= per_layer
    # what every rollout cell reports, this one too: whatever the Qwen 7B cell lists, but the GQA kernel's roofline and its coverage
    qwen = {m["name"] for m in b.cell("rollout-7b-d14-grpo")["per_layer"]} - {"decode_attn_roofline", "scope_coverage_pct.rollout"}
    assert qwen <= per_layer and {"pass_excess_ms", "setup_engine_init_s", "setup_uncovered_s", "decode_attn_pct", "decode_kv_write_pct"} <= qwen
    assert per_layer == qwen | NEW | SHARED_MOE  # no other family's metric (ssm, gdn, conv coverage) reads this cell
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    assert {m["name"] for m in b.doc["per_layer"] if m.get("workloads") == [CELL]} == NEW
    for m in (m for m in b.doc["per_layer"] if m["name"] in NEW):  # the entry and the metric's file say the same
        lm = b.layer_metric(m["name"])
        assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert b.layer_metric("decode_mla_pct")["scopes"] == b.layer_metric("prefill_mla_pct")["scopes"] == MLA
    assert b.layer_metric("decode_mla_pct")["moves"] == "tpot_p95_ms" and b.layer_metric("prefill_mla_pct")["moves"] == "ttft_p50_ms"
    roof = b.layer_metric("decode_mla_cache_roofline")
    assert (roof["reader"], roof["scopes"], roof["tokens_counter"]) == ("latent_cache_roofline", ["attn"], "areal_decode_latent_tokens_read_total")
    cover = set(b.layer_metric("scope_coverage_pct.rollout-mla")["scopes"])
    assert cover >= set(MLA) | {"moe_shared", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "mlp", "lm_head", "sampler", "embed"}
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert "server" not in fam["control"]  # no int8 latent pages: the program serves none
    assert set(fam["control"]["round_int8"]) == {
        "w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down", "wq", "w_kva", "w_kvb", "wo",
    }
    assert {"areal_decode_latent_tokens_read_total", "areal_decode_moe_experts_touched_total", "areal_decode_chunks_total"} <= set(fam["counters"])
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and all(s in why for s in ("all 48 layers", "16 of 128 experts", "1/8 vocabulary", "61 KB", "1/8 of"))


def test_the_parents_entries_are_all_still_there_and_only_appended_to():
    """Against the parent commit's BENCHMARK.json where git has it: every
    entry it had is there unchanged but for this cell's name appended to
    ``workloads`` lists, and in the parent's order."""
    import subprocess

    b = bench()
    try:
        old = json.loads(subprocess.run(["git", "show", "aebcf5adb20d6d4b43dfe146bb7caac3642e6f36:BENCHMARK.json"], cwd=b.root, capture_output=True, check=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    new = b.doc
    assert {k: new[k] for k in ("command", "paths", "run_seconds")} == {k: old[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        head = new[group][: len(old[group])]
        for was, now in zip(old[group], head):
            if "workloads" in was and now != was:
                assert now == {**was, "workloads": was["workloads"] + [CELL]}
            else:
                assert now == was
        assert all(CELL in json.dumps(x) or x.get("name") == CONFIG for x in new[group][len(old[group]) :])
    assert len(new["configs"]) == len(old["configs"]) + 1 and len(new["workloads"]) == len(old["workloads"]) + 1


def test_configuration_file_is_the_published_config_with_the_share_alone_reduced():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == ["n_routed_experts", "vocab_size"] and cfg["source"] == entry["source"]
    assert cfg["reduced_from"] == {"n_routed_experts": 128, "vocab_size": 128256}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (48, 16, 16032) and 16032 % 128 == 32
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]) == (512, 128, 64, 128, None)
    assert (cfg["moe_intermediate_size"], cfg["n_shared_experts"], cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (768, 2, 6, 2.448)
    a = cfg["assumed"]
    assert a == {"router_experts": 128, "expert_first": 0, "latent_row_lanes": 640, "latent_page_dtype": "bfloat16", "initializer_range": 0.02}
    assert set(a) == set(cfg["assumed_notes"])  # every assumed key has its note
    kind = b.cell_kind("rollout_family")
    for words in ("8 chips share EACH of the 48 layers", "rank 0", "experts 0-15", "rows 0-16,031", "shared block", "exchange"):
        assert words in cfg["stands_for"]
    fam = b.cell(CELL)["params"]["family"]
    assert fam["not_the_programs"] == ["initializer_range", "latent_page_dtype"]  # the pages' type is the served one: said, not set
    mcfg = kind.model_config(cfg, fam, "bfloat16")  # the program builds the family from it, at the published widths
    assert (mcfg.count("mla"), mcfg.num_moe_layers, mcfg.num_heads, mcfg.vocab_size, mcfg.tie_word_embeddings) == (48, 47, 32, 16032, False)
    assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first, mcfg.num_experts_per_tok, mcfg.moe_shared_intermediate_size) == (16, 128, 0, 6, 1536)
    assert (mcfg.latent_dim, mcfg.latent_lanes, mcfg.kv_pools, mcfg.sm_scale, mcfg.rope_interleave) == (576, 640, {"k": (1, 640)}, 192**-0.5, True)
    assert (mcfg.router_score, mcfg.router_bias, mcfg.router_norm_eps, mcfg.routed_scaling_factor) == ("sigmoid", True, 1e-20, 2.448)
    if os.path.exists(CATALOG):  # the guide's catalog row, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set(cfg["reduced"])
        assert set(cfg) == set(row["config"]) | set(kind.NOTES)


def test_bytes_by_hand_at_the_published_sizes():
    import numpy as np

    b = bench()
    cfg = b.cell(CELL)["model"]
    load_run()
    from benchlib import kanana2_reference, kanana2_weights

    shp = kanana2_weights.shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in tree.values())  # noqa: E731
    per_layer = lambda stack: sum(int(np.prod(s[1:])) for s in shp[stack].values())  # noqa: E731
    attention = 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608  # W_q, W_kva, its norm, W_kvb, W_o
    assert attention == 26_345_984
    assert per_layer("mla") == attention + 4_096 + 37_748_736 == 64_098_816  # the leading dense layer
    # an expert layer here: attention, two norms, 16 experts of 4,718,592, the shared block, router + bias at 128
    assert per_layer("mla_moe") == attention + 4_096 + 16 * 4_718_592 + 9_437_184 + 262_272 == 111_547_008
    top = {k: v for k, v in shp.items() if not isinstance(v, dict)}
    assert count(top) == 2 * 16032 * 2048 + 2048 == 65_667_072 + 2_048
    total = count(top) + sum(count(v) for v in shp.values() if isinstance(v, dict))
    assert total == 47 * 111_547_008 + 64_098_816 + 65_667_072 + 2_048 == 5_372_477_312
    whole = {**cfg, "n_routed_experts": 128, "vocab_size": 128256, "assumed": {"initializer_range": 0.02}}
    shp = kanana2_weights.shapes(whole)
    assert count({k: v for k, v in shp.items() if not isinstance(v, dict)}) + sum(count(v) for v in shp.values() if isinstance(v, dict)) == 30_670_815_104
    # a token leaves 576 values a layer behind, stored 640 wide: 61,440 B over the 48 layers (55,296 published)
    mcfg = b.cell_kind("rollout_family").model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    (heads, lanes), = mcfg.kv_pools.values()
    assert mcfg.num_kv_layers * heads * lanes * 2 == 48 * 1280 == 61_440 and 48 * mcfg.latent_dim * 2 == 55_296
    assert b.reader("latent_cache_roofline").token_layer_cost(cfg) == (69_632, 1_152)
    assert b.reader("moe_weight_roofline").expert_bytes(cfg) == 3 * 2048 * 768 * 2
    d = kanana2_reference.dims(cfg)
    assert (d["E"], d["E_all"], d["e0"], d["Fs"], d["dense"]) == (16, 128, 0, 1536, 1)


@pytest.fixture(scope="module")
def recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_new_reader_on_a_recorded_trace_by_hand(recorded):
    """The least time by hand: reads a chunk x the chunk programs in the
    trace (by device seconds over a whole run's) x max(1,152 B / 819 GB/s,
    69,632 operations / 197 TFLOP/s), against the device seconds of the
    named scope (``attn`` of the recorded Qwen trace is that program's GQA
    kernel: a stand-in with a name the trace has). Without the counter, the
    scope, the trace or a latent in the configuration the reader gives
    nothing and does not raise: what the parent commit gives."""
    b = bench()
    metric = b.layer_metric("decode_mla_cache_roofline")
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["attn"], metric["within_module"])
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]
    per_chunk = 32 * 48 * 20_000.0  # 32 steps x 48 layers x 20k cached tokens of live slots
    counters = {"areal_decode_latent_tokens_read_total": 5 * per_chunk, "areal_decode_chunks_total": 5.0}
    facts = {**recorded, "cfg": b.cell(CELL)["model"], "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "status": ({}, {})}}
    n = per_chunk * runs
    assert 1_152 / 819e9 > 69_632 / 197e12  # bound by memory at the published sizes: 60 operations a byte
    want = 100.0 * (n * 1_152 / 819e9) / secs
    reader = b.reader("latent_cache_roofline")
    assert reader.read(metric, facts) == pytest.approx(want, rel=1e-9) and want > 0
    assert reader.read({**metric, "scopes": ["mla_proj"]}, facts) is None  # no such scope in that program
    assert reader.read(metric, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None  # a program without the counter
    assert reader.read(metric, {**facts, "extra": None}) is None and reader.read(metric, {**facts, "trace": None}) is None
    assert reader.read(metric, {**facts, "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # a configuration without a latent
    named = b.reader("scope_share_named")
    share = named.read(b.layer_metric("decode_mla_pct"), recorded)  # the recorded program has attn and kv_write, no mla_proj
    assert 0 < share < 100
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-mla"), recorded) == pytest.approx(old)


def test_seeded_weights_have_the_layout_the_program_reads():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench_kanana2_util import make_params, model_config, tiny_model

    from areal_tpu.models import hybrid

    cfg = tiny_model()
    p = make_params(cfg, 5)
    want = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), model_config(cfg), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(lambda a: a.shape, want)
    m = {k: np.asarray(v, np.float64) for k, v in p["mla_moe"].items()}
    assert m["we_gate"].shape == (3, 4, 64, 32) and m["w_router"].shape == (3, 64, 8) and m["router_bias"].shape == (3, 8)
    assert abs(m["wq"].std() / 0.05 - 1) < 0.05 and abs(m["kv_norm"].mean() - 1) < 0.05 and 0.05 < m["kv_norm"].std() < 0.15
    assert 0.02 < m["router_bias"].std() < 0.08  # it changes some selections and leaves most
    assert not np.array_equal(np.asarray(make_params(cfg, 2**31 + 5)["mla"]["w_kvb"]), np.asarray(p["mla"]["w_kvb"]))  # the seed decides, above 2**31 too
