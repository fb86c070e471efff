"""What PR 32 adds to the benchmark as files: the configuration
``olmo-hybrid-7b-d16`` (the published ``config.json`` of Olmo-Hybrid-7B,
depth and ``layer_types`` cut to the first 16 layers), the cell
``rollout-olmo-hybrid-7b-d16-grpo`` of kind ``rollout_family_probe``
(``rollout_family`` unchanged, then a probe of the slots' state), four
per-layer metrics and their new reader (the live slots' state bytes against
the recurrence's device time), and a rehearsal of the cell at a tiny size."""

import json
import os

import pytest
from chipbench_olmo_util import CELL, CONFIG, rehearse
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
NEW = ["decode_gdn_pct", "prefill_gdn_pct", "decode_gdn_state_roofline", "scope_coverage_pct.rollout-gdn"]
GDN = ["gdn_proj", "gdn_conv", "gdn_state", "state_write"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-reasoning" and cell["config_name"] == CONFIG
    assert cell["params"]["kind"] == "rollout_family_probe" and callable(b.cell_kind("rollout_family_probe").run)
    p = cell["params"]
    assert (p["clients"], p["warm_seconds"], p["trace_seconds"], p["ttft_grace_seconds"]) == (8, 10, 8, 4)
    assert p["server"] == {"slots": 64, "max_seq_len": 4096, "page_size": 128, "kv_hbm_gb": 4.0, "decode_steps": 32, "attn_window_step": 4096}
    assert p["check"]["state_probe"] == {"requests": 8, "prompt_len": 256, "new_tokens": 768} and 0 < p["check"]["limit_state_rel"] < 0.1
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= per_layer and len(per_layer) == 13 + 4
    lfm2 = {m["name"] for m in b.cell("rollout-lfm2-8b-a1b-d14-grpo")["per_layer"]}
    assert per_layer - set(NEW) == {n for n in lfm2 if "moe" not in n}  # the thirteen cell 5 shares with the other rollout cells
    assert not {n for n in per_layer if "ssm" in n or "moe" in n or n in ("decode_attn_roofline", "scope_coverage_pct.rollout")}
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    assert [m["name"] for m in b.doc["per_layer"] if m.get("workloads") == [CELL]] == NEW
    # appended after what was there; never asserted to be last
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index(CONFIG) > configs.index("lfm2-8b-a1b-d14") and cells.index(CELL) > cells.index("rollout-lfm2-8b-a1b-d14-grpo")
    for m in (m for m in b.doc["per_layer"] if m["name"] in NEW):  # the entry and the metric's file say the same
        lm = b.layer_metric(m["name"])
        assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert b.layer_metric("decode_gdn_pct")["scopes"] == b.layer_metric("prefill_gdn_pct")["scopes"] == GDN
    assert set(b.layer_metric("scope_coverage_pct.rollout-gdn")["scopes"]) >= set(GDN) | {"attn", "mlp", "kv_write", "lm_head", "sampler"}
    fam = p["family"]
    for piece in ("weights", "reference"):  # the pieces the cell names exist where the kind looks for them
        assert os.path.isfile(os.path.join(CHIP, "benchlib", fam[piece] + ".py"))
    assert fam["state"] == {"leaf": "gdn", "dtype_key": "gdn_state_dtype", "control_dtype": "bfloat16"}
    assert fam["control"]["server"] == {"kv_quantization": "int8"}
    assert set(fam["control"]["round_int8"]) == {"w_gate", "w_up", "w_down", "q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}
    why = b.workload_entry(CELL)["why"]
    assert len(why) <= 200 and "2.2 MB" in why and "61 KB" in why and "16 of 32" in why


def test_configuration_file_is_the_published_config_cut_in_depth_only():
    b = bench()
    entry = b.config_entry(CONFIG)
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "layer_types"] and cfg["source"] == entry["source"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["num_hidden_layers"] == 16 and cfg["layer_types"] == PERIOD * 4
    assert cfg["reduced_from"] == {"num_hidden_layers": 32, "layer_types": PERIOD * 8}
    a = cfg["assumed"]
    assert a["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"] and a["rope_theta"] is None
    assert (a["norm_placement"], a["qk_norm_over"], a["gdn_state_dtype"], a["conv_state_dtype"], a["initializer_range"]) == ("post", "whole", "float32", "bfloat16", 0.02)
    kind = b.cell_kind("rollout_family")
    assert set(cfg) == set(PUBLISHED) | {"num_hidden_layers", "layer_types"} | set(kind.NOTES)
    assert set(a) == set(cfg["assumed_notes"])  # every assumed key has its note
    assert "ASSUMPTION" in cfg["assumed_notes"]["rope_theta"] and "ASSUMPTION" in cfg["assumed_notes"]["norm_placement"]
    assert "embedding AND the head" in cfg["stands_for"]
    fam = b.cell(CELL)["params"]["family"]
    assert set(fam["not_the_programs"]) == {"initializer_range", "linear_attention_form"}
    mcfg = kind.model_config(cfg, fam, "bfloat16")  # the program builds the family from it, at the published widths
    assert (mcfg.count("gdn"), mcfg.num_kv_layers, mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim_, mcfg.kv_head_dim) == (12, 4, 30, 30, 128, 128)
    assert (mcfg.gdn_n_heads, mcfg.gdn_k_dim, mcfg.gdn_v_dim, mcfg.gdn_d_conv, mcfg.gdn_neg_eigval, mcfg.gdn_head_pack) == (30, 96, 192, 4, True, 2)
    assert (mcfg.norm_placement, mcfg.qk_norm_over, mcfg.rope_theta, mcfg.tie_word_embeddings) == ("post", "whole", None, False)
    if os.path.exists(CATALOG):  # the guide's catalog row, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
        assert row["source_url"] == cfg["source"]
        assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set(cfg["reduced"])


def test_bytes_by_hand_at_the_published_sizes():
    import numpy as np

    b = bench()
    cfg = b.cell(CELL)["model"]
    load_run()
    from benchlib import olmo_hybrid_weights

    # ISSUE 32: a linear layer 215,570,172 parameters, an attention layer 185,809,920, embedding + head + final norm 770,707,200
    shp = olmo_hybrid_weights.shapes(cfg)
    per_layer = lambda stack: sum(int(np.prod(s[1:])) for s in shp[stack].values())  # noqa: E731
    assert (per_layer("gdn"), per_layer("attention")) == (215_570_172, 185_809_920)
    assert olmo_hybrid_weights.count(cfg) == 12 * 215_570_172 + 4 * 185_809_920 + 770_707_200 == 4_100_788_944
    full = {**cfg, "layer_types": cfg["reduced_from"]["layer_types"]}
    assert olmo_hybrid_weights.count(full) == 7_430_870_688  # the published "7B"
    # the state: 30 heads of 96 x 192 float32 a layer, and the program's cache holds exactly that, two heads a tile
    assert b.reader("gdn_state_roofline").state_bytes(cfg) == 30 * 96 * 192 * 4 == 2_211_840
    mcfg = b.cell_kind("rollout_family").model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    shapes = mcfg.state_shapes(64)
    assert shapes["gdn"][0] == (12, 64, 15, 96, 384) and shapes["conv"][0] == (12, 64, 3 * 11520)
    a_slot = sum(int(np.prod(s)) // 64 * np.dtype(t).itemsize for s, t in shapes.values())
    assert a_slot == 12 * 2_211_840 + 12 * 3 * 11520 * 2 == 27_371_520  # ISSUE 32's bytes a slot
    assert 2 * 4 * 30 * 128 * 2 == 61_440  # K and V bytes a token over the 4 attention layers


def test_seeded_weights_follow_the_layers_published_initialisation():
    import jax.numpy as jnp
    import numpy as np
    from chipbench_olmo_util import make_params, model_config, tiny_model

    cfg = tiny_model()
    p = make_params(cfg, 5)
    mcfg = model_config(cfg)
    from areal_tpu.models import hybrid

    want = hybrid.init_params(__import__("jax").random.PRNGKey(0), mcfg, jnp.float32)
    assert {k: (sorted(v) if isinstance(v, dict) else v.shape) for k, v in p.items()} == {
        k: (sorted(v) if isinstance(v, dict) else v.shape) for k, v in want.items()
    }  # the layout the program's forward reads
    g = {k: np.asarray(v, np.float64) for k, v in p["gdn"].items()}
    assert abs(g["q_proj"].std() / 0.02 - 1) < 0.05 and abs(g["input_norm"].mean() - 1) < 0.05 and 0.05 < g["o_norm"].std() < 0.15
    for name in ("q_conv_w", "k_conv_w", "v_conv_w"):
        assert np.abs(g[name]).max() <= 0.5 and abs(g[name].std() - 0.5 / 3**0.5) < 0.03
    assert np.exp(g["A_log"]).max() <= 16 and np.exp(g["A_log"]).min() > 0
    dt = np.log1p(np.exp(g["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    assert np.array_equal(np.asarray(make_params(cfg, 5)["gdn"]["v_proj"]), np.asarray(p["gdn"]["v_proj"]))  # the seed decides
    assert not np.array_equal(np.asarray(make_params(cfg, 2**31 + 5)["gdn"]["v_proj"]), np.asarray(p["gdn"]["v_proj"]))


@pytest.fixture(scope="module")
def recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_the_four_new_metrics_read_a_scoped_trace(recorded):
    """The least time by hand: updates a chunk x the chunk programs in the
    trace (by device seconds over a whole run's) x 2 x 2,211,840 B over 819
    GB/s, against the device seconds of the named scope (here ``mlp`` of the
    recorded Qwen trace stands in for ``gdn_state``, which that program
    lacks). Without the counter, the scope or the trace every new reader
    gives nothing and does not raise: what the parent commit gives."""
    b = bench()
    metric = {**b.layer_metric("decode_gdn_state_roofline"), "scopes": ["mlp"]}
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], metric["within_module"])
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]
    counters = {"areal_decode_gdn_state_updates_total": 5 * 32 * 12 * 23.0, "areal_decode_chunks_total": 5.0}
    facts = {**recorded, "cfg": b.cell(CELL)["model"], "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "status": ({}, {})}}
    want = 100.0 * (32 * 12 * 23.0 * runs * 2 * 2_211_840 / 819e9) / secs
    reader = b.reader("gdn_state_roofline")
    assert reader.read(metric, facts) == pytest.approx(want, rel=1e-9) and want > 0
    assert reader.read(b.layer_metric("decode_gdn_state_roofline"), facts) is None  # no such scope in that program
    assert reader.read(metric, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None  # a program without the counter
    assert reader.read(metric, {**facts, "extra": None}) is None and reader.read(metric, {**facts, "trace": None}) is None
    assert reader.read(metric, {**facts, "cfg": b.cell("rollout-7b-d14-grpo")["model"]}) is None  # another family's cell
    named = b.reader("scope_share_named")
    for name in ("decode_gdn_pct", "prefill_gdn_pct"):
        assert named.read(b.layer_metric(name), recorded) is None  # the recorded program has no such scope
    share = named.read({**b.layer_metric("decode_gdn_pct"), "scopes": ["mlp", "attn"]}, recorded)  # stand-ins it has
    assert 0 < share < 100
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert named.read(b.layer_metric("scope_coverage_pct.rollout-gdn"), recorded) == pytest.approx(old)


def test_cell_rehearses_on_cpu_with_its_state_probe(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing to a model with a recurrent state
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["mean_abs"] < 2e-5 and chk["max_abs"] < 1e-4  # float32 on both sides
    assert (chk["probe_requests"], chk["probe_failed"], chk["slots_changed"]) == (2, 0, 2) and chk["state_tokens"] == 2 * (12 + 20 - 1)
    assert chk["state_rel"] < 1e-5 and chk["state_rel_head_mean"] <= chk["state_rel"]
    json.loads(json.dumps(res))


def test_control_comes_out_not_correct_by_both_limits(tmp_path):
    """int8 KV pages and the MLP and delta-rule projection matrices rounded to
    int8 fail the logprob limit; the state held in bfloat16 fails the state's
    (sound float32 rehearsals read 2e-6 and 3e-7)."""
    res = rehearse(0, tmp_path, control=True)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-4 and chk["state_rel"] > 1e-3 and chk["slots_changed"] == 2
