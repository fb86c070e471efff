"""What PR 30 adds to the benchmark as files: the configuration
``lfm2-8b-a1b-d14`` (the published ``config.json`` of LFM2-8B-A1B, depth and
``layer_types`` cut to the first 14 layers), the cell
``rollout-lfm2-8b-a1b-d14-grpo`` of kind ``rollout_family``, five per-layer
metrics and their two new readers (the touched experts' bytes against the
expert matmuls' device time; the busiest expert over the mean)."""

import json
import os

import pytest
from chipbench_lfm2_util import CELL
from chipbench_util import CHIP, bench, load_run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL_LAYERS = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4 + ["full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json, the keys that say something of its shape
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
NEW = ["decode_moe_pct", "prefill_moe_pct", "decode_moe_weight_roofline", "moe_load_max_over_mean", "scope_coverage_pct.rollout-moe"]


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-reasoning" and cell["params"]["kind"] == "rollout_family"
    assert callable(b.cell_kind("rollout_family").run)
    p = cell["params"]
    assert p["clients"] == 16 and p["warm_seconds"] == 10 and p["ttft_grace_seconds"] == 4
    assert p["server"] == {"slots": 128, "max_seq_len": 4096, "page_size": 128, "kv_hbm_gb": 3.5, "decode_steps": 32, "attn_window_step": 4096}
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= per_layer and len(per_layer) == 13 + 5
    # cannot join: the attention cost function counts every layer as attending, the coverage vocabularies lack the new scopes
    assert not {"decode_attn_roofline", "scope_coverage_pct.rollout", "scope_coverage_pct.rollout-hybrid", "decode_ssm_pct"} & per_layer
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    new = [m["name"] for m in b.doc["per_layer"] if m.get("workloads") == [CELL]]
    assert new == NEW
    # appended after what was there, never asserted to be LAST: PR 26's files test holds its configuration to the last
    # place and so fails from this PR on (a file this PR may not edit; PERF.md section 7)
    configs, cells = [c["name"] for c in b.doc["configs"]], [w["name"] for w in b.doc["workloads"]]
    assert configs.index("lfm2-8b-a1b-d14") > configs.index("granite-4.0-h-micro") and cells.index(CELL) > cells.index("rollout-granite-h-micro-grpo")
    for m in (m for m in b.doc["per_layer"] if m["name"] in NEW):  # the entry and the metric's file say the same
        lm = b.layer_metric(m["name"])
        assert all(lm[k] == m[k] for k in ("unit", "better", "source", "layer", "moves"))
    # the pieces the cell names exist where the kind looks for them
    for piece in ("weights", "reference"):
        assert os.path.isfile(os.path.join(CHIP, "benchlib", p["family"][piece] + ".py"))


def test_configuration_file_is_the_published_config_cut_in_depth_only():
    b = bench()
    entry = b.config_entry("lfm2-8b-a1b-d14")
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "layer_types"] and cfg["source"] == entry["source"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["num_hidden_layers"] == 14 and cfg["layer_types"] == FULL_LAYERS[:14]
    assert cfg["reduced_from"] == {"num_hidden_layers": 24, "layer_types": FULL_LAYERS} and len(FULL_LAYERS) == 24
    assert cfg["layer_types"].count("full_attention") == 3 and cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 12
    a = cfg["assumed"]
    assert a["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"] and a["tie_word_embeddings"] is True
    assert (a["router_score"], a["router_norm_eps"], a["kv_lane_pad"], a["conv_state_dtype"]) == ("sigmoid", 1e-06, 128, "bfloat16")
    notes = bench().cell_kind("rollout_family").NOTES
    assert set(cfg) == set(PUBLISHED) | {"num_hidden_layers", "layer_types"} | set(notes)
    assert set(cfg["assumed"]) >= set(cfg["assumed_notes"]) >= {"tie_word_embeddings", "router_score", "kv_lane_pad"}
    # the program builds the family from it, all 32 experts and the published widths
    mcfg = b.cell_kind("rollout_family").model_config(cfg, b.cell(CELL)["params"]["family"], "bfloat16")
    assert (mcfg.num_experts, mcfg.num_experts_per_tok, mcfg.moe_intermediate_size, mcfg.intermediate_size) == (32, 4, 1792, 7168)
    assert mcfg.num_moe_layers == 12 and mcfg.num_kv_layers == 3 and mcfg.count("conv") == 11
    control = b.cell(CELL)["params"]["family"]["control"]
    assert control["server"] == {"kv_quantization": "int8"} and set(control["round_int8"]) == {"w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down"}
    if os.path.exists(CATALOG):  # the guide's catalog row, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        assert row["source_url"] == cfg["source"]
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])


def test_bytes_by_hand_at_the_published_sizes():
    b = bench()
    cfg = b.cell(CELL)["model"]
    load_run()
    from benchlib import lfm2_weights

    # one expert: 3 matrices of 2048 x 1792 bfloat16 = ISSUE 30's 22,020,096 B
    assert b.reader("moe_weight_roofline").expert_bytes(cfg) == 3 * 2048 * 1792 * 2 == 22_020_096
    # the weight tree at 14 layers: ISSUE 30's 4,667,077,376 parameters, 9.33 GB in bfloat16
    import math

    leaves = []

    def walk(t):
        for v in t.values():
            walk(v) if isinstance(v, dict) else leaves.append(math.prod(v))

    walk(lfm2_weights.shapes(cfg))
    assert sum(leaves) == 4_667_077_376
    expert = 12 * 32 * 3 * 2048 * 1792
    assert round(100 * expert / sum(leaves), 1) == 90.6  # the share of a step's bytes that is expert weights


def test_seeded_experts_of_a_layer_share_a_part():
    """``expert_own_share`` 0.25 (the configuration's ``assumed``): two
    experts of a layer correlate by 1 - 0.25^2 = 0.94, experts of different
    layers and everything else not at all, and every weight keeps the std of
    ``initializer_range``; without the key the experts are independent (the
    CPU tests' tiny models). The program is never told."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench_lfm2_util import family, tiny_model

    load_run()
    from benchlib import lfm2_weights

    cfg = tiny_model()
    assert bench().cell(CELL)["model"]["assumed"]["expert_own_share"] == 0.25 and "expert_own_share" in family()["not_the_programs"]

    def corr(a, b):
        return float(np.corrcoef(np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel())[0, 1])

    alone = lfm2_weights.make_params(cfg, 5, jnp.float32)["conv_moe"]
    cfg["assumed"] = {**cfg["assumed"], "expert_own_share": 0.25}
    shared = lfm2_weights.make_params(cfg, 5, jnp.float32)["conv_moe"]
    for name in ("we_gate", "we_up", "we_down"):
        w = shared[name]
        assert abs(corr(w[0, 0], w[0, 1]) - 0.9375) < 0.02 and abs(corr(w[0, 0], w[1, 0])) < 0.06
        assert abs(corr(alone[name][0, 0], alone[name][0, 1])) < 0.06
        assert abs(float(np.std(np.asarray(w))) / 0.05 - 1) < 0.02 and abs(float(np.std(np.asarray(alone[name]))) / 0.05 - 1) < 0.02
    assert abs(corr(shared["we_gate"][0, 0], shared["we_up"][0, 0])) < 0.06 and abs(corr(shared["w_router"][0, :, 0], shared["w_router"][0, :, 1])) < 0.4
    from chipbench_lfm2_util import model_config

    assert model_config(cfg) == model_config(tiny_model())


# the lines of `rollout.run` that `rollout_family.run` replaces: what names the family, and nothing of the window
FAMILY = {
    'eng, server, make_params = build_server(cfg, sp, p["dtype"], seed, devs, ctx["control"])',
    "del eng, server  # the KV pool makes room for the reference",
    "res = check_outputs(records, make_params(), cfg, sp, chk, seed)",
    'limit = float(chk["limit_mean_abs_logprob"])',
    "f\"{res['mean_abs']} (limit {limit}), max {res['max_abs']}\"",
    '+ (" [CONTROL: program with int8 weights and int8 KV]" if ctx["control"] else "")',
    '"kind": "rollout",',
}


def test_window_and_arithmetic_are_the_rollout_kinds_line_for_line():
    """`cells/rollout.py` may not be edited, so this kind carries a copy of
    its `run`; this holds them equal: every line of `rollout.run` but those
    that name the family or the check's statistic stands in this kind's
    `run`, in the same order, and what this kind adds (the memory ledger's
    log line, the extra readings) assigns nothing that the window's
    arithmetic reads."""
    import difflib
    import inspect
    import re

    b = bench()

    def code(kind):
        lines = (ln.strip() for ln in inspect.getsource(b.cell_kind(kind).run).splitlines())
        return [ln for ln in lines if ln and not ln.startswith("#")]

    diff = [d for d in difflib.ndiff(code("rollout"), code("rollout_family")) if d[0] in "+-"]
    assert {d[2:] for d in diff if d[0] == "-"} == FAMILY
    added = [d[2:] for d in diff if d[0] == "+"]
    assert len(added) < 20
    reads = re.compile(r"^(values|window|setup_s|delta|records|s|c0|c1|t0|t1|c_warm|c_end|n_comp|n_hit|seconds|trace|trace_counters)\b[^=(]*=[^=]")
    assert [ln for ln in added if reads.match(ln)] == []


@pytest.fixture(scope="module")
def recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_expert_roofline_arithmetic_on_a_recorded_trace(recorded):
    """The least time by hand: experts touched a chunk x the chunk programs
    in the trace (by device seconds over a whole run's) x 22 MB over 819 GB/s, against the device seconds of the
    named scope (here ``mlp`` of the recorded Qwen trace stands in for
    ``moe_experts``, which that program lacks). Without the counter, the
    scope or the trace the reader gives nothing and does not raise."""
    from benchlib import trace_reduce

    b = bench()
    metric = {**b.layer_metric("decode_moe_weight_roofline"), "scopes": ["mlp"]}
    _, secs = b.reader("scope_share_named").scope_seconds(recorded, ["mlp"], metric["within_module"])
    durs = [d for n, _, d in recorded["trace"].devices[0].modules if n.startswith("jit_chunk(")]
    runs = sum(durs) / sorted(durs)[len(durs) // 2]  # the chunk programs as whole runs' worth (an odd count: the median is an element)
    assert len(durs) == 15 and 13.5 < runs < 13.7  # the trace's two cut runs (0.11 and 0.23 of 0.60 s) count for their traced part
    counters = {"areal_decode_moe_experts_touched_total": 5 * 32 * 12 * 31.5, "areal_decode_chunks_total": 5.0}
    facts = {**recorded, "cfg": b.cell(CELL)["model"], "device_kind": "TPU v5 lite", "extra": {"trace_counters": counters, "status": ({}, {})}}
    want = 100.0 * (32 * 12 * 31.5 * runs * 22_020_096 / 819e9) / secs
    assert runs > 0 and secs > 0
    reader = b.reader("moe_weight_roofline")
    assert reader.read(metric, facts) == pytest.approx(want, rel=1e-9)
    assert reader.read(b.layer_metric("decode_moe_weight_roofline"), facts) is None  # no such scope in that program
    assert reader.read(metric, {**facts, "extra": {"trace_counters": dict.fromkeys(counters, 0.0)}}) is None  # a program without the counter
    assert reader.read(metric, {**facts, "extra": None}) is None and reader.read(metric, {**facts, "trace": None}) is None
    for name in ("decode_moe_pct", "prefill_moe_pct"):
        assert b.reader("scope_share_named").read(b.layer_metric(name), recorded) is None
    cov = b.layer_metric("scope_coverage_pct.rollout-moe")
    assert set(cov["scopes"]) >= {"moe_router", "moe_dispatch", "moe_experts", "moe_combine", "conv_proj", "conv_mix", "state_write", "attn", "mlp"}
    old = b.reader("scope_share").read(b.layer_metric("scope_coverage_pct.rollout"), recorded)
    assert b.reader("scope_share_named").read(cov, recorded) == pytest.approx(old)


def test_load_balance_reader_by_hand():
    b = bench()
    metric, reader = b.layer_metric("moe_load_max_over_mean"), b.reader("moe_load_balance")
    first = {"moe": {"load": [[10, 10, 10, 10], [0, 0, 0, 0]]}}
    last = {"moe": {"load": [[20, 20, 20, 20], [8, 2, 1, 1]]}}
    assert reader.read(metric, {"extra": {"status": (first, last)}}) == pytest.approx(8 * 4 / 12)  # the second layer: 8 rows of 12 on one of 4
    assert reader.read(metric, {"extra": {"status": (first, first)}}) is None  # nothing decoded in the window
    assert reader.read(metric, {"extra": {"status": ({"moe": None}, {"moe": None})}}) is None  # a program without the field
    assert reader.read(metric, {}) is None
