"""What PR 26 adds to the benchmark as files: the configuration
``granite-4.0-h-micro`` (the published ``config.json``, nothing reduced), the
cell ``rollout-granite-h-micro-grpo`` of kind ``rollout_hybrid``, four
per-layer metrics and their two readers (scope names taken from the metric
file; the recurrent state's bytes a step)."""

import json
import os

import pytest
from chipbench_hybrid_util import CELL
from chipbench_util import CHIP, bench, load_run

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json, the keys that say something of its shape
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": [("attention" if i % 10 == 5 else "mamba") for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_new_files_resolve_and_the_cell_reports_what_it_lists():
    b = bench()
    assert b.problems() == []
    cell = b.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "grpo-reasoning" and cell["params"]["kind"] == "rollout_hybrid"
    assert callable(b.cell_kind("rollout_hybrid").run)
    assert {m["name"] for m in cell["end_to_end"]} == {"rollout_tok_s", "ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert {"decode_ssm_pct", "decode_ssm_state_roofline", "prefill_ssm_pct", "scope_coverage_pct.rollout-hybrid"} <= per_layer
    # cannot join: the attention cost function counts 40 attending layers, the coverage vocabulary lacks the new scopes
    assert not {"decode_attn_roofline", "scope_coverage_pct.rollout"} & per_layer
    for name in per_layer:
        b.reader(b.layer_metric(name)["reader"])
    new = [m for m in b.doc["per_layer"] if m.get("workloads") == [CELL]]
    assert len(new) == 4 and [c["name"] for c in b.doc["configs"]][-1] == "granite-4.0-h-micro"


def test_configuration_file_is_the_published_config():
    b = bench()
    entry = b.config_entry("granite-4.0-h-micro")
    with open(os.path.join(b.root, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["reduced"] == [] == cfg["reduced"] and cfg["source"] == entry["source"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["assumed"]["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["assumed"]["ssm_state_dtype"] == "float32"
    # nothing at the top level but the published keys and the notes: what is assumed is under `assumed`, with its reason
    notes = load_run() and bench().cell_kind("rollout_hybrid").NOTES
    assert set(cfg) == set(PUBLISHED) | set(notes) - {"reduced_from"}
    assert set(cfg["assumed_notes"]) >= {"initializer_range", "kv_lane_pad"} and set(cfg["assumed"]) >= set(cfg["assumed_notes"])
    if os.path.exists(CATALOG):  # the guide's catalog row, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
        assert row["source_url"] == cfg["source"] and {k: cfg[k] for k in row["config"]} == row["config"]


def test_state_bytes_by_hand_at_the_published_sizes():
    b = bench()
    per_slot = b.reader("state_roofline").state_bytes_per_slot(b.cell(CELL)["model"])
    # a Mamba layer and slot: 64 heads x 64 x 128 float32 = 2 MiB, and 3 conv inputs of 4,352 bfloat16 channels
    assert per_slot == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    # 64 slots: the 4.89 GB of ISSUE 26's table
    assert round(64 * per_slot / 1e9, 2) == 4.89


# the lines of `rollout.run` that `rollout_hybrid.run` replaces: what names the family, and nothing of the window
FAMILY = {
    'eng, server, make_params = build_server(cfg, sp, p["dtype"], seed, devs, ctx["control"])',
    "del eng, server  # the KV pool makes room for the reference",
    "res = check_outputs(records, make_params(), cfg, sp, chk, seed)",
    '+ (" [CONTROL: program with int8 weights and int8 KV]" if ctx["control"] else "")',
    '"kind": "rollout",',
}


def test_window_and_arithmetic_are_the_rollout_kinds_line_for_line():
    """`cells/rollout.py` may not be edited, so this kind carries a copy of
    its `run`; the yardstick lives in two places, and this holds them equal:
    every line of `rollout.run` but the five that name the family stands in
    this kind's `run`, in the same order, and what this kind adds (the state
    probe, its logs) assigns nothing that the window's arithmetic reads."""
    import difflib
    import inspect
    import re

    b = bench()

    def code(kind):
        lines = (ln.strip() for ln in inspect.getsource(b.cell_kind(kind).run).splitlines())
        return [ln for ln in lines if ln and not ln.startswith("#")]

    diff = [d for d in difflib.ndiff(code("rollout"), code("rollout_hybrid")) if d[0] in "+-"]
    assert {d[2:] for d in diff if d[0] == "-"} == FAMILY
    added = [d[2:] for d in diff if d[0] == "+"]
    assert len(added) < 50
    reads = re.compile(r"^(values|window|setup_s|delta|records|s|c0|c1|t0|t1|c_warm|c_end|n_comp|n_hit|seconds|trace|trace_counters)\b[^=(]*=[^=]")
    assert [ln for ln in added if reads.match(ln)] == []


def test_phase_line_reads_the_loop_histograms():
    rh = bench().cell_kind("rollout_hybrid")
    p0 = {"device_wait": [10.0, 20.0, {0.5: 18.0, 1.0: 20.0, float("inf"): 20.0}], "dispatch": [0.1, 20.0, {}]}
    p1 = {"device_wait": [40.0, 70.0, {0.5: 18.0, 1.0: 69.0, 5.0: 70.0, float("inf"): 70.0}], "dispatch": [0.35, 70.0, {}], "other": [0.0001, 70.0, {}]}
    line = rh.phase_line(p0, p1)
    assert line == "device_wait 30.000s, dispatch 0.250s; 50 passes, the slowest device_wait at or under 5.0s"
    assert rh.phase_line(p1, p1) == "no pass"


@pytest.fixture(scope="module")
def recorded():
    load_run()
    from benchlib import trace_reduce

    path = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
    return {"trace": trace_reduce.load(path), "xplane": path}


def test_named_scope_reader_on_a_recorded_trace(recorded):
    """The new reader takes its scopes from the metric file: with the scopes of
    an accepted metric it reads that metric's number from PR 24's recorded
    trace; with this PR's scopes, which that program lacks, it reads nothing."""
    b = bench()
    named, old = b.reader("scope_share_named"), b.reader("scope_share")
    for name in ("decode_attn_pct", "decode_head_sampler_pct"):
        m = b.layer_metric(name)
        assert named.read({**m, "reader": "scope_share_named"}, recorded) == pytest.approx(old.read(m, recorded), rel=1e-12)
    cov = b.layer_metric("scope_coverage_pct.rollout-hybrid")
    assert set(cov["scopes"]) >= {"ssm_proj", "ssm_conv", "ssm_state", "state_write", "attn", "mlp"}
    assert named.read(cov, recorded) == pytest.approx(old.read(b.layer_metric("scope_coverage_pct.rollout"), recorded))
    assert named.read(b.layer_metric("decode_ssm_pct"), recorded) is None
    assert named.read(b.layer_metric("decode_ssm_pct"), {"trace": None}) is None


def test_state_roofline_arithmetic_on_a_recorded_trace(recorded):
    """The least time by hand: live slots x steps x 2 x bytes a slot over 819
    GB/s, against the device seconds of the named scope (here ``attn`` of the
    recorded Qwen trace stands in for ``ssm_state``, which that program lacks)."""
    from benchlib import trace_reduce

    b = bench()
    metric = {**b.layer_metric("decode_ssm_state_roofline"), "scopes": ["attn"]}
    by, secs = b.reader("scope_share_named").scope_seconds(recorded, ["attn"], metric["within_module"])
    _, runs = trace_reduce.matched(recorded["trace"], "modules", metric["steps_module_pattern"])
    facts = {
        **recorded, "cfg": b.cell(CELL)["model"], "server": {"decode_steps": 32}, "device_kind": "TPU v5 lite",
        "trace_span": (10.0, 18.0),
        "gauges": [{"t": 9.0, "active_slots": 64}, {"t": 11.0, "active_slots": 20}, {"t": 17.0, "active_slots": 28}, {"t": 19.0, "active_slots": 0}],
    }
    want = 100.0 * (24 * runs * 32 * 2 * 76_437_504 / 819e9) / secs
    assert runs > 0 and secs > 0 and by == {"attn": secs}
    assert b.reader("state_roofline").read(metric, facts) == pytest.approx(want, rel=1e-9)
    assert b.reader("state_roofline").read(b.layer_metric("decode_ssm_state_roofline"), facts) is None  # no such scope there
    assert b.reader("state_roofline").read(metric, {**facts, "gauges": []}) is None
