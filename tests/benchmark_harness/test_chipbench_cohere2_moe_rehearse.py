"""The cell ``rollout-command-a-plus-ep16-d4-longctx-grpo`` rehearsed on the
CPU at a tiny size of its configuration's shape (float32, the gather path, 8
slots, one period S S S F with a window of 16 in two-page rings, 4 of 16
experts held, four shared) through the same ``run.py`` code path as a chip
run, traced, behind the test-only size override: warm-up waves, the window,
the drain, the extra counters and ``/statusz`` fields, the output check
against the plain reference (every checked context is several windows long,
so a ring one token off shows in the next logprob); then the control, whose
logprobs must read not correct. Nothing here is a speed."""

import json

from chipbench_cohere2_moe_util import CELL, rehearse
from chipbench_util import bench


def test_cohere2_moe_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms", "moe_load_max_over_mean"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing beside window rings
    assert res["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 1e-5  # float32 on both sides
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 1e-4
    json.loads(json.dumps(res))


def test_cohere2_moe_rollout_control_comes_out_not_correct(tmp_path):
    """The attention, expert and shared-expert matrices rounded to int8 per
    output channel in the program's place (pages and rings stay as they are:
    ``serving_limits`` refuses int8 pages beside rings): the logprobs miss the
    limit that sound float32 rehearsals keep by two orders."""
    res = rehearse(0, tmp_path, control=True, limit=2e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-3 and chk["max_abs"] > chk["median_abs"]
