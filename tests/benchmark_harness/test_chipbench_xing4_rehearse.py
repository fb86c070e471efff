"""The cell ``rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo`` rehearsed on the
CPU at a tiny size of its configuration's shape (float32, the gather path, 8
slots, 2 dense + 4 expert layers on four residual streams, 4 of 16 experts
held, YaRN's original length 32 against contexts to 120) through the same
``run.py`` code path as a chip run, traced, behind the test-only size
override: warm-up waves, the window, the drain, the extra counters and
``/statusz`` fields, the output check against the plain reference; then the
control, whose logprobs must read not correct. Nothing here is a speed."""

import json

from chipbench_util import bench
from chipbench_xing4_util import CELL, rehearse


def test_xing4_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms", "moe_load_max_over_mean"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing over latent pages
    assert res["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 1e-5  # float32 on both sides
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 1e-4
    json.loads(json.dumps(res))


def test_xing4_rollout_control_comes_out_not_correct(tmp_path):
    """The dense, expert and shared FFN weights and the five latent-attention
    matrices rounded to int8 per output channel in the program's place
    (``Phi``, the coefficients' gains and biases and the pages as they are):
    the logprobs miss the limit that sound float32 rehearsals keep by two
    orders."""
    res = rehearse(0, tmp_path, control=True, limit=2e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-3 and chk["max_abs"] > chk["median_abs"]
