"""The cell ``rollout-kanana-2-30b-a3b-ep8-grpo`` rehearsed on the CPU at a
tiny size of its configuration's shape (float32, the gather path, 8 slots,
experts 0-3 of 8, latent rows of 136 in 256 lanes) through the same
``run.py`` code path as a chip run, traced, behind the test-only size
override: warm-up waves, the window, the drain, the extra counters and the
output check against the plain reference, which computes every token's
attention in the first form and routes for itself; then the control.
Nothing here is a speed."""

import json

from chipbench_kanana2_util import CELL, rehearse
from chipbench_util import bench


def test_kanana2_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms", "moe_load_max_over_mean"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing over latent pages
    assert 1.0 <= res["metrics"]["moe_load_max_over_mean"]["value"] <= 8.0  # over the 8 experts the router scores
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 2e-6  # float32 on both sides, the same experts picked
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 2e-5
    json.loads(json.dumps(res))


def test_kanana2_rollout_control_comes_out_not_correct(tmp_path):
    """The FFN, expert, shared and the four latent-attention matrices rounded
    to int8 per output channel, in the program's place: not correct. Sound
    float32 rehearsals read a mean |logprob - reference| under 2e-6; the
    control reads 1e-4 and more."""
    res = rehearse(0, tmp_path, control=True, limit=1e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-4 and chk["max_abs"] > chk["median_abs"]
