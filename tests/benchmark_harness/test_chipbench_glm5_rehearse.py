"""The cell ``rollout-glm-5-ep16-d6-longctx-grpo`` rehearsed on the CPU at a
tiny size of its configuration's shape (float32, the gather path, 8 slots,
experts 0-3 of 8, latent rows of 136 in 256 lanes beside index keys of 128,
``index_topk`` 16 against contexts of 10-120) through the same ``run.py``
code path as a chip run, traced, behind the test-only size override: warm-up
waves, the window, the drain, the extra counters, the output check against
the plain reference and the selection probe (the first layer's cached index
keys and the selection made from them, held mid-decode); then the control,
whose logprobs AND whose probe must both read not correct. Nothing here is a
speed."""

import json

from chipbench_glm5_util import CELL, rehearse
from chipbench_util import bench


def test_glm5_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms", "moe_load_max_over_mean", "dsa_selected_pct"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing over latent pages
    assert 10.0 < res["metrics"]["dsa_selected_pct"]["value"] < 100.0  # 16 of a few dozen cached tokens, all of them under 16
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 2e-6  # float32 on both sides, the same tokens selected, the same experts picked
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 2e-5
    assert chk["probe_requests"] == 2 and chk["probe_failed"] == 0 and chk["key_rel"] < 1e-6 and chk["selected_common"] == 1.0
    assert chk["places"] >= 8 and chk["picked_a_place"] == 16.0 and chk["cached_tokens"] > 2 * 60
    json.loads(json.dumps(res))


def test_glm5_rollout_control_comes_out_not_correct_on_both_counts(tmp_path):
    """The FFN, expert, shared and the five latent-attention matrices rounded
    to int8 per output channel, in the program's place, and the last 16
    tokens in the selection's place: the logprobs miss the limit that sound
    float32 rehearsals keep by three orders, and the probe finds under
    two thirds of the reference's S_t."""
    res = rehearse(0, tmp_path, control=True, limit=1e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-3 and chk["max_abs"] > chk["median_abs"]
    assert chk["selected_common"] < 0.67 and chk["picked_a_place"] == 16.0 and chk["key_rel"] < 1e-6  # the keys are sound: the RULE is another
