"""The cell ``rollout-phi-4-mini-flash-longctx-grpo`` rehearsed on the CPU at
a tiny size of its configuration's shape (float32, the gather path, 8 slots,
12 layers in the five kinds, a window of 8 against pages of 4 and contexts of
10-120: every request's rings wrap) through the same ``run.py`` code path as
a chip run, traced, behind the test-only size override: warm-up waves, the
window, the drain, the extra counters and ``/statusz`` fields, the output
check against the plain reference and the state probe; then the control,
whose logprobs AND whose state must both read not correct. Nothing here is a
speed."""

import json

from chipbench_phi4flash_util import CELL, rehearse
from chipbench_util import bench


def test_phi4flash_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing behind a recurrent state
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 5e-6  # float32 on both sides
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 5e-5
    assert chk["probe_requests"] == 2 and chk["probe_failed"] == 0 and chk["slots_changed"] == 2 and chk["state_rel"] < 1e-5
    json.loads(json.dumps(res))


def test_phi4flash_rollout_control_comes_out_not_correct_on_both_counts(tmp_path):
    """The MLP, selective-scan, attention and memory-unit matrices rounded to
    int8 per output channel in the program's place, and the S6 state held in
    bfloat16: the logprobs miss the limit that sound float32 rehearsals keep
    by two orders, and the state its limit by three."""
    res = rehearse(0, tmp_path, control=True, limit=2e-5, limit_state=1e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-3 and chk["max_abs"] > chk["median_abs"]
    assert chk["state_rel"] > 1e-3 and chk["probe_failed"] == 0
