"""The ``rollout_family`` cell kind rehearsed on the CPU at a tiny size of
the ``lfm2-8b-a1b-d14`` configuration's shape (float32, the gather path, 8
slots, 8 experts) through the same ``run.py`` code path as a chip run,
traced, behind the test-only size override: warm-up waves, the window, the
drain, the extra counters and the output check against the plain reference,
which routes for itself. Nothing here is a speed."""

import json

from chipbench_lfm2_util import CELL, rehearse
from chipbench_util import bench


def test_family_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms", "moe_load_max_over_mean"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing to a model with conv state
    assert 1.0 <= res["metrics"]["moe_load_max_over_mean"]["value"] <= 8.0  # 8 experts: between even and one expert only
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 2e-6  # float32 on both sides, the same experts picked
    assert chk["share_over_0.1"] == 0.0 and chk["max_abs"] < 2e-5
    json.loads(json.dumps(res))
