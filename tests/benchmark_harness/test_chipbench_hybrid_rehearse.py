"""The ``rollout_hybrid`` cell kind rehearsed on the CPU at a tiny size of the
configuration's shape (float32, the gather path, 8 slots) through the same
``run.py`` code path as a chip run, traced, behind the test-only size
override: warm-up waves, the window, the drain and the output check against
the plain reference. Nothing here is a speed."""

import json

from chipbench_hybrid_util import CELL, rehearse
from chipbench_util import bench


def test_hybrid_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out
    assert {"batch_occupancy_pct", "prefix_hit_pct", "ttft_p95_ms"} <= set(res["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert res["metrics"]["prefix_hit_pct"]["value"] == 0.0  # the radix cache serves nothing to a recurrent model
    chk = res["rehearsal"]["check"]
    assert chk["n"] == 4 and chk["radix_hits"] == 0 and chk["mean_abs"] < 1e-6  # float32 on both sides
    # the state probe: two requests on the idle engine, two slots' state changed, each the reference's after exactly the tokens consumed
    assert (chk["probe_requests"], chk["probe_failed"], chk["slots_changed"]) == (2, 0, 2) and chk["state_tokens"] == 2 * (12 + 24 - 1)
    assert 0 < chk["state_rel_head_mean"] <= chk["state_rel"] < 2e-6  # float32 on both sides; a token too many or too few reads 1e-2 and more
    json.loads(json.dumps(res))
