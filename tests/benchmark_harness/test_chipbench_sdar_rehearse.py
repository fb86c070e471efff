"""The cell ``rollout-sdar-30b-a3b-d7-block4-grpo`` rehearsed on the CPU at a
tiny size of its configuration's shape (float32, the gather path, 8 slots, 3
layers, 8 experts top-2, blocks of 4 at two denoise passes and a commit pass)
through the same ``run.py`` code path as a chip run, traced, behind the
test-only size override: warm-up waves, the window, the drain, the extra
counters and ``/statusz`` fields, the ids-only output check against the plain
reference, then the kind's probe of all three rules with the replies kept
whole; then the control, which must read not correct by the inner check AND by
the probe's other order; and a reply without its trace. Nothing here is a
speed."""

import json

import numpy as np
import pytest
from chipbench_sdar_util import CELL, rehearse
from chipbench_util import bench, load_run


def test_sdar_rollout_cell_rehearses_on_cpu(tmp_path):
    res = rehearse(1, tmp_path)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["window_s"] > 0
    cell = bench().cell(CELL)
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in cell["end_to_end"])
    # device-trace metrics find no device plane on the CPU and are left out; the program counters are read
    have = set(res["metrics"])
    assert {"batch_occupancy_pct", "prefix_hit_pct", "moe_load_max_over_mean", "block_passes_per_token_pct", "block_commit_pass_pct"} <= have <= {m["name"] for m in cell["per_layer"]}
    # whole blocks take 3 slot-passes a 4 tokens; a request's first and last block emit fewer for the same passes, and at this size they are most blocks
    assert 75.0 <= res["metrics"]["block_passes_per_token_pct"]["value"] < 200.0 and 33.0 <= res["metrics"]["block_commit_pass_pct"]["value"] <= 50.0
    chk = res["rehearsal"]["check"]
    # prompts of 10 tokens (2 past a block boundary: the ids alone give every pass's state): float32 on both sides
    assert chk["n"] == 4 and chk["mean_abs"] < 1e-5 and chk["max_abs"] < 1e-4
    assert chk["probe_requests"] == 12 and chk["probe_failed"] == 0 and chk["trace_shape"] is True
    assert set(chk["trace_abs_by_rule"]) == {"sequential", "low_confidence_static", "low_confidence_dynamic"} and chk["trace_abs"] < 1e-5 and chk["trace_max"] < 1e-4
    # prompts of 9-12 tokens in the probe: what the ids-only reference misses in the prompt-end block is measured, and is no rounding
    assert chk["prompt_end_abs_sum"] > 1e-3 and chk["prompt_end_share_of_mean"] > 0
    json.loads(json.dumps(res))


def test_sdar_rollout_control_comes_out_not_correct_by_both_checks(tmp_path):
    """The attention and expert matrices rounded to int8 per output channel in
    the program's place: the inner check's logprobs miss the limit that sound
    float32 rehearsals keep by two orders. And the probe, whose engine is
    sound, feeds the reference each block's pass numbers reversed: it misses
    its limit by as much."""
    res = rehearse(0, tmp_path, control=True, limit=2e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-3 and chk["max_abs"] > chk["median_abs"]
    assert chk["probe_failed"] == 0 and chk["trace_abs"] > 1e-2 and chk["trace_shape"] is True  # the replies are sound; the order told is not


def test_the_probes_shapes_and_a_reply_without_its_trace():
    load_run()
    from benchlib.cells import rollout_family_trace as kind

    assert kind.blocks_of(9, [0, 0, 1, 0, 0, 1, 1, 0, 0], 4) == [[0, 0, 1], [0, 0, 1, 1], [0, 0]]
    assert kind.blocks_of(8, [0, 0, 1, 1, 0], 4) == [[0, 0, 1, 1], [0]]
    assert kind.reversed_blocks(9, [0, 0, 1, 0, 0, 1, 1, 0, 1], 4) == [1, 0, 0, 1, 1, 0, 0, 1, 0]
    ok = kind.shape_ok
    assert ok("sequential", [0, 0, 1, 1], 2) and ok("sequential", [0, 0, 1], 2) and ok("sequential", [0], 2) and not ok("sequential", [0, 1, 0, 1], 2)
    assert ok("sequential", [0, 1, 2, 3], 1) and not ok("sequential", [0, 0, 1, 1], 1)
    assert ok("low_confidence_static", [1, 0, 0, 1], 2) and ok("low_confidence_static", [0, 1, 0], 2) and not ok("low_confidence_static", [0, 1, 1, 1], 2)
    assert not ok("low_confidence_static", [0, 0, 0, 1], 2) and not ok("low_confidence_static", [0, 2, 0, 2], 2)  # three in a pass; a pass skipped
    assert ok("low_confidence_dynamic", [2, 0, 1, 0], 2) and ok("low_confidence_dynamic", [0, 0, 0, 0], 2) and not ok("low_confidence_dynamic", [0, 2, 2, 0], 2)
    assert not ok("sequential", [], 2) and not ok("low_confidence_dynamic", [-1, 0], 2)
