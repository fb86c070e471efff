"""The control of the ``lfm2`` rollout cell's output check at a size a test
can hold: int8 KV pages and the FFN weights (dense and expert matrices)
rounded to int8 per output channel, in the program's place, has to come out
as not correct. Sound float32 rehearsals read a mean |logprob - reference|
under 2e-6; the control reads 1e-3 and more (rounded weights move every
logit, and some tokens' experts with them). The chip runs of the control at the
cell's own size are in PERF.md."""

from chipbench_lfm2_util import CELL, rehearse
from chipbench_util import bench


def test_family_rollout_control_comes_out_not_correct(tmp_path):
    res = rehearse(0, tmp_path, control=True, limit=1e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-4 and chk["max_abs"] > chk["median_abs"]
