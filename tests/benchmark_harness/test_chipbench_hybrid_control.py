"""The control of the hybrid rollout cell's output check at a size a test can
hold: the recurrent state kept in bfloat16 and the attention layers' KV pages
in int8, in the program's place, has to come out as not correct. Sound
float32 rehearsals read a mean |logprob - reference| of 7e-8 and a state
error of 3e-7; the control reads 2e-6 and 3e-3. The chip runs of the control at the cell's own size
are in PERF.md."""

from chipbench_hybrid_util import CELL, rehearse
from chipbench_util import bench


def test_hybrid_rollout_control_comes_out_not_correct(tmp_path):
    # the logprob limit is let pass here, as it does on the chip (PERF.md section 4): the state's limit has to refuse the control
    res = rehearse(0, tmp_path, control=True, limit=1.0, limit_state=1e-5)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(CELL)["end_to_end"]}
    chk = res["rehearsal"]["check"]
    assert chk["mean_abs"] > 1e-6 and chk["slots_changed"] == 2
    assert chk["state_rel"] > 1e-3  # a bfloat16 state against float32 everywhere else: sound rehearsals read 3e-7
