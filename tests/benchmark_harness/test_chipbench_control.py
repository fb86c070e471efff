"""The control of the output check at a size a test can hold: the nearest
precision below the configured one, in the program's place, has to come out
as not correct. Rollout cells switch on the program's own int8 weights and
int8 KV; the train cell puts the reference with int8 matmuls, forward and
backward, in the trainer's place. The chip runs of the control at the cells'
own sizes are in PERF.md."""

from chipbench_util import TRAIN_LIMITS, bench, rehearse


def test_rollout_control_rehearsal_comes_out_not_correct(tmp_path):
    cell = "rollout-7b-d14-grpo"  # the second rollout cell's rehearsal, untraced
    res = rehearse(cell, 0, tmp_path, control=True)
    assert res["correct"] is False and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench().cell(cell)["end_to_end"]}
    assert res["rehearsal"]["check"]["mean_abs"] > 1e-4  # sound: 1e-6, limit 1e-5


def test_train_control_rehearsal_comes_out_not_correct(tmp_path):
    res = rehearse("train-1.5b-packed4k", 0, tmp_path, control=True)
    assert res["correct"] is False and res["metrics"] == {}
    chk = res["rehearsal"]["check"]
    # int8 moves the gradient a hundred times further than its limit; the loss barely
    assert chk["grad_rel"] > 100 * TRAIN_LIMITS["grad_rel"]
    assert chk["update_rel"] > 10 * TRAIN_LIMITS["update_rel"]
