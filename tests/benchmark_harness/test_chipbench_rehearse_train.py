"""The train cell rehearsed on the CPU at tiny size through the same ``run.py``
code path behind a test-only size override; traced, so that one run reads the
end-to-end values and the per-layer metrics. The output check holds the
trainer's own step (loss, gradient from AdamW's first moment, parameter change)
to the float32 reference's within float32 rounding."""

from chipbench_util import TRAIN_LIMITS, check_rehearsal, rehearse


def test_train_cell_rehearses_on_cpu(tmp_path):
    res = rehearse("train-1.5b-packed4k", 1, tmp_path)
    check_rehearsal("train-1.5b-packed4k", 1, res)
    chk = res["rehearsal"]["check"]
    assert all(chk[k] <= TRAIN_LIMITS[k] for k in TRAIN_LIMITS)
    assert chk["compared"] > 10_000 and chk["moved"] > 0.9 * chk["compared"]  # float32 parameters move, bf16 ones barely
