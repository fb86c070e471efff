"""The traffic generator: the stated length distributions are the ones it
draws, the train batch is three full rows whatever the seed, and the warm-up
plan touches every prefill size."""

import numpy as np
import pytest
from chipbench_util import bench


@pytest.fixture(scope="module")
def mixes():
    b = bench()
    return b.traffic("grpo-reasoning"), b.traffic("grpo-packed-4k")


def test_length_distributions(mixes):
    from benchlib import traffic

    mix, _ = mixes
    rng = traffic.rng_for(5, 1)
    p = traffic.draw_lengths(mix["prompt_len"], rng, 50_000)
    o = traffic.draw_lengths(mix["output_len"], rng, 50_000)
    assert p.min() >= 128 and p.max() <= 1024
    assert abs(np.median(p) - np.sqrt(128 * 1024)) < 12  # log-uniform: geometric middle
    assert o.min() >= 16 and o.max() == 3072
    assert abs(np.median(o) - 384) < 12
    assert abs(np.std(np.log(o[(o > 16) & (o < 3072)])) - 1.0) < 0.06  # sigma 1.0, less the clipped tails
    assert traffic.draw_lengths({"dist": "fixed", "value": 9}, rng, 3).tolist() == [9, 9, 9]
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf", "lo": 1, "hi": 2}, rng, 1)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 99])
def test_packed_batch_is_three_full_rows_whatever_the_seed(mixes, seed):
    from benchlib import traffic

    from areal_tpu.utils.data import pad_sequences_to_tensors
    from areal_tpu.utils.grid import pack_grid

    mix, pm = mixes
    seqs = traffic.packed_batch(pm, mix, seed, 151936)
    lens = sorted(len(s["input_ids"]) for s in seqs)
    assert lens == sorted(t for _, t in traffic.packed_shapes(pm, mix))  # shapes do not move with the seed
    assert sum(lens) == pm["rows"] * pm["row_len"] == 12288
    grid = pack_grid(pad_sequences_to_tensors(seqs), row_len=pm["row_len"], pad_rows_to=1)
    assert grid.n_rows == pm["rows"] == len(traffic.ffd_rows(lens, pm["row_len"]))
    again = traffic.packed_batch(pm, mix, seed, 151936)
    assert all((a["input_ids"] == b["input_ids"]).all() for a, b in zip(seqs, again))
    for s in seqs:
        n = len(s["input_ids"])
        assert s["loss_mask"].shape == s["advantages"].shape == s["old_logprobs"].shape == (n,)
        assert s["loss_mask"][0] == 0 and s["loss_mask"][-1] == 1


def test_sweep_plan_touches_every_prefill_size(mixes):
    """15 prompts a bucket split into the engine's batched-prefill sizes
    8+4+2+1; the repeats walk the slot-scatter sizes up to the slot count."""
    import importlib.util
    import os

    from chipbench_util import CHIP

    spec = importlib.util.spec_from_file_location("chipbench_rollout", os.path.join(CHIP, "benchlib", "cells", "rollout.py"))
    rollout = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rollout)
    mix, _ = mixes
    waves = rollout.sweep_plan(mix, {"slots": 128, "max_seq_len": 4096})
    assert waves[0]["prompt_lens"] == [256, 512, 768, 1024] and waves[0]["per_bucket"] == 15
    assert [w["repeat"] for w in waves[1:]] == [1, 2, 4, 8, 16, 32, 64, 128]
    small = rollout.sweep_plan(mix, {"slots": 64, "max_seq_len": 4096})
    assert small[0]["per_bucket"] * 4 <= 64 and small[-1]["repeat"] == 64
