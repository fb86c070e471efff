"""The peaks table and the op and byte functions against values computed by
hand at the Qwen2.5-1.5B shapes (hidden 1536, intermediate 8960, 28 layers,
12 query / 2 KV heads of 128, vocabulary 151936, tied)."""

import json
import os

import pytest
from chipbench_util import CHIP, load_run


@pytest.fixture(scope="module")
def cfg():
    load_run()
    with open(os.path.join(CHIP, "configs", "qwen2.5-1.5b.json")) as f:
        return json.load(f)


def test_peaks_table_and_unknown_device():
    load_run()
    from benchlib import peaks

    p = peaks.peaks_for("TPU v5 lite")
    assert (p["flops_bf16"], p["ops_int8"], p["hbm_bytes_s"], p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_matmul_params_and_bytes(cfg):
    from benchlib import peaks

    mp = peaks.matmul_params(cfg)
    # q and o: 1536x1536 each; k and v: 1536x256 each; gate, up, down: 1536x8960 each
    assert mp["layer"] == 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960 == 46_792_704
    assert mp["layers"] == 28 * 46_792_704 == 1_310_195_712
    assert mp["head"] == 151936 * 1536 == 233_373_696
    # + per layer: 2 norms (1536), q bias 1536, k and v bias 256 each; final norm 1536
    n_params = 1_310_195_712 + 28 * (2 * 1536 + 1536 + 512) + 1536 + 233_373_696
    assert peaks.weight_bytes(cfg) == 2 * n_params == 3_087_428_608
    assert peaks.kv_bytes_per_token(cfg) == 2 * 28 * 2 * 128 * 2 == 28_672


def test_decode_attention_cost(cfg):
    from benchlib import peaks

    ops, byts = peaks.decode_attention_cost(cfg, live_kv_tokens=100_000)
    assert ops == 4 * 128 * 12 * 28 * 100_000 == 17_203_200_000
    assert byts == 28_672 * 100_000
    r = peaks.roofline(ops, byts, seconds=0.01, peak=peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "memory"  # 6 ops a byte against a ridge of 240
    assert r["pct"] == pytest.approx(100 * (28_672e5 / 819e9) / 0.01)


def test_prefill_and_train_costs(cfg):
    from benchlib import peaks

    lens = [1000, 24]
    pairs = 1000 * 1001 / 2 + 24 * 25 / 2
    att = 4 * 128 * 12 * 28 * pairs
    assert peaks.attention_ops_causal(cfg, lens) == att
    ops, byts = peaks.prefill_cost(cfg, lens)
    assert ops == 2 * 1_310_195_712 * 1024 + att
    assert byts == 2 * 1_310_195_712 + 28_672 * 1024
    assert peaks.train_step_flops(cfg, lens) == 6 * (1_310_195_712 + 233_373_696) * 1024 + 3 * att
    d7 = json.load(open(os.path.join(CHIP, "configs", "qwen2.5-7b-d14.json")))
    assert peaks.kv_bytes_per_token(d7) == 28_672  # 14 layers x 4 KV heads
    assert peaks.weight_bytes(d7) == pytest.approx(8.7e9, rel=0.01)
