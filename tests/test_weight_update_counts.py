"""Counts of zero-pause weight updates under load, and of the wire format.

Several staged updates in a row while requests decode: none is aborted, the
version ends at the number of updates, a request that spans the commits
carries every version in order, and what was staged is as many bytes as the
served tree. How long the commit fence is against the staging window on a
chip is not measured here (docs/weight_sync.md)."""

import struct
import threading
import time

import jax
import ml_dtypes
import numpy as np
import pytest

from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.server import decode_weight_bucket, encode_weight_bucket, flatten_params
from areal_tpu.observability import hw_accounting as hw

from tpu_testing import tiny_decode_engine

N_UPDATES = 3


def _engine(stage_target: str) -> DecodeEngine:
    return tiny_decode_engine(max_seq_len=1024, page_size=128, weight_stage_target=stage_target)


def _buckets(eng: DecodeEngine, delta: float) -> list[dict]:
    items = sorted(flatten_params(jax.tree.map(lambda x: np.asarray(x) + delta, eng.params)).items())
    return [dict(items[: len(items) // 2]), dict(items[len(items) // 2 :])]


def _wait_generated(eng: DecodeEngine, n: int) -> None:
    deadline = time.monotonic() + 120
    while eng.stats["generated_tokens"] < n:
        assert time.monotonic() < deadline, "generation stalled"
        time.sleep(0.005)


@pytest.mark.parametrize("stage_target", ["device", "host"])
def test_updates_under_load_abort_nothing_and_end_at_their_count(stage_target):
    eng = _engine(stage_target)
    eng.start()
    try:
        done = threading.Event()
        got = []

        def cb(resp):
            got.append(resp)
            if len(got) == 3:
                done.set()

        for i in range(3):
            g = GenerationHyperparameters(max_new_tokens=1000, temperature=1.0, ignore_eos=True)
            eng.submit(ModelRequest(input_ids=[3 + i, 5, 7], gconfig=g), cb)
        _wait_generated(eng, 12)
        for u in range(1, N_UPDATES + 1):
            at_begin = eng.stats["generated_tokens"]
            eng.begin_staged_update()
            first, second = _buckets(eng, 0.01 * u)
            eng.stage_weight_bucket(first)
            _wait_generated(eng, at_begin + 8)  # decoding goes on between buckets
            eng.stage_weight_bucket(second)
            eng.commit_staged_weights(version=u)
            assert eng.last_update_gen_tokens >= 8
        assert done.wait(300), f"{len(got)}/3 finished"
        assert eng.get_version() == N_UPDATES
        assert eng.stats["aborted"] == 0 and eng.stats.get("preempted", 0) == 0
        for resp in got:
            assert resp.stop_reason == StopReason.LENGTH.value
            assert len(resp.output_versions) == 1000
            assert resp.output_versions == sorted(resp.output_versions)
            # in flight across every commit: no version is skipped
            assert sorted(set(resp.output_versions)) == list(range(N_UPDATES + 1))
    finally:
        eng.stop()


@pytest.mark.parametrize("stage_target", ["device", "host"])
def test_staged_bytes_are_the_served_tree_s(stage_target):
    eng = _engine(stage_target)
    served = hw.tree_bytes(eng.params)
    assert served == sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(eng.params))
    assert eng.hbm_ledger()["components"]["staged_update"] == 0
    eng.begin_staged_update()
    first, second = _buckets(eng, 0.5)
    eng.stage_weight_bucket(first)
    part = eng.hbm_ledger()["components"]["staged_update"]
    assert 0 < part < served
    eng.stage_weight_bucket(second)
    assert eng.hbm_ledger()["components"]["staged_update"] == served
    on_host = [isinstance(v, np.ndarray) for v in eng._staged_flat.values()]
    assert all(on_host) if stage_target == "host" else not any(on_host)
    eng.abort_staged_update()
    assert eng.hbm_ledger()["components"]["staged_update"] == 0 and eng.get_version() == 0


def test_weight_bucket_wire_bytes_and_round_trip():
    """8-byte header length, a json header, then each array's raw bytes in
    order: bfloat16 travels at half the float32 bytes, and the body decodes
    to the arrays that went in."""
    rng = np.random.default_rng(5)
    f32 = rng.normal(0, 1, (64, 48)).astype(np.float32)
    entries = [
        ("layers/wq", f32), ("layers/wo", f32[:, :24].astype(ml_dtypes.bfloat16)),
        ("final_norm", np.ones((48,), np.float32)),
    ]
    body = encode_weight_bucket(entries)
    (hlen,) = struct.unpack_from("<Q", body, 0)
    payload = 64 * 48 * 4 + 64 * 24 * 2 + 48 * 4
    assert len(body) == 8 + hlen + payload
    flat = decode_weight_bucket(body)
    assert list(flat) == [name for name, _ in entries]
    for name, arr in entries:
        assert flat[name].dtype == arr.dtype and flat[name].shape == arr.shape
        np.testing.assert_array_equal(np.asarray(flat[name], np.float32), np.asarray(arr, np.float32))
    with pytest.raises(AssertionError, match="bucket size mismatch"):
        decode_weight_bucket(body + b"\0")
