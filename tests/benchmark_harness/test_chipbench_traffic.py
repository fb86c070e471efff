"""The traffic generator's group streams: deterministic in the seed, the same work
in every seed. (Lengths and the train batch: ``test_chipbench_traffic_batch``.
No file here holds more than 8 tests: xdist's ``loadfile`` hands files out by
test count, so every seed file of 9 tests or more, the timing-sensitive ones
among them, is handed out exactly as without this directory.)"""

import numpy as np
import pytest
from chipbench_util import bench


@pytest.fixture(scope="module")
def mixes():
    b = bench()
    return b.traffic("grpo-reasoning"), b.traffic("grpo-packed-4k")


def _groups(mix, seed, client, n):
    from benchlib import traffic

    s = traffic.GroupStream(mix, seed, client, 151936)
    return [s.next() for _ in range(n)]


def _shapes(groups):
    return [(len(g["prompt"]), tuple(g["new_tokens"])) for g in groups]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_groups(mixes, seed):
    mix, _ = mixes
    a, b = _groups(mix, seed, 3, 4), _groups(mix, seed, 3, 4)
    assert a == b
    assert _groups(mix, seed, 4, 1) != a[:1]  # another client, another stream
    assert _groups(mix, seed + 1, 3, 1) != a[:1]  # another seed, other tokens


def test_every_seed_offers_the_same_work(mixes):
    """The lengths are streams of the mix's shape_seed, client k sends
    stream k; a seed only draws the tokens."""
    mix, _ = mixes
    per_seed = [{c: _shapes(_groups(mix, seed, c, 3)) for c in range(16)} for seed in (1, 2, 2**31 + 7)]
    assert per_seed[0] == per_seed[1] == per_seed[2]
    assert len({tuple(v) for v in per_seed[0].values()}) == 16  # the streams differ from each other
    other = dict(mix, shape_seed=mix["shape_seed"] + 1)
    assert _shapes(_groups(other, 1, 0, 3)) != _shapes(_groups(mix, 1, 0, 3))


def test_group_shape_and_clipping(mixes):
    mix, _ = mixes
    for g in [g for c in range(16) for g in _groups(mix, 11, c, 12)]:
        assert len(g["new_tokens"]) == mix["group_size"] == 8
        assert mix["prompt_len"]["lo"] <= len(g["prompt"]) <= mix["prompt_len"]["hi"]
        assert all(1 <= n <= mix["output_len"]["hi"] for n in g["new_tokens"])
        assert all(len(g["prompt"]) + n <= mix["max_total"] for n in g["new_tokens"])
        assert all(0 <= t < 151936 for t in g["prompt"][:16])


def test_first_wave_is_scaled_down(mixes):
    mix, _ = mixes
    first = np.mean([np.mean(_groups(dict(mix, shape_seed=s), 1, 0, 1)[0]["new_tokens"]) for s in range(200)])
    later = np.mean([np.mean(_groups(dict(mix, shape_seed=s), 1, 0, 2)[1]["new_tokens"]) for s in range(200)])
    assert 0.35 * later < first < 0.65 * later  # u~U(0,1): about half
