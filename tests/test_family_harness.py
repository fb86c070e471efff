"""``tests/family_harness.py`` itself: the decode step of a configuration, page
size and path is built and traced once however many tests walk it, another
path or page size is another program, and a walk through the cache leaves
every slot but its own as it found it."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.models import hybrid  # noqa: E402
from tests import family_harness as fh  # noqa: E402

PSZ = 16
CFG = {**ou.tiny_model(ou.KINDS[:4]), "vocab_size": 384}  # one period of the olmo tiny model at a vocabulary no other test has: a configuration this process has not met


@pytest.fixture(scope="module")
def tiny():
    return ou.model_config(CFG), ou.make_params(CFG, 3)


@pytest.fixture
def traces(monkeypatch):
    """The calls of ``hybrid.forward_decode_paged``: one a trace of the harness's step, none where a traced program runs."""
    seen = []
    real = hybrid.forward_decode_paged

    def counted(*args, **kw):
        seen.append((kw["page_size"], kw["use_kernel"]))
        return real(*args, **kw)

    monkeypatch.setattr(hybrid, "forward_decode_paged", counted)
    return seen


def test_two_walks_at_one_configuration_trace_the_step_once(tiny, traces):
    mcfg, params = tiny
    ids = np.random.default_rng(0).integers(0, 384, 20)
    first, _ = fh.through_the_cache(mcfg, params, ids, 9, 16, page_size=PSZ)
    again, _ = fh.through_the_cache(mcfg, ou.make_params(CFG, 4), ids, 9, 16, page_size=PSZ)  # other weights: arguments
    assert traces == [(PSZ, False)] and first.shape == again.shape == (12, 384) and np.abs(first - again).max() > 1e-3
    assert fh.decode_step(mcfg, PSZ, False) is fh.decode_step(mcfg, PSZ, False)


def test_another_path_or_page_size_is_another_program(tiny):
    mcfg, _ = tiny
    steps = {fh.decode_step(mcfg, PSZ, False), fh.decode_step(mcfg, PSZ, True), fh.decode_step(mcfg, 8, False)}
    assert len(steps) == 3
    assert fh.prefill_program(mcfg, PSZ) is fh.prefill_program(mcfg, PSZ) and fh.prefill_program(mcfg, 8) is not fh.prefill_program(mcfg, PSZ)


def test_the_other_slots_come_back_as_they_went_in(tiny):
    """A marked cache: prefill into slot 2 and five decode steps of it leave
    the state rows of slots 0, 1 and 3, and every page but slot 2's and the
    trash page, bit for bit; slot 2's own state and pages moved."""
    mcfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 384, 14)
    cache, table = fh.fresh_cache(mcfg, 4, 2, PSZ)
    assert table.tolist() == [[1, 2], [3, 4], [5, 6], [7, 8]] and cache["k"].shape[2] == 9
    table[[0, 1, 3]] = 0  # they hold no request: a step's rows of theirs go to the trash page
    marked = {k: v + jnp.asarray(3.0, v.dtype) for k, v in cache.items()}
    before = {k: np.asarray(v) for k, v in marked.items()}
    cache = fh.prefill_into_slot(mcfg, params, marked, table, [(2, ids[:10])], 16, PSZ)
    step, active = fh.decode_step(mcfg, PSZ, False), jnp.arange(4) == 2
    for t in range(9, 14):
        tokens, positions = jnp.zeros(4, jnp.int32).at[2].set(int(ids[t])), jnp.zeros(4, jnp.int32).at[2].set(t)
        _, cache = step(params, tokens, positions, cache, jnp.asarray(table), active)
    after = {k: np.asarray(v) for k, v in cache.items()}
    for leaf in ("gdn", "conv"):  # [layers, slots, ...]
        assert np.array_equal(after[leaf][:, [0, 1, 3]], before[leaf][:, [0, 1, 3]]) and not np.array_equal(after[leaf][:, 2], before[leaf][:, 2])
    for leaf in ("k", "v"):  # [layers, heads, pages, ...]: slot 2 holds pages 5 and 6
        others = [1, 2, 3, 4, 7, 8]
        assert np.array_equal(after[leaf][:, :, others], before[leaf][:, :, others]) and not np.array_equal(after[leaf][:, :, 5], before[leaf][:, :, 5])
