"""``DecodeEngine.precompile()``'s two arguments and the sets it warms from."""

import collections
import logging

import pytest

from areal_tpu.inference.decode_programs import PREFILL_SIZES

from tpu_testing import tiny_decode_engine as _engine


def test_a_spent_budget_compiles_nothing_and_says_what_it_deferred():
    eng = _engine()
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    log = logging.getLogger("areal_tpu.decode_engine")  # does not propagate to pytest's capture
    log.addHandler(handler)
    try:
        eng.precompile(budget_s=0.0)
    finally:
        log.removeHandler(handler)
    assert eng.programs._fn_cache == {}
    assert "precompile budget 0s spent after 0 programs; 21 deferred to lazy compile" in said


def test_prompt_buckets_narrow_the_prefill_programs_only():
    eng = _engine()
    eng.precompile(prompt_buckets=[256])
    kinds = collections.Counter(k[0] for k in eng.programs._fn_cache)
    assert kinds == {"chunk": 4, "upd": 3, "clamp": 3, "pagecopy": 3, "prefill": len(PREFILL_SIZES)}
    assert {k[2] for k in eng.programs._fn_cache if k[0] == "prefill"} == {256}
    assert eng.programs.keys() == set(eng.programs.warm_keys(prompt_buckets=[256]))  # what was named is what was built


@pytest.mark.parametrize(
    "cfg,buckets,wps,scatters",
    [
        (dict(max_batch_size=4, max_seq_len=512, attn_window_step=512), [256, 512], [32], [1, 2, 4]),
        # a window step under the context: one chunk program a window reached
        (dict(max_batch_size=6, max_seq_len=1024, attn_window_step=256), [256, 512, 768, 1024], [16, 32, 48, 64], [1, 2, 4, 6]),
        (dict(max_batch_size=1, max_seq_len=256, attn_window_step=512), [256], [16], [1]),
    ],
    ids=["ctx512", "ctx1024-step256", "one-slot"],
)
def test_reachable_sets_follow_the_configuration(cfg, buckets, wps, scatters):
    eng = _engine(**cfg)
    assert eng.programs.reachable_prompt_buckets() == buckets
    assert eng.programs.reachable_chunk_wps() == wps
    assert eng.programs.reachable_scatter_sizes() == scatters
