"""Counts of the shared-prefix workload: what a warm wave prefills and hits.

Every prompt carries one long shared prefix and a short tail of its own. A
cold wave prefills from token zero; once its requests have published into
the radix tree, a warm wave of NEW tails over the same prefix prefills the
tails alone. Held here as counts (tokens prefilled, tokens hit, pages held
and returned); how much faster the warm wave is on a chip is not measured."""

import numpy as np
import pytest

from areal_tpu.api.config import PrefixCacheConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference.decode_engine import DecodeEngine

from tpu_testing import tiny_decode_engine


def _engine(page_size: int, slots: int, max_seq_len: int) -> DecodeEngine:
    return tiny_decode_engine(
        page_size=page_size, max_batch_size=slots, max_seq_len=max_seq_len, prefix_cache=PrefixCacheConfig(enabled=True)
    )


def _serve_wave(eng: DecodeEngine, prompts) -> None:
    """One admission wave, driven from this thread (no decode thread, so the
    counters are read between passes and never during one)."""
    done = []
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True)
    for ids in prompts:
        eng.submit(ModelRequest(input_ids=list(ids), gconfig=g), done.append)
    for _ in range(64):
        rows = eng._admit_pending()
        eng._apply_slot_updates(rows)
        eng._drain(eng._dispatch_chunk())
        if not any(t is not None for t in eng._slot_task) and not eng._backlog:
            break
    assert len(done) == len(prompts), f"{len(done)}/{len(prompts)} finished"


@pytest.mark.parametrize(
    "page_size,n_requests,prefix_pages,suffix_tokens",
    [(16, 4, 6, 40), (8, 2, 9, 13), (32, 3, 3, 50), (16, 1, 5, 1), (16, 4, 6, 16)],
    ids=["psz16x4", "psz8x2", "psz32x3", "one-token-tail", "page-aligned-tail"],
)
def test_warm_wave_prefills_the_tails_and_hits_the_prefix(page_size, n_requests, prefix_pages, suffix_tokens):
    prefix_tokens = prefix_pages * page_size
    eng = _engine(page_size, n_requests, max_seq_len=512)
    rng = np.random.default_rng(page_size + n_requests)
    prefix = rng.integers(0, 256, prefix_tokens).tolist()

    def wave():
        return [prefix + rng.integers(0, 256, suffix_tokens).tolist() for _ in range(n_requests)]

    _serve_wave(eng, wave())  # cold: publishes the prefix
    prefilled, hit = eng.stats["prefill_tokens"], eng.stats["prefix_hit_tokens"]
    _serve_wave(eng, wave())  # warm: new tails over the same prefix
    assert eng.stats["prefill_tokens"] - prefilled == n_requests * suffix_tokens
    assert eng.stats["prefix_hit_tokens"] - hit == n_requests * prefix_tokens
    # with every request ended, each page still out is the tree's own
    held = eng.prefix_cache_stats()["pages_held"]
    assert held >= prefix_pages and eng.slots.pool.used == held
    assert eng.flush_prefix_cache() == held
    assert eng.slots.pool.used == 0 and eng.prefix_cache_stats()["pages_held"] == 0
