"""``SlotCache`` alone: who owns a page, with no engine, thread or device.

The ownership rule has one statement (``inference/slot_cache.py``): a page is
held by a live slot, a parked request, the prefix tree, or any mix of them,
and the pool's refcount of the page is the number of its holders. Every
scenario walks a request's pages through the ledger and holds the rule after
every step: the pool's ``used`` is the number of distinct pages the holders
name, each page's refcount is the number of holders that name it, and the
audit (``leaked_pages``, what ``DecodeEngine.drain()`` reports) reads 0 —
also where a parked request and the tree share a page, the case the engine's
own audit read below zero until PR 42."""

import collections

import pytest

from areal_tpu.api.config import PrefixCacheConfig, ServerConfig
from areal_tpu.inference.slot_cache import SlotCache

from tpu_testing import TINY_QWEN2

PSZ = 16
V = 3  # the policy version every page is made under


def _cache(n_slots=4, prefix=True, **kw) -> tuple[SlotCache, list]:
    cfg = ServerConfig(
        max_batch_size=n_slots, max_seq_len=128, page_size=PSZ, prefix_cache=PrefixCacheConfig(enabled=prefix), **kw
    )
    events = []
    return SlotCache(cfg, TINY_QWEN2, record=lambda kind, **f: events.append((kind, f))), events


def _holds(sc: SlotCache) -> None:
    """The ownership rule, from the ledger's own lists."""
    holders = collections.Counter()
    for slot in range(sc.config.max_batch_size):
        assert len(sc.pages(slot)) == len(sc.page_versions(slot))
        assert list(sc.page_table()[slot, : len(sc.pages(slot))]) == sc.pages(slot)
        assert not sc.page_table()[slot, len(sc.pages(slot)) :].any()  # the rest: the trash page
        holders.update(sc.pages(slot))
    for p in sc.parked.values():
        assert not sc.pages(p.slot)  # a parked slot's own list is empty
        holders.update(p.pages)
    if sc.radix is not None:
        holders.update(sc.radix.pages())
        assert sc.radix_pages == len(sc.radix.pages())
    assert sc.pool.used == len(holders)
    assert sc.free_pages == sc.n_pages - 1 - len(holders)
    for page, n in holders.items():
        assert sc.pool._rc[page] == n, (page, n)
    assert sc.leaked_pages() == 0


def _admit(sc: SlotCache, slot: int, n_tokens: int) -> list[int]:
    """A cold admission: pages for ``n_tokens`` of prompt."""
    pages = sc.take(-(-n_tokens // PSZ))
    sc.assign(slot, pages, [V] * len(pages))
    return list(range(100, 100 + n_tokens))


def _steps_finish(sc, events):
    ids = _admit(sc, 0, 40)
    yield "admitted"
    assert sc.extend(0, 5, V) and len(sc.pages(0)) == 5
    yield "grown ahead of the decode head"
    assert sc.trim(0, 4) == 1 and sc.trim(0, 4) == 0
    yield "speculation rolled back"
    sc.publish(ids + list(range(20)), sc.pages(0), sc.page_versions(0), 59, V)
    assert sc.radix_pages == 3  # pages strictly below position 59
    yield "published"
    sc.release(0)
    assert sc.pool.used == 3 and sc.free_slots([False] * 4) == [0, 1, 2, 3]
    yield "released: the tree alone holds the published pages"
    assert sc.flush_prefix() == 3 and sc.pool.used == 0
    yield "flushed"


def _steps_park_evict(sc, events):
    ids = _admit(sc, 1, 40)
    p = sc.park("r", 1, ids, 39, 0, V)
    assert p.pages and sc.radix_pages == 2 and set(sc.radix.pages()) < set(p.pages)
    assert sc.free_slots([False] * 4) == [0, 2, 3]
    yield "parked: the entry and the tree share the published pages"
    sc.release(1)  # what _finish does after the park: nothing left to free
    assert sc.pool.used == 3
    yield "the slot released after the park frees nothing"
    assert sc.evict_oldest_parked() == 1 and not sc.parked and sc.pool.used == 2
    yield "parking evicted: the tree still serves the prefix"
    assert sc.readmitted(["r", "other"]) == 1 and sc.readmitted(["r"]) == 0
    m = sc.match(ids)  # the admission holds a reference of its own from here on
    assert m is not None and len(m[0]) == 2 and m[1] == [V, V]
    sc.assign(2, m[0] + sc.take(1), m[1] + [V])
    yield "admitted over the cached prefix"
    sc.unmatch(sc.match(ids)[0])
    yield "a second admission matched, found no page for its suffix and let go"
    sc.release(2)
    assert sc.flush_prefix() == 2 and sc.pool.used == 0
    yield "released and flushed"


def _steps_park_resume(sc, events):
    ids = _admit(sc, 0, 20)
    pages = list(sc.pages(0))
    sc.park("a", 0, ids, 19, 3, V)
    yield "parked"
    assert sc.resume("missing", ids) is None
    p = sc.resume("a", ids)
    assert p.slot == 0 and p.n_emitted == 3 and sc.pages(0) == pages and not sc.parked
    yield "resumed in place: the slot holds its pages again"
    sc.park("a", 0, ids, 19, 3, V)
    assert sc.resume("a", ids + [7]) is None and not sc.parked and sc.readmitted(["a"]) == 1
    yield "a rid reused with other content drops its parking"
    _admit(sc, 3, 16)
    sc.park("b", 3, list(range(16)), 15, 0, V)
    assert sc.drop_parked("b") and not sc.drop_parked("b")
    yield "a cancelled rid's parking lets go"
    sc.flush_prefix()
    assert sc.pool.used == 0
    yield "flushed"


def _steps_alias(sc, events):
    _admit(sc, 0, 40)  # 3 pages; decode writes into the third
    assert sc.alias(1, 2, 2) is None  # the source was never admitted
    dst, src = sc.alias(1, 0, 2)
    assert src == sc.pages(0)[2] and dst == sc.pages(1)[2] != src and sc.pages(1)[:2] == sc.pages(0)[:2]
    assert sc.page_versions(1) == [V, V, V]
    yield "a group's sibling shares the full prompt pages and owns a copy of the last"
    sc.release(0)
    yield "the primary gone, the sibling keeps the shared pages alive"
    sc.release(1)
    assert sc.pool.used == 0
    yield "both gone"


def _steps_ladder(sc, events):
    assert sc.n_pages == 9  # eight pages to hand out
    ids = _admit(sc, 0, 32)
    sc.publish(ids + [1], sc.pages(0), sc.page_versions(0), 32, V)
    sc.release(0)  # 2 pages, the tree's alone
    parked_ids = _admit(sc, 1, 16)
    sc.park("old", 1, parked_ids, 15, 0, V)  # position 15: nothing publishable yet
    _admit(sc, 0, 5 * PSZ)
    assert sc.free_pages == 0
    yield "the pool is empty: 2 pages cached, 1 parked, 5 decoding"
    assert sc.extend(0, 7, V) and sc.radix_pages == 0 and events[-1] == ("evict_radix", {"pages": 2})
    yield "first rung: the tree's least recently used leaves"
    assert sc.extend(0, 8, V) and not sc.parked
    assert events[-1] == ("evict_parked", {"severity": "warn", "slot": 1}) and len(events) == 2
    yield "second rung: the oldest parked request"
    assert sc.take(1) is None and not sc.extend(1, 1, V) and sc.pages(1) == []
    yield "the ladder exhausted: whom to preempt is the scheduler's"
    sc.release(0)
    assert sc.pool.used == 0
    assert sc.take(1, keep=sc.free_pages) is None  # what must stay free is not handed out
    yield "given back"


def _bytes_a_token() -> int:
    return sum(h * d for h, d in TINY_QWEN2.kv_pools.values()) * TINY_QWEN2.num_kv_layers * 4  # float32


SCENARIOS = {
    "finish": _steps_finish,
    "park-evict-match": _steps_park_evict,
    "park-resume-drop": _steps_park_resume,
    "alias": _steps_alias,
    "ladder": _steps_ladder,
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_every_page_has_its_holders_after_every_step(scenario):
    # the ladder's pool: a budget of nine pages (the trash page and eight)
    sc, events = _cache(n_slots=2, kv_hbm_gb=9.5 * PSZ * _bytes_a_token() / 2**30) if scenario == "ladder" else _cache()
    _holds(sc)
    said = []
    for step in SCENARIOS[scenario](sc, events):
        said.append(step)
        try:
            _holds(sc)
        except AssertionError as e:
            raise AssertionError(f"after {said}: {e}") from e
    assert len(said) >= 3


def test_a_model_without_a_servable_prefix_gets_no_tree_and_a_budget_sizes_the_pool():
    sc, _ = _cache(prefix=True)
    assert sc.radix is not None and sc.radix.max_pages == (sc.n_pages - 1) // 2 and sc.n_pages == 4 * 8 + 1
    off = SlotCache(sc.config, TINY_QWEN2, no_prefix="a recurrent state has no prefix")
    assert off.radix is None and off.prefix_stats() is None and off.radix_pages == 0
    ids = _admit(off, 0, 40)
    off.publish(ids, off.pages(0), off.page_versions(0), 39, V)  # neither inserts
    off.park("r", 0, ids, 39, 0, V)
    assert off.flush_prefix() == 0 and off.leaked_pages() == 0
    stats = sc.prefix_stats()
    assert list(stats)[:3] == ["pages_held", "max_pages", "page_size"] and stats["page_size"] == PSZ
    budget, _ = _cache(kv_hbm_gb=1e-3)
    assert budget.n_pages == int(1e-3 * 2**30) // (PSZ * _bytes_a_token())
    with pytest.raises(ValueError, match="unknown kv_quantization"):
        _cache(kv_quantization="int4")
