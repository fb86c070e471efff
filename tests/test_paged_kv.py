"""Paged KV cache: pool accounting, budgeted pools smaller than S*T,
preemption under pool pressure, prefix sharing by page aliasing.

The dense-slab engine was O(S*T) HBM; these tests pin the paged engine's
core property — KV memory ∝ used tokens, correct under pressure — the role
SGLang's paged allocator plays for the reference (blog/AReaL_v0_3.md:266)."""

import threading
import time

import jax
import numpy as np
import pytest

from areal_tpu.api.config import MeshConfig, ServerConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.paged_kv import PagePool, n_pages_for_budget
from areal_tpu.models import qwen

from tpu_testing import TINY_QWEN2


def test_page_pool_accounting():
    pool = PagePool(8)
    assert pool.available == 7  # page 0 reserved
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3] and pool.used == 3
    assert pool.alloc(5) is None  # only 4 left
    pool.ref(a[:2])  # alias two pages
    pool.free(a)  # drops rc: pages 1,2 survive (rc 1), page 3 freed
    assert pool.used == 2
    pool.free(a[:2])
    assert pool.used == 0 and pool.available == 7
    with pytest.raises(AssertionError):
        pool.free([3])  # double free


def _engine(n_slots=4, max_len=256, steps=8, n_pages=None):
    cfg_kw = dict(
        max_batch_size=n_slots,
        max_seq_len=max_len,
        decode_steps_per_call=steps,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    if n_pages is not None:
        # express the desired pool size as an HBM budget, exercising the
        # budget -> pages conversion on the way
        page_bytes = (
            2
            * TINY_QWEN2.num_layers
            * TINY_QWEN2.num_kv_heads
            * 128
            * TINY_QWEN2.head_dim_
            * np.dtype(np.float32).itemsize
        )
        cfg_kw["kv_hbm_gb"] = n_pages * page_bytes / (1 << 30)
        assert (
            n_pages_for_budget(
                n_pages * page_bytes,
                TINY_QWEN2.num_layers,
                TINY_QWEN2.num_kv_heads,
                128,
                TINY_QWEN2.head_dim_,
                4,
            )
            == n_pages
        )
    cfg = ServerConfig(**cfg_kw)
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    return eng


def _run_all(eng, reqs, timeout=300.0):
    done = threading.Event()
    results = []
    lock = threading.Lock()

    def cb(resp):
        with lock:
            results.append(resp)
            if len(results) == len(reqs):
                done.set()

    for r in reqs:
        eng.submit(r, cb)
    assert done.wait(timeout), f"only {len(results)}/{len(reqs)} finished"
    return results


def test_pool_pressure_preempts_and_recovers():
    """Pool of 5 usable pages, 4 slots wanting ~2 pages each: the engine
    must keep making progress (evict/preempt/backlog), every request gets a
    terminal callback, and the pool drains back to empty."""
    eng = _engine(n_pages=6)  # 5 usable + trash
    eng.start()
    try:
        rng = np.random.default_rng(0)
        reqs = [
            ModelRequest(
                rid=f"r{i}",
                input_ids=rng.integers(0, 256, 100).tolist(),
                gconfig=GenerationHyperparameters(
                    max_new_tokens=120, greedy=True
                ),
            )
            for i in range(6)
        ]
        results = _run_all(eng, reqs)
        assert len(results) == 6
        # completed requests ran to their length budget; preempted ones
        # aborted with partial output (client retry territory)
        for r in results:
            assert r.stop_reason in ("length", "stop", "abort")
        assert any(r.stop_reason == "length" for r in results)
    finally:
        eng.stop()
    # after all requests finish, the only pages still out are the radix
    # tree's own (completed prompts publish their full pages); flushing the
    # tree must drain the pool to zero — anything else is a refcount leak
    assert eng.slots.pool.used == eng.prefix_cache_stats().get("pages_held", 0)
    eng.flush_prefix_cache()
    assert eng.slots.pool.used == 0, "pages leaked after all requests finished"


def test_prefix_sharing_page_accounting():
    """A GRPO-style group of identical prompts must prefill once, alias the
    shared prompt pages, and drain cleanly."""
    eng = _engine(n_slots=4, max_len=256)
    eng.start()
    try:
        prompt = list(np.random.default_rng(1).integers(0, 256, 130))
        reqs = [
            ModelRequest(
                rid=f"g{i}",
                input_ids=[int(x) for x in prompt],
                gconfig=GenerationHyperparameters(max_new_tokens=16, greedy=True),
            )
            for i in range(4)
        ]
        results = _run_all(eng, reqs)
        outs = {tuple(r.output_tokens) for r in results}
        assert len(outs) == 1, "greedy duplicates must decode identically"
        assert eng.stats.get("prefix_shared", 0) >= 1
        assert eng.stats["prefills"] < 4
    finally:
        eng.stop()
    assert eng.slots.pool.used == eng.prefix_cache_stats().get("pages_held", 0)
    eng.flush_prefix_cache()
    assert eng.slots.pool.used == 0


def test_budgeted_pool_sizes_from_hbm():
    """kv_hbm_gb produces a pool smaller than the dense equivalent."""
    eng = _engine(n_pages=4)
    dense_pages = 4 * (256 // 128) + 1
    assert eng.slots.pool.n_pages == 4 < dense_pages


# -- refcount safety under aliasing ----------------------------------------


def test_double_free_of_aliased_page_asserts():
    """Freeing past zero must assert even when the page was aliased along
    the way (rc 1 -> 2 -> 1 -> 0 -> boom)."""
    pool = PagePool(4)
    (p,) = pool.alloc(1)
    pool.ref([p])
    pool.free([p])
    pool.free([p])
    assert pool.available == 3
    with pytest.raises(AssertionError):
        pool.free([p])


def test_free_while_aliased_keeps_page_out_of_free_list():
    """One owner freeing an aliased page must not recycle it under the
    other owner: the page stays allocatable-to-nobody until rc hits 0."""
    pool = PagePool(5)
    a = pool.alloc(3)
    pool.ref(a[:1])  # second owner of a[0]
    pool.free(a)  # first owner drops all three
    assert pool.used == 1  # a[0] survives at rc 1
    got = pool.alloc(3)
    assert got is not None and a[0] not in got, "aliased page was recycled"
    pool.free(got)
    pool.free(a[:1])
    assert pool.used == 0


def test_ref_of_unallocated_page_asserts():
    pool = PagePool(4)
    with pytest.raises(AssertionError):
        pool.ref([2])  # never allocated


def test_radix_evict_while_referenced_keeps_page_alive():
    """Evicting a tree node whose page a live slot still references must
    only drop the TREE's claim — the page stays out of the free list until
    the slot frees it too."""
    from areal_tpu.inference.paged_kv import RadixPrefixCache

    pool = PagePool(8)
    tree = RadixPrefixCache(pool, page_size=2, max_pages=4)
    pages = pool.alloc(2)
    tree.insert([1, 2, 3, 4], pages, [0, 0])
    pool.free(pages)  # producer's own refs drop; tree keeps both alive
    assert pool.used == 2
    matched, _ = tree.match([1, 2, 3, 4])
    assert matched == pages
    pool.ref(matched)  # a slot aliases the cached pages
    assert tree.evict(2) == 2  # pool pressure evicts both tree nodes
    assert pool.used == 2, "slot-referenced pages must survive tree eviction"
    pool.free(matched)  # the slot finishes
    assert pool.used == 0


def test_radix_interior_eviction_never_orphans_children():
    """LRU eviction removes leaves only: an interior node with a live child
    is not evictable, so a deep chain evicts bottom-up and a child's path
    stays walkable until the child itself goes."""
    from areal_tpu.inference.paged_kv import RadixPrefixCache

    pool = PagePool(16)
    tree = RadixPrefixCache(pool, page_size=2, max_pages=8)
    # chain a-b-c plus a sibling branch a-d; the interior node a is OLDEST
    # by access but must outlive both branches
    pa = pool.alloc(3)
    tree.insert([1, 2, 3, 4, 5, 6], pa, [0, 0, 0])
    pd = pool.alloc(2)
    tree.insert([1, 2, 9, 9], pd, [0, 0])  # shares node a = pages[0]
    pool.free(pa)
    pool.free(pd)
    assert tree.pages_held == 4  # a, b, c, d (a shared)
    assert tree.evict(1) == 1  # one LEAF went, never node a
    m, _ = tree.match([1, 2])
    assert m == [pa[0]], "interior node evaporated under a live child"
    # evicting everything walks bottom-up and empties cleanly
    assert tree.evict(10) == 3
    assert tree.pages_held == 0 and pool.used == 0


# ---------------------------------------------------------------------------
# abort page accounting (request lifecycle manager, ISSUE 6): cancelling a
# request at any point of its life must return every page — alias-refcounted
# radix pages included
# ---------------------------------------------------------------------------


def _audit_zero(eng):
    """Every page still out must be the radix tree's own claim; flushing the
    tree must drain the pool to zero."""
    assert eng.slots.pool.used == eng.prefix_cache_stats().get("pages_held", 0), (
        "pages out beyond the radix tree's claim"
    )
    eng.flush_prefix_cache()
    assert eng.slots.pool.used == 0, "pages leaked after abort"


def _submit_until_decoding(eng, req):
    done = threading.Event()
    box = {}
    eng.submit(req, lambda r: (box.update(r=r), done.set()))
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(
            t is not None and t.req.rid == req.rid and t.out_tokens
            for t in eng._slot_task
        ):
            return done, box
        time.sleep(0.02)
    raise TimeoutError("request never started decoding")


def test_abort_before_prefill_returns_every_page():
    """A request cancelled while still queued (the pre-prefill boundary: the
    reap runs between loop passes, and admission+prefill are atomic within
    one pass) never allocates a page."""
    eng = _engine(n_slots=2)
    try:
        # keep the loop busy so the victim stays queued
        fills = [
            ModelRequest(
                rid=f"fill{i}",
                input_ids=[7 + i, 8, 9],
                gconfig=GenerationHyperparameters(
                    max_new_tokens=100_000, greedy=True, ignore_eos=True
                ),
            )
            for i in range(2)
        ]
        fill_done = []
        eng.start()
        for f in fills:
            d = threading.Event()
            eng.submit(f, lambda r, d=d: d.set())
            fill_done.append(d)
        victim = ModelRequest(
            rid="victim",
            input_ids=[1, 2, 3, 4],
            gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
        )
        vd = threading.Event()
        vbox = {}
        eng.submit(victim, lambda r: (vbox.update(r=r), vd.set()))
        eng.abort_request("victim")
        assert vd.wait(30)
        assert vbox["r"].stop_reason == "cancelled"
        assert vbox["r"].output_tokens == []
        for f in fills:
            eng.abort_request(f.rid)
        for d in fill_done:
            assert d.wait(60)
    finally:
        eng.stop()
    _audit_zero(eng)


def test_abort_mid_decode_returns_aliased_radix_pages():
    """Abort a request whose prompt pages were ALIASED out of the radix
    cache (refcount++ at admission): the abort drops only the request's
    refs — the tree's claims stay intact, and a flush drains to zero."""
    eng = _engine(n_slots=2, max_len=512)
    prompt = list(range(100, 100 + 256))  # two full pages: radix-publishable
    try:
        eng.start()
        # warm the tree: a completed request publishes its prompt pages
        warm = ModelRequest(
            rid="warm",
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
        )
        [r0] = _run_all(eng, [warm])
        assert eng.prefix_cache_stats()["pages_held"] >= 2
        hits_before = eng.stats["prefix_cache_hits"]
        # same prompt again: admission aliases the cached prefix pages
        victim = ModelRequest(
            rid="victim2",
            input_ids=prompt,
            gconfig=GenerationHyperparameters(
                max_new_tokens=100_000, greedy=True, ignore_eos=True
            ),
        )
        done, box = _submit_until_decoding(eng, victim)
        assert eng.stats["prefix_cache_hits"] == hits_before + 1
        eng.abort_request("victim2")
        assert done.wait(30)
        assert box["r"].stop_reason == "cancelled"
    finally:
        eng.stop()
    _audit_zero(eng)


def test_abort_while_parked_returns_every_page():
    """A rid parked by an abort-pause (KV retained for resume) and then
    cancelled must free the parked pages — they are owned by the parked
    entry, not a slot."""
    eng = _engine(n_slots=2)
    try:
        eng.start()
        req = ModelRequest(
            rid="parked",
            input_ids=[3, 1, 4, 1, 5, 9],
            gconfig=GenerationHyperparameters(
                max_new_tokens=100_000, greedy=True, ignore_eos=True
            ),
        )
        done, box = _submit_until_decoding(eng, req)
        eng.pause_generation()  # abort-pause: rid parks, keeps its pages
        assert done.wait(30)
        assert box["r"].stop_reason == "abort"
        assert "parked" in eng.slots.parked
        parked_pages = list(eng.slots.parked["parked"].pages)
        assert parked_pages, "nothing parked to audit"
        eng.abort_request("parked")
        eng.continue_generation()
        deadline = time.monotonic() + 30
        while "parked" in eng.slots.parked and time.monotonic() < deadline:
            time.sleep(0.02)
        assert "parked" not in eng.slots.parked
    finally:
        eng.stop()
    _audit_zero(eng)
