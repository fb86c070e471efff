"""Cross-request radix prefix cache (ISSUE 5): longest page-aligned prefix
match over the paged pool, suffix-only prefill numerics, publication at
completion/park, the eviction ladder, and the flush-on-commit staleness
policy. The reference leans on SGLang's RadixAttention for all of this;
inference/paged_kv.py RadixPrefixCache is our page-granular equivalent."""

import threading

import jax
import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    PrefixCacheConfig,
    ServerConfig,
)
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.paged_kv import PagePool, RadixPrefixCache
from areal_tpu.models import qwen

from tpu_testing import TINY_QWEN2

PSZ = 16  # small pages -> multi-page prompts at tiny test lengths


def _engine(n_slots=4, max_len=256, steps=8, prefix_cache=None, **cfg_kw):
    cfg = ServerConfig(
        max_batch_size=n_slots,
        max_seq_len=max_len,
        decode_steps_per_call=steps,
        page_size=PSZ,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        prefix_cache=prefix_cache or PrefixCacheConfig(),
        **cfg_kw,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    return eng


def _drive(eng, max_chunks=64):
    """Direct-drive the admission/dispatch cycle until all slots drain
    (no decode thread -> no races with test-side pokes)."""
    for _ in range(max_chunks):
        rows = eng._admit_pending()
        eng._apply_slot_updates(rows)
        eng._drain(eng._dispatch_chunk())
        if not any(t is not None for t in eng._slot_task) and not eng._backlog:
            break


# -- tree unit behavior ------------------------------------------------------


def test_radix_longest_prefix_match_and_lru():
    pool = PagePool(32)
    tree = RadixPrefixCache(pool, page_size=4, max_pages=16)
    ids = list(range(12))  # 3 pages
    pages = pool.alloc(3)
    assert tree.insert(ids, pages, [7, 7, 7]) == 3
    pool.free(pages)
    # full match, partial match, diverging match
    assert tree.match(ids)[0] == pages
    assert tree.match(ids[:8])[0] == pages[:2]
    assert tree.match(ids[:4] + [99, 99, 99, 99])[0] == pages[:1]
    assert tree.match([99] * 8)[0] == []
    # sub-page tails never match (page granularity)
    assert tree.match(ids[:6])[0] == pages[:1]
    # versions ride along
    assert tree.match(ids)[1] == [7, 7, 7]
    # the tree counts raw lookups only; hit/miss accounting is the
    # engine's (de-duplicated per admitted request, not per retry)
    assert tree.stats["lookups"] == 6


def test_radix_insert_dedups_existing_path():
    """Re-publishing the same content keeps the FIRST page set; the
    duplicate producer's pages follow their normal free path untouched."""
    pool = PagePool(32)
    tree = RadixPrefixCache(pool, page_size=4, max_pages=16)
    ids = list(range(8))
    first = pool.alloc(2)
    tree.insert(ids, first, [0, 0])
    dup = pool.alloc(2)
    assert tree.insert(ids, dup, [0, 0]) == 0  # nothing adopted
    pool.free(dup)
    assert tree.match(ids)[0] == first
    # extending the path adopts only the new tail page
    ext = pool.alloc(1)
    assert tree.insert(list(range(12)), first + ext, [0, 0, 0]) == 1
    assert tree.match(list(range(12)))[0] == first + ext


def test_radix_insert_longer_than_capacity_never_orphans_or_leaks():
    """An insert longer than max_pages must not evict its OWN path tail to
    make room (that would chain new nodes under a detached parent and leak
    their pool refs forever): adoption stops at the cap, every adopted page
    stays reachable, and flush returns the pool to zero."""
    pool = PagePool(32)
    tree = RadixPrefixCache(pool, page_size=2, max_pages=2)
    ids = list(range(6))  # 3 pages > cap 2
    pages = pool.alloc(3)
    adopted = tree.insert(ids, pages, [0, 0, 0])
    pool.free(pages)
    assert adopted == 2 and tree.pages_held == 2
    assert tree.match(ids)[0] == pages[:2]  # everything adopted is reachable
    assert tree.flush() == 2
    assert pool.used == 0, "insert-at-capacity leaked pool pages"
    # same guard when the tree is at capacity from an UNRELATED old chain:
    # that chain is evictable, the new path itself is not
    a = pool.alloc(2)
    tree.insert([9, 9, 8, 8], a, [0, 0])
    pool.free(a)
    b = pool.alloc(3)
    assert tree.insert(list(range(6)), b, [0, 0, 0]) == 2
    pool.free(b)
    assert tree.pages_held == 2
    tree.flush()
    assert pool.used == 0


def test_radix_capacity_evicts_lru_before_adopting():
    pool = PagePool(32)
    tree = RadixPrefixCache(pool, page_size=4, max_pages=2)
    a = pool.alloc(2)
    tree.insert([1] * 8, a, [0, 0])
    pool.free(a)
    tree.match([1] * 8)  # touch: a's chain is now most-recent
    b = pool.alloc(2)
    tree.insert([2] * 8, b, [0, 0])
    pool.free(b)
    assert tree.pages_held == 2
    # a was touched later than b's insert... match to refresh b instead
    tree.match([2] * 8)
    c = pool.alloc(1)
    tree.insert([3] * 4, c, [0])
    pool.free(c)
    assert tree.pages_held <= 2
    assert tree.match([2] * 8)[0], "the recently-touched chain was evicted"


# -- engine: suffix-only prefill numerics ------------------------------------


@pytest.mark.slow  # ~11s; tier-1 keeps the stricter vs-cold-engine pin below
def test_warm_repeat_matches_cold_greedy():
    """Second admission of the same prompt radix-matches the published
    pages, prefills only the suffix, and decodes the IDENTICAL greedy
    continuation — the correctness pin for forward_prefill_paged."""
    eng = _engine()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).tolist()  # 6 full pages + tail
    g = GenerationHyperparameters(max_new_tokens=8, greedy=True)
    out = []
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 0
    assert eng.prefix_cache_stats()["pages_held"] >= 6
    cold_tokens = int(eng.stats["prefill_tokens"])
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert len(out) == 2
    assert out[1].output_tokens == out[0].output_tokens
    assert eng.stats["prefix_cache_hits"] == 1
    assert eng.stats["prefix_hit_tokens"] == 96  # (100-1)//16 pages
    # warm admission prefilled ONLY the 4-token suffix
    assert eng.stats["prefill_tokens"] - cold_tokens == 4


def test_shared_prefix_different_suffix_matches_cold_engine():
    """The headline workload: same system/few-shot prefix, different
    question. Warm admission must produce exactly what a cold engine does."""
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, 256, 64).tolist()  # 4 full pages
    tail_a = rng.integers(0, 256, 20).tolist()
    tail_b = rng.integers(0, 256, 28).tolist()
    g = GenerationHyperparameters(max_new_tokens=8, greedy=True)

    eng = _engine()
    out = []
    eng.submit(ModelRequest(input_ids=prefix + tail_a, gconfig=g), out.append)
    _drive(eng)
    eng.submit(ModelRequest(input_ids=prefix + tail_b, gconfig=g), out.append)
    _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 1
    assert eng.stats["prefix_hit_tokens"] == 64

    cold = _engine()
    ref = []
    cold.submit(ModelRequest(input_ids=prefix + tail_b, gconfig=g), ref.append)
    _drive(cold)
    assert out[1].output_tokens == ref[0].output_tokens


def test_warm_repeat_matches_cold_greedy_int8_kv():
    """Same pin under int8 KV pages: the suffix prefill's prefix gather
    must dequantize with the per-token-vector scales (and re-quantize its
    own writes), or warm continuations drift from cold ones."""
    eng = _engine(kv_quantization="int8")
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 256, 100).tolist()
    g = GenerationHyperparameters(max_new_tokens=8, greedy=True)
    out = []
    for _ in range(2):
        eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
        _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 1
    assert out[1].output_tokens == out[0].output_tokens


def test_warm_admission_group_mixes_with_cold():
    """One admission wave holding a radix-warm prompt AND a cold prompt
    routes each through its own prefill path and both complete."""
    rng = np.random.default_rng(2)
    shared = rng.integers(0, 256, 48).tolist()
    g = GenerationHyperparameters(max_new_tokens=4, greedy=True)
    eng = _engine()
    out = []
    eng.submit(ModelRequest(input_ids=shared + [1, 2, 3], gconfig=g), out.append)
    _drive(eng)
    eng.submit(ModelRequest(input_ids=shared + [7, 8, 9], gconfig=g), out.append)
    eng.submit(
        ModelRequest(input_ids=rng.integers(0, 256, 30).tolist(), gconfig=g),
        out.append,
    )
    _drive(eng)
    assert len(out) == 3
    assert eng.stats["prefix_cache_hits"] == 1
    assert eng.stats["prefix_cache_misses"] >= 2


# -- acceptance: multi-turn re-admission after parked-KV eviction ------------


def test_multi_turn_readmission_after_parked_eviction_hits_radix():
    """A parked rid whose KV was evicted under pool pressure re-admits its
    NEXT turn (prompt + emitted + feedback) through the radix tree: the
    prior turns' pages were published at park time, so the resubmission
    aliases them instead of re-prefilling from token zero."""
    eng = _engine(max_len=512)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, 70).tolist()
    out = []
    eng.submit(
        ModelRequest(
            rid="episode-1",
            input_ids=list(prompt),
            gconfig=GenerationHyperparameters(
                max_new_tokens=64, greedy=True, ignore_eos=True
            ),
        ),
        out.append,
    )
    # a few chunks in, the trainer pauses for a weight update (abort mode)
    rows = eng._admit_pending()
    eng._apply_slot_updates(rows)
    for _ in range(3):
        eng._drain(eng._dispatch_chunk())
    eng.pause_generation()
    eng._abort_all()
    assert out and out[0].stop_reason == "abort"
    emitted = list(out[0].output_tokens)
    assert len(emitted) >= 16
    assert "episode-1" in eng.slots.parked
    published = eng.prefix_cache_stats()["pages_held"]
    assert published >= (70 + len(emitted) - 1) // PSZ - 1
    # pool pressure evicts the parked KV -> the rid-affinity fast path dies
    assert eng.slots.evict_oldest_parked() is not None
    eng.continue_generation()
    # turn 2: the episode resubmits prompt + turn-1 emission + feedback
    turn2 = list(prompt) + emitted + rng.integers(0, 256, 11).tolist()
    eng.submit(
        ModelRequest(
            rid="episode-1",
            input_ids=turn2,
            gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
        ),
        out.append,
    )
    _drive(eng)
    assert len(out) == 2 and out[1].stop_reason in ("stop", "length")
    assert eng.stats["kv_resumes"] == 0  # the parked entry was gone
    assert eng.stats["prefix_cache_hits"] == 1
    # prior turns' pages served from the tree: everything parked except the
    # partial write page
    assert eng.stats["prefix_hit_tokens"] >= (70 + len(emitted)) // PSZ * PSZ - PSZ


# -- weight commits vs cached KV ---------------------------------------------


def _commit_update(eng, version):
    """Full weight update through the real staged path (inline: no thread)."""
    from areal_tpu.inference.server import flatten_params

    eng.begin_staged_update()
    eng.stage_weight_bucket(flatten_params(jax.tree.map(np.asarray, eng.params)))
    eng.commit_staged_weights(version)


def test_flush_policy_drops_cache_at_commit():
    eng = _engine()
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 256, 80).tolist()
    g = GenerationHyperparameters(max_new_tokens=4, greedy=True)
    out = []
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert eng.prefix_cache_stats()["pages_held"] > 0
    _commit_update(eng, version=1)
    # default policy: the tree is empty and nothing stale is matchable
    assert eng.prefix_cache_stats()["pages_held"] == 0
    assert eng.slots.pool.used == 0
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 0
    # the v1 run republished under v1; a v1-time repeat now hits
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 1


def test_keep_policy_survives_commit_for_ablation():
    eng = _engine(prefix_cache=PrefixCacheConfig(across_updates="keep"))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, 80).tolist()
    g = GenerationHyperparameters(max_new_tokens=4, greedy=True)
    out = []
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    held = eng.prefix_cache_stats()["pages_held"]
    assert held > 0
    _commit_update(eng, version=1)
    assert eng.prefix_cache_stats()["pages_held"] == held
    eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
    _drive(eng)
    assert eng.stats["prefix_cache_hits"] == 1  # stale KV served, by design


def test_disabled_cache_never_matches_or_publishes():
    eng = _engine(prefix_cache=PrefixCacheConfig(enabled=False))
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 256, 80).tolist()
    g = GenerationHyperparameters(max_new_tokens=4, greedy=True)
    out = []
    for _ in range(2):
        eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), out.append)
        _drive(eng)
    assert eng.prefix_cache_stats() == {"enabled": False}
    assert eng.stats["prefix_cache_hits"] == 0
    assert eng.slots.pool.used == 0


# -- ops surface -------------------------------------------------------------


def test_statusz_and_flush_endpoint():
    """/statusz exports the decode counters + prefix_cache section;
    /flush_prefix_cache drops the tree through the live decode loop."""
    import json
    import urllib.request

    from areal_tpu.inference.server import ServerThread

    eng = _engine()
    st = ServerThread(eng.config, eng)
    st.start()
    try:
        rng = np.random.default_rng(7)
        done = threading.Event()
        eng.submit(
            ModelRequest(
                input_ids=rng.integers(0, 256, 60).tolist(),
                gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
            ),
            lambda r: done.set(),
        )
        assert done.wait(120)
        with urllib.request.urlopen(f"http://{st.address}/statusz", timeout=30) as r:
            s = json.loads(r.read())
        for key in ("prefills", "prefill_batches", "chunks", "prefix_cache_hits"):
            assert key in s["stats"], s["stats"]
        assert s["prefix_cache"]["enabled"]
        assert s["prefix_cache"]["pages_held"] > 0
        req = urllib.request.Request(
            f"http://{st.address}/flush_prefix_cache", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            f = json.loads(r.read())
        assert f["freed_pages"] > 0
        assert eng.prefix_cache_stats()["pages_held"] == 0
    finally:
        st.stop()
