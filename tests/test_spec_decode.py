"""Speculative tree decoding (docs/serving.md "Speculative decoding"):
greedy byte-identity twins across every admission path (cold prefill,
radix hit, parked resume, mid-commit version split), allocator-level
rollback audits after rejected drafts, deadline reaps mid-speculation,
and the host-side drafter unit behavior.

The twin pattern (PR 6/12/13): two engines built from the same params and
config except the feature flag, fed identical greedy requests — outputs
must compare byte-identical, because the verify/accept walk only ever
emits tokens the target sampler itself produced."""

import threading
import time

import jax
import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    RequestLifecycleConfig,
    ServerConfig,
    SpeculativeConfig,
)
from areal_tpu.api.io_struct import (
    GenerationHyperparameters,
    ModelRequest,
    StopReason,
)
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.models import qwen

from tpu_testing import TINY_QWEN2

PAGE = 16  # small pages: radix publish + rollback churn within 256 ctx


@pytest.fixture(scope="module")
def tiny_params():
    return qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)


def _cfg(spec: SpeculativeConfig | None = None, **kw) -> ServerConfig:
    defaults = dict(
        max_batch_size=2,
        max_seq_len=256,
        decode_steps_per_call=4,
        page_size=PAGE,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    defaults.update(kw)
    cfg = ServerConfig(**defaults)
    if spec is not None:
        cfg.speculative = spec
    return cfg


def _engine(params, spec=None, **kw) -> DecodeEngine:
    eng = DecodeEngine(_cfg(spec=spec, **kw), params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    eng.start()
    return eng


def _greedy(n=24, **kw) -> GenerationHyperparameters:
    return GenerationHyperparameters(max_new_tokens=n, greedy=True, **kw)


def _leaked(eng: DecodeEngine) -> int:
    """PagePool refcount audit: pages in use not accounted for by the
    radix tree (the only legitimate holder once all requests ended)."""
    held = eng.prefix_cache_stats()["pages_held"] if eng.slots.radix is not None else 0
    return eng.slots.pool.used - held


def _settle(eng: DecodeEngine, timeout=30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = eng.admission_snapshot()
        if (
            snap["queue_depth"] == 0
            and snap["active_slots"] == 0
            and not eng.slots.parked
        ):
            return
        time.sleep(0.05)
    raise TimeoutError("engine never drained")


def _wait_decoding(eng: DecodeEngine, rid: str, timeout=30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for t in eng._slot_task:
            if t is not None and t.req.rid == rid and t.out_tokens:
                return
        time.sleep(0.02)
    raise TimeoutError(f"rid {rid} never started decoding")


# acceptance-friendly (periodic: prompt-lookup drafting hits) + adversarial
# (random: drafts mostly reject) prompt mix
_PROMPTS = [
    [7, 3, 9] * 8,
    list(range(50, 82)),
    ([5, 11, 5, 11, 2] * 8)[:36],
    list(np.random.default_rng(13).integers(1, 250, 40)),
]


def _run_all(eng: DecodeEngine, reqs: list[ModelRequest], timeout=180.0):
    done = threading.Event()
    out: dict[str, object] = {}
    lock = threading.Lock()

    def cb(resp):
        with lock:
            out[resp.rid] = resp
            if len(out) == len(reqs):
                done.set()

    for r in reqs:
        eng.submit(r, cb)
    assert done.wait(timeout), f"only {len(out)}/{len(reqs)} finished"
    return out


# the radix twin's shared warm prefix: two full publishable pages
_SHARED = ([9, 2, 9, 2, 7] * 8)[: 2 * PAGE]
_LONG_PROMPT = [7, 3, 9] * 8
_LONG_TOTAL = 96


@pytest.fixture(scope="module")
def baseline(tiny_params):
    """Every spec-OFF twin half, served once on one shared engine. The twin
    halves across tests use identical params + config + greedy requests, so
    their baselines are identical — building a fresh spec-off engine per
    test would re-serve the same bytes (and dominate suite time on CPU)."""
    eng = _engine(tiny_params)
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        prompts = {
            rid: r.output_tokens for rid, r in _run_all(eng, reqs).items()
        }
        long = _run_all(
            eng,
            [ModelRequest(rid="b", input_ids=list(_LONG_PROMPT),
                          gconfig=_greedy(_LONG_TOTAL, ignore_eos=True))],
        )["b"].output_tokens
        _run_all(
            eng, [ModelRequest(rid="warm", input_ids=list(_SHARED),
                               gconfig=_greedy(8))]
        )
        follow = _run_all(
            eng,
            [ModelRequest(rid="follow", input_ids=list(_SHARED) + [4, 4, 1, 3],
                          gconfig=_greedy(24))],
        )["follow"].output_tokens
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        eng.stop()
    return {"prompts": prompts, "long": long, "follow": follow}


# ---------------------------------------------------------------------------
# twin: cold prefill (both drafters)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("drafter", ["ngram", "tree"])
def test_spec_twin_cold_prefill_greedy_identity(tiny_params, baseline, drafter):
    """Spec-off vs spec-on over a cold-prefill workload mixing acceptance-
    friendly and adversarial prompts: byte-identical greedy outputs, real
    speculation activity, zero leaked pages."""
    eng = _engine(tiny_params, spec=SpeculativeConfig(enabled=True, drafter=drafter))
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        outs = {rid: r.output_tokens for rid, r in _run_all(eng, reqs).items()}
        _settle(eng)
        assert _leaked(eng) == 0
        assert eng.stats["spec_rounds"] > 0, "speculation never ran"
        assert eng.stats["spec_accepted_tokens"] > 0, (
            "periodic prompts should yield accepted drafts"
        )
    finally:
        eng.stop()
    assert outs == baseline["prompts"], f"{drafter} spec-on diverged from baseline"


# ---------------------------------------------------------------------------
# twin: radix-hit admission
# ---------------------------------------------------------------------------


def test_spec_twin_radix_hit(tiny_params, baseline):
    """The radix-hit admission path (prefix pages aliased from the tree,
    suffix-only prefill) under speculation: byte-identical to the spec-off
    twin (which admitted its follow request through the same radix-hit
    path), and the published prefix pages never contain unverified tokens
    (a later radix-hit request decodes the same bytes)."""
    eng = _engine(tiny_params, spec=SpeculativeConfig(enabled=True, drafter="tree"))
    try:
        warm = ModelRequest(
            rid="warm", input_ids=list(_SHARED), gconfig=_greedy(8)
        )
        _run_all(eng, [warm])
        assert eng.prefix_cache_stats()["pages_held"] >= 2
        hits0 = eng.stats["prefix_cache_hits"]
        follow = ModelRequest(
            rid="follow",
            input_ids=list(_SHARED) + [4, 4, 1, 3],
            gconfig=_greedy(24),
        )
        out = _run_all(eng, [follow])["follow"].output_tokens
        assert eng.stats["prefix_cache_hits"] == hits0 + 1, (
            "follow-up request must admit through the radix-hit path"
        )
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        eng.stop()
    assert out == baseline["follow"]


# ---------------------------------------------------------------------------
# twin: parked resume
# ---------------------------------------------------------------------------


def test_spec_twin_parked_resume(tiny_params, baseline):
    """An abort-pause parks a spec-decoding rid mid-flight; the resumed
    attempt (zero-prefill KV restore) continues speculating. The
    concatenated park+resume output must equal the uninterrupted spec-off
    twin's — greedy continuation is split-point invariant."""
    prompt, total, base = _LONG_PROMPT, _LONG_TOTAL, baseline["long"]
    eng = _engine(tiny_params, spec=SpeculativeConfig(enabled=True))
    try:
        done = threading.Event()
        box: dict[str, object] = {}
        req = ModelRequest(
            rid="parked",
            input_ids=list(prompt),
            gconfig=_greedy(total, ignore_eos=True),
        )
        eng.submit(req, lambda r: (box.update(r=r), done.set()))
        _wait_decoding(eng, "parked")
        eng.pause_generation()  # abort-pause: rid parks, keeps its KV
        assert done.wait(30)
        part1 = box["r"].output_tokens
        assert box["r"].stop_reason == StopReason.ABORT.value
        assert "parked" in eng.slots.parked
        assert 0 < len(part1) < total, "pause landed outside the window"
        eng.continue_generation()
        resumed = _run_all(
            eng,
            [ModelRequest(
                rid="parked",
                input_ids=list(prompt) + list(part1),
                gconfig=_greedy(total - len(part1), ignore_eos=True),
            )],
        )["parked"]
        assert eng.stats["kv_resumes"] == 1, "resume must restore parked KV"
        assert list(part1) + list(resumed.output_tokens) == list(base)
        assert eng.stats["spec_rounds"] > 0
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# twin: mid-commit version split
# ---------------------------------------------------------------------------


def test_spec_twin_mid_commit_version_split(tiny_params, baseline):
    """A staged weight commit lands while a spec-on request is mid-flight:
    per-token version tags split monotonically at the commit, and with an
    identity delta the bytes still match the uninterrupted spec-off twin
    (draft and verify share one weight version per round — the commit can
    never land between them)."""
    from areal_tpu.inference.server import flatten_params

    prompt, total, base = _LONG_PROMPT, _LONG_TOTAL, baseline["long"]
    # private host copies: the staged commit donates the served tree
    host = jax.tree.map(np.asarray, tiny_params)
    eng = _engine(
        jax.tree.map(np.copy, host), spec=SpeculativeConfig(enabled=True)
    )
    try:
        done = threading.Event()
        box: dict[str, object] = {}
        req = ModelRequest(
            rid="span",
            input_ids=list(prompt),
            gconfig=_greedy(total, ignore_eos=True),
        )
        eng.submit(req, lambda r: (box.update(r=r), done.set()))
        _wait_decoding(eng, "span")
        # identity delta: versions split, bytes must not
        eng.begin_staged_update()
        eng.stage_weight_bucket(flatten_params(jax.tree.map(np.asarray, host)))
        eng.commit_staged_weights(version=1)
        assert eng.get_version() == 1
        assert done.wait(120), "generation did not finish"
        resp = box["r"]
        assert resp.stop_reason != StopReason.ABORT.value
        assert list(resp.output_tokens) == list(base)
        versions = resp.output_versions
        assert len(versions) == total
        assert versions == sorted(versions), "per-token versions not monotone"
        assert versions[0] == 0 and versions[-1] == 1, (
            "commit must land inside the generation window"
        )
        assert eng.stats["spec_rounds"] > 0
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# rollback + reap audits
# ---------------------------------------------------------------------------


def test_spec_rejected_drafts_roll_back_pages(tiny_params):
    """Rejected draft tails are rolled back through the refcounted pool:
    rollback activity is observable, and after settling every page is
    free or radix-held — free + held == total, nothing stranded."""
    eng = _engine(tiny_params, spec=SpeculativeConfig(enabled=True, drafter="tree"))
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        _run_all(eng, reqs)
        assert eng.stats["spec_rounds"] > 0
        assert eng.stats["spec_rollback_pages"] > 0, (
            "the adversarial prompts should force rejected tails"
        )
        _settle(eng)
        assert _leaked(eng) == 0
        held = eng.prefix_cache_stats()["pages_held"]
        assert eng.slots.pool.used == held  # free + held == total
    finally:
        eng.stop()


def test_spec_twin_int8_kv_greedy_identity(tiny_params):
    """Spec-on vs spec-off under kv_quantization="int8": the verify walk
    reads the SAME quantized pages the plain decode path reads, so greedy
    outputs stay byte-identical — int8 shifts numerics relative to the
    full-precision baseline fixture, so the spec-off half is re-served on
    its own int8 engine rather than reusing the bf16 baseline."""
    off = _engine(tiny_params, kv_quantization="int8")
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        base = {rid: r.output_tokens for rid, r in _run_all(off, reqs).items()}
        _settle(off)
        assert _leaked(off) == 0
    finally:
        off.stop()
    on = _engine(
        tiny_params,
        spec=SpeculativeConfig(enabled=True, drafter="tree"),
        kv_quantization="int8",
    )
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        outs = {rid: r.output_tokens for rid, r in _run_all(on, reqs).items()}
        _settle(on)
        assert _leaked(on) == 0
        assert on.stats["spec_rounds"] > 0, "speculation never ran"
        assert on.stats["spec_accepted_tokens"] > 0
    finally:
        on.stop()
    assert outs == base, "spec-on diverged from spec-off under int8 KV"


def test_spec_rollback_with_quantized_pages_no_leak(tiny_params):
    """Rejected-tail rollback over int8 KV pages: the value and scale
    planes live in the same refcounted pages, so the audit is unchanged —
    rollback activity observable, nothing stranded after settling."""
    eng = _engine(
        tiny_params,
        spec=SpeculativeConfig(enabled=True, drafter="tree"),
        kv_quantization="int8",
    )
    try:
        reqs = [
            ModelRequest(rid=f"r{i}", input_ids=list(p), gconfig=_greedy())
            for i, p in enumerate(_PROMPTS)
        ]
        _run_all(eng, reqs)
        assert eng.stats["spec_rollback_pages"] > 0, (
            "the adversarial prompts should force rejected tails"
        )
        _settle(eng)
        assert _leaked(eng) == 0
        held = eng.prefix_cache_stats()["pages_held"]
        assert eng.slots.pool.used == held  # free + held == total
    finally:
        eng.stop()


def test_spec_deadline_reaps_mid_speculation(tiny_params):
    """The lifecycle deadline reaper fires while the slot is speculating:
    partial output with consistent version tags, pages fully returned."""
    eng = _engine(
        tiny_params,
        spec=SpeculativeConfig(enabled=True),
        lifecycle=RequestLifecycleConfig(),
    )
    try:
        t0 = time.time()
        resp = eng.generate_sync(
            ModelRequest(
                input_ids=[7, 3, 9] * 8,
                deadline=t0 + 1.2,
                gconfig=GenerationHyperparameters(
                    max_new_tokens=100_000, greedy=True, ignore_eos=True
                ),
            ),
            timeout=60,
        )
        assert resp.stop_reason == StopReason.DEADLINE.value
        assert len(resp.output_tokens) > 0
        assert len(resp.output_versions) == len(resp.output_tokens)
        assert eng.stats["spec_rounds"] > 0
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# drafter unit behavior (host-side, no engine)
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    from areal_tpu.inference import speculative as sp

    cfg = SpeculativeConfig(enabled=True, spec_depth=3, max_ngram=3)
    d = sp.build_drafter(cfg)
    # suffix [7,3] matched earlier; the continuation that followed is [9,7,3]
    chains, source = d.propose([9, 7, 3, 9, 7, 3])
    assert source == "ngram"
    assert chains[0] == [9, 7, 3]
    # no earlier occurrence of the suffix: nothing proposed
    chains, source = d.propose([1, 2, 3, 4, 5])
    assert chains == [] and source == "none"


def test_tree_drafter_merges_distinct_sites():
    from areal_tpu.inference import speculative as sp

    cfg = SpeculativeConfig(
        enabled=True, drafter="tree", spec_depth=3, tree_width=2, max_ngram=2
    )
    d = sp.build_drafter(cfg)
    # suffix [5] occurs twice with different continuations -> two chains
    chains, source = d.propose([5, 8, 1, 5, 2, 6, 5])
    assert source == "ngram" and len(chains) == 2
    assert sorted(c[0] for c in chains) == [2, 8]
    bundle = sp.draft_batch(d, {0: [5, 8, 1, 5, 2, 6, 5]}, S=2, K=cfg.max_nodes() - 1)
    n = int(bundle.n_draft[0])
    assert n >= 2
    # both first-token branches are children of the pending-token root
    roots = [
        int(bundle.tokens[0, j])
        for j in range(n)
        if int(bundle.parent_row[0, j]) == 0
    ]
    assert sorted(roots) == [2, 8]
    # the untouched slot proposes nothing
    assert int(bundle.n_draft[1]) == 0 and bundle.sources[1] == "none"


def test_radix_lookup_extension():
    from areal_tpu.inference.paged_kv import PagePool, RadixPrefixCache

    pool = PagePool(8)
    cache = RadixPrefixCache(pool, PAGE, max_pages=8)
    ids = list(range(100, 100 + 2 * PAGE))
    pages = pool.alloc(2)
    cache.insert(np.asarray(ids), pages, [0, 0])
    # mid-page probe: the published continuation extends it
    ext = cache.lookup_extension(ids[: PAGE + 4], 4)
    assert ext == ids[PAGE + 4 : PAGE + 8]
    # probe past the published content: nothing to extend with
    assert cache.lookup_extension(ids, 4) == []
    # read-only: lookups took no refs — only the caller's alloc and the
    # tree's insert-time refs remain, and both unwind to zero
    cache.flush()
    pool.free(pages)
    assert pool.used == 0


def test_speculative_config_validation():
    with pytest.raises(ValueError):
        SpeculativeConfig(drafter="eagle")
    with pytest.raises(ValueError):
        SpeculativeConfig(spec_depth=0)
    assert SpeculativeConfig(drafter="tree", spec_depth=4, tree_width=2).max_nodes() == 9
    assert SpeculativeConfig(drafter="ngram", spec_depth=4).max_nodes() == 5
