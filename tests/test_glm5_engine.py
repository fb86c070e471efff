"""The decode engine serving a latent-attention model with a learned index
(``glm_moe_dsa``): TWO page pools of different row widths on one page table
(a latent row of 256 lanes and an index key of 128 beside it), through
admission, a group's aliasing and the copy of the last partial page,
preemption, parking, the drain's leak audit, the counters and the refusals.

Tiny size of the benchmark configuration's shape (3 layers, experts 0-3 of 8,
top-3, one shared, vocabulary 500, ``index_topk`` 16 against contexts of
37-140 tokens: the selection prunes in every decode step), float32, seeded
weights, against the benchmark's plain reference by logprobs through prefill
and paged decode, never by sampled tokens alone.

Tolerances: as tests/test_kanana2_engine.py (float32 on both sides, logits of
order 1 over a vocabulary of 500: 2e-5). An index key one token off, a
neighbour's page in either pool, or a sibling that got its primary's latent
page but not its index page moves a logprob by 1e-2 and more."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_glm5_util as gu  # noqa: E402

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 2e-5
ROW, KEY = 256 * 4, 128 * 4  # a token's latent row and its index key as the tiny pages store them, float32


def _server_config(**kw):
    base = dict(
        dtype="float32", max_batch_size=10, max_seq_len=512, page_size=16, decode_steps_per_call=4,
        attn_window_step=512, seed=3, mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    return ServerConfig(**{**base, **kw})


def _mesh(scfg):
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[: scfg.mesh.model])


def _engine(**kw):
    cfg = gu.tiny_model()
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=gu.make_params(cfg, 23), model_cfg=gu.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    from benchlib import glm5_reference

    return glm5_reference.token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_a_grpo_group_of_eight_aliases_and_copies_both_pools(served):
    """Eight requests on one prompt of 37 tokens (2 whole pages and 5 rows of
    a third; past index_topk 16, so every decode step prunes): one prefill;
    the seven siblings alias the primary's two full pages by refcount, in
    BOTH pools at once (one page id names a page of each), and get a copy of
    the partial third of both, by bytes; every one then decodes its own 40
    tokens on the reference's logprobs."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 37).tolist()
    g = GenerationHyperparameters(max_new_tokens=40, temperature=1.0, ignore_eos=True)
    prefills, shared, used0 = eng.stats["prefills"], eng.stats.get("prefix_shared", 0), eng.slots.pool.used
    _held(eng)
    box, done = {}, threading.Event()
    for i in range(8):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
    eng.continue_generation()
    while eng.stats.get("prefix_shared", 0) < shared + 7:
        time.sleep(0.01)
    _held(eng)
    try:
        slots = [i for i, t in enumerate(eng._slot_task) if t is not None]
        assert len(slots) == 8 and eng.stats["prefills"] == prefills + 1
        pages = [eng.slots.pages(s) for s in slots]
        assert len({tuple(p[:2]) for p in pages}) == 1 and len({p[2] for p in pages}) == 8  # two aliased, the third each one's own
        assert all(eng.slots.pool._rc[p] == 8 for p in pages[0][:2])
        assert 2 + 8 <= eng.slots.pool.used - used0 <= 2 + 8 * 3
        assert set(eng.cache) == {"k", "idx"}
        k, idx = np.asarray(eng.cache["k"]), np.asarray(eng.cache["idx"])  # [3 layers, 1, pages, 16 rows, 256 | 128 lanes]
        first_k, first_i = k[:, 0, pages[0][2], :5], idx[:, 0, pages[0][2], :5]
        assert np.abs(first_k[..., :136]).min(axis=-1).max() > 0 and not first_k[..., 136:].any()  # 136 values a row, zero lanes after
        assert np.abs(first_i).min(axis=-1).max() > 0  # 128 values a key: the page is all key
        for p in pages[1:]:  # the copy of the last partial page, BOTH pools: 3 layers x 5 rows x (1,024 + 512) B
            assert np.array_equal(k[:, 0, p[2], :5], first_k) and np.array_equal(idx[:, 0, p[2], :5], first_i)
        assert first_k.nbytes + first_i.nbytes == 3 * 5 * (ROW + KEY)
    finally:
        eng.continue_generation()
    assert done.wait(300)
    assert len({tuple(box[i].output_tokens) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        assert len(box[i].output_tokens) == 40 and box[i].stop_reason == StopReason.LENGTH.value
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err.max())


def test_a_preempted_request_rebuilds_both_pools_by_prefill(served):
    """A preempted slot loses its pages; the same rid comes back with prompt
    + emitted and prefills all of it again (latent rows AND index keys, the
    selection made a query block at a time) into fresh pages; a parked one
    resumes from its pages with no prefill. Both go on along the reference's
    logprobs, past 100 cached tokens where 16 are selected."""
    eng, cfg = served
    prompt = np.random.default_rng(4).integers(0, cfg["vocab_size"], 19).tolist()

    def interrupted(rid, interrupt):
        box, ev = [], threading.Event()
        g = GenerationHyperparameters(max_new_tokens=100, temperature=1.0, ignore_eos=True)
        eng.submit(ModelRequest(input_ids=prompt, rid=rid, gconfig=g), lambda r: (box.append(r), ev.set()))
        while not any(t is not None and t.req.rid == rid and len(t.out_tokens) >= 8 for t in eng._slot_task):
            time.sleep(0.01)
        interrupt(rid)
        assert ev.wait(120)
        first = box[0]
        assert first.stop_reason == StopReason.ABORT.value and 0 < len(first.output_tokens) < 100
        rest = _gen(eng, prompt + first.output_tokens, 100 - len(first.output_tokens), rid=rid, greedy=False)
        toks = first.output_tokens + rest.output_tokens
        return np.abs(np.asarray(first.output_logprobs + rest.output_logprobs) - _reference(eng, cfg, prompt, toks))

    def park(_rid):
        eng.pause_generation("abort")
        assert eng._pause_ack.wait(60)
        eng.continue_generation()

    def preempt(rid):
        _held(eng)  # the loop idles: its bookkeeping is ours for a moment
        slot = next(i for i, t in enumerate(eng._slot_task) if t is not None and t.req.rid == rid)
        used = eng.slots.pool.used
        eng._apply_slot_updates([eng._preempt(slot)])
        assert eng.slots.pool.used < used  # its pages went back to the pool
        eng.continue_generation()

    resumes, prefills = eng.stats["kv_resumes"], eng.stats["prefills"]
    err = interrupted("parked", park)
    assert (eng.stats["kv_resumes"], eng.stats["prefills"]) == (resumes + 1, prefills + 1) and err.max() < REF_TOL, err
    err = interrupted("preempted", preempt)
    assert (eng.stats["kv_resumes"], eng.stats["prefills"]) == (resumes + 1, prefills + 3) and err.max() < REF_TOL, err


def test_counts_of_a_decode_chunk_and_the_status_page(served):
    """One request decoding 24 tokens from a prompt of 9: what the index
    scored is the slot's cached tokens a step and layer, what it selected
    min(16, cached) of them, counted on the device from the selection itself;
    the masked form fetches every cached row; ``/statusz`` says which form
    runs."""
    eng, cfg = served
    _held(eng)
    read0, scored0, sel0 = eng._obs.latent_tokens_read.get(), eng._obs.index_tokens_scored.get(), eng._obs.latent_tokens_selected.get()
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 9).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        assert len(r.output_tokens) == 24
        cached = [9 + t for t in range(24)]  # step t reads the 9 + t tokens cached so far, its own row among them
        assert eng._obs.index_tokens_scored.get() - scored0 == 3 * sum(cached)
        assert eng._obs.latent_tokens_selected.get() - sel0 == 3 * sum(min(16, n) for n in cached)  # under 16 everything, then 16
        assert eng._obs.latent_tokens_read.get() - read0 == 3 * sum(cached)  # the masked form: selected < read = scored
        assert eng.sparse_attention_status() == {"index_topk": 16, "read_form": "masked"}
        assert eng.moe_status()["held"] == [0, 4]
        assert set(eng.cache) == {"k", "idx"}  # the counts are no part of the cache
    finally:
        eng.continue_generation()


def test_the_ledger_and_the_budget_count_both_pools(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "latent_pages"}
    led = eng.hbm_ledger()["components"]
    assert led["recurrent_state"] == 0 and led["kv_page_pool"] == 3 * eng.slots.pool.n_pages * 16 * (ROW + KEY)  # three layers, a row and a key a token
    impl = eng.attention_impl()
    assert impl["decode"] == impl["kv_write"] == "xla" and impl["prefill"] == "xla"  # off the TPU: the gather path
    # the budget by hand at the published sizes: 6 layers x 128 tokens x (640 + 128) lanes x 2 B a page = 9,216 B a token
    pools = {"k": (1, 640), "idx": (1, 128)}
    assert paged_kv.kv_token_bytes(pools, 6, 2) == 9216
    assert paged_kv.n_pages_for_budget(int(3.5 * 2**30), 6, 1, 128, 640, 2, pools=pools) == int(3.5 * 2**30) // (128 * 9216) == 3185
    assert paged_kv.n_pages_for_budget(int(3.0 * 2**30), 6, 1, 128, 640, 2, pools=pools) == 2730
    assert paged_kv.n_pages_for_budget(int(3.5 * 2**30), 48, 1, 128, 640, 2, pools=1) == 477  # a count of like pools reads as before
    budget = _server_config(kv_hbm_gb=1e-3)
    e2 = DecodeEngine(budget, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(budget))
    e2.initialize()
    assert e2.slots.pool.n_pages == int(1e-3 * 2**30) // (3 * 16 * (ROW + KEY))
    assert e2.cache["k"].shape == (3, 1, e2.slots.pool.n_pages, 16, 256) and e2.cache["idx"].shape == (3, 1, e2.slots.pool.n_pages, 16, 128)


def test_a_wave_of_long_prompts_over_the_pool_waits_and_preempts_nothing():
    """12 prompts of 96-128 tokens (6-8 pages each) against a pool of 40
    pages: admission leaves the decoding slots' next chunks their pages, the
    rest of the wave waits in the backlog, and every request ends by length
    with all its tokens; the drain's leak audit finds every page back."""
    eng, cfg = _engine(max_batch_size=12, max_seq_len=256, attn_window_step=256, kv_hbm_gb=41 * 16 * 3 * (ROW + KEY) / 2**30)
    assert eng.slots.pool.n_pages == 41
    eng.start()
    try:
        rng = np.random.default_rng(11)
        g = GenerationHyperparameters(max_new_tokens=6, temperature=1.0, ignore_eos=True)
        box, done = [], threading.Event()
        _held(eng)
        for i in range(12):
            prompt = rng.integers(0, cfg["vocab_size"], int(rng.integers(96, 129))).tolist()
            eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda r: (box.append(r), len(box) == 12 and done.set()))
        eng.continue_generation()
        assert done.wait(300)
        assert all(r.stop_reason == StopReason.LENGTH.value and len(r.output_tokens) == 6 for r in box)
        assert eng.stats.get("preempted", 0) == 0
        summary = eng.drain(budget_s=5.0)
        assert summary["leaked_pages"] == 0 and eng.slots.pool.used == 0
    finally:
        eng.stop()


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names and carry
    the low-rank query's and the index's scopes beside the latent ones
    (docs/observability.md "Spans and scopes")."""
    import re

    from areal_tpu.models import hybrid

    eng, _ = served
    _held(eng)  # the cache is the loop's while it runs
    try:
        S, psz = eng.config.max_batch_size, eng.config.page_size
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        with jax.set_mesh(eng.mesh):
            chunk = eng.programs.chunk_fn(4, 2, False, False, False).lower(eng.params, eng.cache, i32(S, 2), eng._dev_state, eng._rng)
            prefill = eng.programs.prefill_fn(2, 256).lower(eng.params, eng.cache, i32(2, 256), i32(2), i32(2 * 256 // psz), i32(2))
    finally:
        eng.continue_generation()
    shared = ("embed", "mlp", hybrid.MOE_SHARED_SCOPE) + hybrid.MLA_SCOPES + hybrid.DSA_SCOPES
    for name, lowered, want in (
        ("chunk", chunk, hybrid.MOE_SCOPES[:3] + shared + ("lm_head", "sampler")),
        ("prefill", prefill, hybrid.MOE_SCOPES + shared),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not {"attn_proj", "state_write"} & have


def test_prefill_programs_hold_one_long_prompt_at_a_time(served):
    """The group sizes a prefill program comes in, by the residual stream's
    bytes: every size at this tiny hidden; at hidden 6,144 in bfloat16 a
    bucket of 4,096 and more is one row a program, 1,024 still eight."""
    eng, _ = served
    progs = eng.programs
    assert progs.prefill_sizes(256) == (8, 4, 2, 1)
    import dataclasses

    was = progs.model_cfg
    try:
        progs.model_cfg = dataclasses.replace(was, hidden_size=6144, dtype="bfloat16")
        assert progs.prefill_sizes(1024) == (4, 2, 1) and progs.prefill_sizes(4096) == progs.prefill_sizes(16384) == (1,)
        progs.model_cfg = dataclasses.replace(was, hidden_size=4096, dtype="bfloat16")
        assert progs.prefill_sizes(1024) == (8, 4, 2, 1)  # the cells of 8 x 1,024 tokens keep every size
    finally:
        progs.model_cfg = was


def _refused(mcfg, **kw):
    scfg = _server_config(**kw)
    return DecodeEngine(scfg, params={"embed": jnp.zeros((2, 2))}, model_cfg=mcfg, mesh=_mesh(scfg))


def test_this_model_is_refused_what_its_module_does_not_implement():
    mcfg = gu.model_config(gu.tiny_model())
    for kw, msg in (
        (dict(speculative=SpeculativeConfig(enabled=True)), "speculative decoding cannot serve a latent-attention model"),
        (dict(quantization="int8"), "int8 weight quantization"),
        (dict(kv_quantization="int8"), "quantized latent pages"),
        (dict(mesh=MeshConfig(data=1, fsdp=1, seq=1, model=2)), "one chip"),
    ):
        with pytest.raises(ValueError, match=msg):
            _refused(mcfg, **kw).initialize()
    eng, _ = _engine(max_batch_size=2, max_seq_len=64, attn_window_step=64)
    with pytest.raises(ValueError, match="latent"):
        eng.set_speculative(True)
    assert eng._spec_cfg is None and eng.slots.radix is None and eng.sparse_attention_status()["index_topk"] == 16
