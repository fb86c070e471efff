"""The decode engine serving a latent-attention model of the ``deepseek_v3``
family: ONE latent row a token and layer in the page pool (no V pool, no
recurrent state), prefill in the plain form, decode in the absorbed form,
sparse experts of which the engine holds a share, and the refusals of what
the model's module does not implement.

Tiny size of the benchmark configuration's shape (4 layers, experts 0-3 of
8, top-3, two shared, vocabulary 500), float32, seeded weights, against the
benchmark's plain reference by logprobs through prefill and paged decode,
never by sampled tokens alone.

Tolerances: as tests/test_lfm2_engine.py (float32 on both sides, logits of
order 1 over a vocabulary of 500: 2e-5). A latent row one token off, a
neighbour's page or a sibling decoding into a shared page moves a logprob by
1e-2 and more."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_kanana2_util as ku  # noqa: E402

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 2e-5
ROW = 256 * 4  # a latent row as the tiny pages store it: 136 values in 256 float32 lanes


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
    cfg = ku.tiny_model()
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=ku.make_params(cfg, 23), model_cfg=ku.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    from benchlib import kanana2_reference

    return kanana2_reference.token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_a_grpo_group_of_eight_matches_the_reference_and_aliases_latent_pages(served):
    """Eight requests on one prompt of 37 tokens (2 whole pages and 5 rows of
    a third): one prefill; the seven siblings alias the primary's two full
    pages by refcount and get a copy of the partial third, by bytes; every
    one then decodes its own 40 tokens on the reference's logprobs."""
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
        # at most 5 pages a request (37 + 40 tokens) of which 2 are shared: the group holds 2 + 8 x (1..3) pages
        assert 2 + 8 <= eng.slots.pool.used - used0 <= 2 + 8 * 3
        k = np.asarray(eng.cache["k"])  # [4 layers, 1, pages, 16 rows, 256 lanes]
        first = k[:, 0, pages[0][2], :5]
        assert np.abs(first[..., :136]).min(axis=-1).max() > 0 and not first[..., 136:].any()  # 136 values a row, zero lanes after
        for p in pages[1:]:  # the copy of the last partial page: its 5 prompt rows, 4 layers x 5 x 1,024 B
            assert np.array_equal(k[:, 0, p[2], :5], first) and first.nbytes == 4 * 5 * ROW
    finally:
        eng.continue_generation()
    assert done.wait(300)
    assert len({tuple(box[i].output_tokens) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        assert len(box[i].output_tokens) == 40 and box[i].stop_reason == StopReason.LENGTH.value
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err.max())


def test_a_preempted_request_rebuilds_its_latent_pages_by_prefill(served):
    """A preempted slot loses its pages; the same rid comes back with prompt
    + emitted and prefills all of it again, in the plain form, into fresh
    pages; a parked one resumes from its pages with no prefill. Both go on
    along the reference's logprobs."""
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
    rebuilt, copied = eng._obs.state_prefills.get(), eng._obs.state_copies.get()  # the process's registry: other engines' too
    err = interrupted("parked", park)
    assert (eng.stats["kv_resumes"], eng.stats["prefills"]) == (resumes + 1, prefills + 1) and err.max() < REF_TOL, err
    err = interrupted("preempted", preempt)
    assert (eng.stats["kv_resumes"], eng.stats["prefills"]) == (resumes + 1, prefills + 3) and err.max() < REF_TOL, err
    assert eng._obs.state_prefills.get() == rebuilt and eng._obs.state_copies.get() == copied  # no recurrent state to rebuild or copy


def test_counts_of_a_decode_chunk_and_the_status_page(served):
    """One request decoding 24 tokens from a prompt of 21: the latent rows
    read are the slot's cached tokens a step and layer, counted on the
    device; the expert load has the router's width, ``moe_touched`` counts
    the held experts only, and ``/statusz`` says which are held."""
    eng, cfg = served
    _held(eng)
    load0 = np.asarray(eng.moe_status()["load"])
    r0, t0, a0 = eng._obs.latent_tokens_read.get(), eng._obs.moe_experts_touched.get(), eng._obs.moe_assignments.get()
    s0 = eng._obs.moe_experts_streamed.get()
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 21).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        assert len(r.output_tokens) == 24
        # step t of 24 reads the 21 + t tokens cached so far (its own row among them) in each of 4 layers
        assert eng._obs.latent_tokens_read.get() - r0 == 4 * sum(21 + t for t in range(24))
        status = eng.moe_status()
        load = np.asarray(status["load"]) - load0
        assert status["held"] == [0, 4] and load.shape == (3, 8) and load.sum() == eng._obs.moe_assignments.get() - a0 == 24 * 3 * 3
        assert eng._obs.moe_experts_touched.get() - t0 == int((load[:, :4]).sum())  # one live row: an expert's rows are its touches
        # XLA's form (no TPU here) reads all 4 held experts a step and layer, live rows or none: whole chunks of steps
        streamed = eng._obs.moe_experts_streamed.get() - s0
        assert streamed >= 24 * 3 * 4 and streamed % (3 * 4) == 0
        assert 0 < int(load[:, 4:].sum())  # some choices fell on experts that are not here: left out, still counted as load
        assert set(eng.cache) == {"k"}  # the counts are no part of the cache
    finally:
        eng.continue_generation()


def test_the_ledger_and_the_budget_count_latent_rows(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "latent_pages"}
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 70).tolist()  # 4 whole pages
    first, again = _gen(eng, prompt, 4), _gen(eng, prompt, 4)
    assert again.output_tokens == first.output_tokens
    assert "cached_prefix_tokens" not in again.metadata and eng.stats["prefix_hit_tokens"] == 0
    led = eng.hbm_ledger()["components"]
    assert led["recurrent_state"] == 0 and led["kv_page_pool"] == 4 * eng.slots.pool.n_pages * 16 * ROW  # four layers, one row a token
    impl = eng.attention_impl()
    assert impl["decode"] == impl["kv_write"] == "xla" and impl["prefill"] == "xla"  # off the TPU: the gather path
    # the budget by hand at the published sizes: 48 layers x 128 tokens x 640 lanes x 2 B a page, one pool
    assert paged_kv.n_pages_for_budget(int(3.5 * 2**30), 48, 1, 128, 640, 2, pools=1) == int(3.5 * 2**30) // (48 * 128 * 1280) == 477
    budget = _server_config(kv_hbm_gb=1e-3)
    e2 = DecodeEngine(budget, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(budget))
    e2.initialize()
    assert e2.slots.pool.n_pages == int(1e-3 * 2**30) // (4 * 16 * ROW) and e2.cache["k"].shape == (4, 1, e2.slots.pool.n_pages, 16, 256)


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names and carry
    the latent-attention and shared-block scopes beside the shared ones
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
    shared = ("embed", "mlp", hybrid.MOE_SHARED_SCOPE) + hybrid.MLA_SCOPES
    for name, lowered, want in (
        ("chunk", chunk, hybrid.MOE_SCOPES[:3] + shared + ("lm_head", "sampler")),
        ("prefill", prefill, hybrid.MOE_SCOPES + shared),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not {"attn_proj", "state_write"} & have  # no GQA projections, no recurrent state here


def _refused(mcfg, **kw):
    scfg = _server_config(**kw)
    return DecodeEngine(scfg, params={"embed": jnp.zeros((2, 2))}, model_cfg=mcfg, mesh=_mesh(scfg))


def test_this_model_is_refused_what_its_module_does_not_implement():
    mcfg = ku.model_config(ku.tiny_model())
    assert not mcfg.has_recurrent_state  # the refusals do not hang on that
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
    assert eng._spec_cfg is None and eng.slots.radix is None and eng.moe_status() == {"load": [[0] * 8] * 3, "held": [0, 4]}
    with pytest.raises(NotImplementedError):
        eng.model.quantize_params_int8({})


@pytest.mark.parametrize("family", ["hybrid", "lfm2", "olmo"])
def test_the_recurrent_families_are_still_refused_the_same(family):
    """The three recurrent families' refusals come from their module's list
    too, with the words they had (tests/test_*_engine.py hold each in full)."""
    import importlib

    from areal_tpu.models import hybrid

    ku.load_run()  # puts the benchmark's ``benchlib`` on the path for the families' helpers
    util = importlib.import_module(f"chipbench_{family}_util")
    mcfg = util.model_config(util.tiny_model())
    limits = hybrid.serving_limits(mcfg)
    assert mcfg.has_recurrent_state and limits["reason"] == "recurrent_state" and "int8_pages" not in limits
    for kw, msg in (
        (dict(speculative=SpeculativeConfig(enabled=True)), "speculative"),
        (dict(quantization="int8"), "int8"),
        (dict(mesh=MeshConfig(data=1, fsdp=1, seq=1, model=2)), "one chip"),
    ):
        with pytest.raises(ValueError, match=msg):
            _refused(mcfg, **kw).initialize()
    from areal_tpu.models import qwen

    assert qwen.serving_limits(None) == {}  # a module that implements all of it refuses nothing


def test_prompt_tokens_under_the_prefill_launch_are_counted_where_the_program_is_dispatched(served, monkeypatch):
    """``areal_decode_prefill_attn_launch_tokens_total`` beside
    ``areal_decode_prefill_tokens_total``: nothing on this backend (the launch
    is a TPU's, and these heads are no whole lane tiles); where the model's
    module says a bucket's program attends under the launch, every prompt
    token that program prefills."""
    eng, cfg = served
    rng = np.random.default_rng(41)
    launched, prefilled = eng._obs.prefill_attn_launch_tokens.get(), eng._obs.prefill_tokens.get()
    _gen(eng, rng.integers(0, cfg["vocab_size"], 19).tolist(), 2)
    assert eng._obs.prefill_tokens.get() == prefilled + 19 and eng._obs.prefill_attn_launch_tokens.get() == launched
    buckets = []
    monkeypatch.setattr(eng.model, "prefill_attn_launch", lambda mcfg, bucket: buckets.append(bucket) or mcfg is eng.model_cfg)
    _gen(eng, rng.integers(0, cfg["vocab_size"], 23).tolist(), 2)
    assert eng._obs.prefill_tokens.get() == prefilled + 42 and eng._obs.prefill_attn_launch_tokens.get() == launched + 23
    assert buckets == [256]  # asked once a dispatched program, by its bucket
