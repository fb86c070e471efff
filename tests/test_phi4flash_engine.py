"""The decode engine serving a model of the ``phi4flash`` family (a
decoder-hybrid-decoder) through its normal entry points: the window layers'
rings (``inference/paged_kv.py`` RING_LEAVES: a slot's fixed pages a window
layer, whatever its context), the ONE full layer's pages under the page
table, seven... here two layers that read them, the selective-scan state,
and what keeps the three consistent: group fan-out (a sibling gets a COPY of
its primary's rings and state), parking, preemption and re-prefill.

Tiny size of the published shape (12 layers in the same five kinds, window 8,
pages of 4), float32, seeded weights, against the benchmark's plain
reference by logprobs through prefill and paged decode, never by sampled
tokens alone.

Tolerances: float32 on both sides, logits of order 1 over a vocabulary of
500: 1e-4 (measured 2e-6 to 1e-5). A ring one token off, a token fed twice
or a neighbour's state moves a logprob by 1e-2 and more."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_phi4flash_util as pu  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 1e-4
W, PSZ, SLOTS = 8, 4, 10
RING_PAGES = 2  # ceil(8 / 4)


def _server_config(**kw):
    base = dict(
        dtype="float32", max_batch_size=SLOTS, max_seq_len=256, page_size=PSZ, decode_steps_per_call=4,
        attn_window_step=256, seed=3, mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    return ServerConfig(**{**base, **kw})


def _mesh(scfg):
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[: scfg.mesh.model])


def _engine(**kw):
    cfg = pu.tiny_model()
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=pu.make_params(cfg, 23), model_cfg=pu.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    return pu.reference().token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=256)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_batched_prefill_then_decode_matches_reference(served):
    """Prompts below the window, across it and five windows long in one
    prefill bucket, then 40 sampled tokens each: the prefill leaves in every
    slot's rings the window of the prompt and in its state the tokens before
    the last, decode feeds that token again and goes on; the rings wrap five
    times more."""
    eng, cfg = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (2, 11, 41)]
    g = GenerationHyperparameters(max_new_tokens=40, temperature=1.0, ignore_eos=True)
    rows0 = eng._obs.prefill_last_token_rows.get()
    _held(eng)  # so that all three are admitted in one wave
    box, done = {}, threading.Event()
    for i, p in enumerate(prompts):
        eng.submit(ModelRequest(input_ids=p, gconfig=g), lambda r, i=i: (box.__setitem__(i, r), len(box) == 3 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    for i, p in enumerate(prompts):
        r = box[i]
        assert len(r.output_tokens) == 40 and r.stop_reason == StopReason.LENGTH.value
        err = np.abs(np.asarray(r.output_logprobs) - _reference(eng, cfg, p, r.output_tokens))
        assert err.max() < REF_TOL, (i, err.max())
    assert eng._obs.prefill_last_token_rows.get() - rows0 == 3  # the cross-decoder's rows of a prompt pass: one a prompt


def test_a_group_of_8_shares_one_prefill_by_copies_of_rings_and_state(served):
    """A GRPO group of 8 on one prompt two windows long: one prefill, seven
    copies of the primary's rings, post-prompt state and last shared page;
    the full prompt pages are aliased."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 19).tolist()
    g = GenerationHyperparameters(max_new_tokens=12, temperature=1.0, ignore_eos=True)
    copies, prefills = eng._obs.state_copies.get(), eng.stats["prefills"]
    _held(eng)
    box, done = {}, threading.Event()
    for i in range(8):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and eng._obs.state_copies.get() == copies + 7
    assert len({tuple(box[i].output_tokens) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err)


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its rings and state; the same rid
    resumes from them with no prefill. A preempted slot loses them and
    prefills prompt + emitted again (the rings rebuilt from the last window
    of it). Both then go on the reference's logprobs."""
    eng, cfg = served
    prompt = np.random.default_rng(4).integers(0, cfg["vocab_size"], 13).tolist()

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
        assert len(toks) == 100
        return np.abs(np.asarray(first.output_logprobs + rest.output_logprobs) - _reference(eng, cfg, prompt, toks))

    def park(_rid):
        eng.pause_generation("abort")
        assert eng._pause_ack.wait(60)
        eng.continue_generation()

    def preempt(rid):
        _held(eng)  # the loop idles: its bookkeeping is ours for a moment
        slot = next(i for i, t in enumerate(eng._slot_task) if t is not None and t.req.rid == rid)
        eng._apply_slot_updates([eng._preempt(slot)])
        eng.continue_generation()

    resumes, rebuilt = eng.stats["kv_resumes"], eng._obs.state_prefills.get()
    err = interrupted("parked", park)
    assert eng.stats["kv_resumes"] == resumes + 1 and eng._obs.state_prefills.get() == rebuilt
    assert err.max() < REF_TOL, err
    err = interrupted("preempted", preempt)
    assert eng.stats["kv_resumes"] == resumes + 1 and eng._obs.state_prefills.get() == rebuilt + 1
    assert err.max() < REF_TOL, err


def test_the_chunks_counts_are_the_live_slots(served):
    """One request decoding 24 tokens on an engine of 10 slots: the counts
    come back with the chunk's tokens. A step at position p reads p + 1
    shared rows in each of 3 layers and min(p + 1, 8) ring rows in each of 3
    window layers, and advances 4 selective-scan states; the nine dead slots
    add nothing, and an ended slot's rings and state stay what they were."""
    eng, cfg = served
    _held(eng)
    obs = eng._obs
    c0 = (obs.shared_kv_tokens_read.get(), obs.window_tokens_read.get(), obs.s6_state_updates.get())
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 5).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        lengths = np.arange(5, 5 + 24)  # cached tokens at each of the 24 steps, the one just written among them
        assert obs.shared_kv_tokens_read.get() - c0[0] == 3 * lengths.sum()
        assert obs.window_tokens_read.get() - c0[1] == 3 * np.minimum(lengths, W).sum()
        assert obs.s6_state_updates.get() - c0[2] == 24 * 4 and len(r.output_tokens) == 24
        kept = {k: np.asarray(eng.cache[k]) for k in ("ssm", "conv", *paged_kv.RING_LEAVES)}
        assert set(eng.cache) == {"k", "v", "ssm", "conv", *paged_kv.RING_LEAVES}  # the counts are no part of the cache
    finally:
        eng.continue_generation()
    time.sleep(0.3)
    _held(eng)
    try:
        for k, v in kept.items():
            assert np.array_equal(v, np.asarray(eng.cache[k])), k
    finally:
        eng.continue_generation()


def test_a_window_layer_holds_its_bound_of_pages_after_five_windows(served):
    """A context of 5 windows and more: the window group still holds its 2
    pages a slot and layer (never more than ceil(window / page) + 1), the
    cross layers hold none, and the full layer's pages have grown with the
    context. ``/statusz`` ``kv_pools`` says which is which."""
    eng, cfg = served
    prompt = np.random.default_rng(9).integers(0, cfg["vocab_size"], 30).tolist()
    box, ev = [], threading.Event()
    g = GenerationHyperparameters(max_new_tokens=40, temperature=1.0, ignore_eos=True)
    eng.submit(ModelRequest(input_ids=prompt, rid="long", gconfig=g), lambda r: (box.append(r), ev.set()))
    while not any(t is not None and t.req.rid == "long" and len(t.out_tokens) >= 16 for t in eng._slot_task):
        time.sleep(0.01)
    _held(eng)
    try:
        view = eng.kv_pools_status()
        slot = next(i for i, t in enumerate(eng._slot_task) if t is not None and t.req.rid == "long")
        assert len(eng.slots.pages(slot)) >= -(-(30 + 16) // PSZ) > RING_PAGES  # the full layer: a page every 4 tokens
        assert view["window"]["pages_per_slot"] == RING_PAGES <= -(-W // PSZ) + 1 and view["window"]["keeps"] == W
        assert view["window"]["pages_held"] == RING_PAGES * 1 and view["window"]["pages_total"] == RING_PAGES * SLOTS
        assert view["window"]["writers"] == view["window"]["readers"] == [1, 3, 5] and view["window"]["layers"] == 3
        assert view["full"]["writers"] == [7] and view["full"]["readers"] == [7, 9, 11] and view["full"]["keeps"] is None
        assert view["full"]["pages_held"] == eng.slots.pool.used == len(eng.slots.pages(slot))
        assert eng.cache["ring_k"].shape == (3, 2, SLOTS + 1, RING_PAGES, PSZ, 16)  # fixed at start-up, whatever the contexts
        assert view["state_bytes"] == eng._obs.state_bytes.get() and view["window_bytes"] == 2 * 3 * 2 * (SLOTS + 1) * RING_PAGES * PSZ * 16 * 4
    finally:
        eng.continue_generation()
    assert ev.wait(120)
    err = np.abs(np.asarray(box[0].output_logprobs) - _reference(eng, cfg, prompt, box[0].output_tokens))
    assert err.max() < REF_TOL


def test_radix_cache_serves_nothing_and_the_ledger_counts_rings_and_state(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "recurrent_state"}
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 18).tolist()  # 4 whole pages
    first, again = _gen(eng, prompt, 4), _gen(eng, prompt, 4)
    assert again.output_tokens == first.output_tokens
    assert "cached_prefix_tokens" not in again.metadata and eng.stats["prefix_hit_tokens"] == 0
    led = eng.hbm_ledger()["components"]
    state_a_slot = 4 * (4 * 128 + 3 * 128) * 4  # four selective-scan layers: [4, 128] of state and 3 taps of 128 channels, float32
    assert led["recurrent_state"] == SLOTS * state_a_slot == eng._obs.state_bytes.get()
    assert led["window_rings"] == 2 * 3 * 2 * (SLOTS + 1) * RING_PAGES * PSZ * 16 * 4
    assert led["kv_page_pool"] == 2 * 1 * 2 * eng.slots.pool.n_pages * PSZ * 16 * 4  # ONE layer under the page table
    assert eng.attention_impl()["decode"] == "xla"  # off a TPU; on one paged_decode_attn takes 40 heads of 128 in groups of 4 (test_tpu_compile)


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names and carry
    the family's scopes beside the shared ones (docs/observability.md); the
    prefill program stops at the shared layer's K and V: no cross read, no
    memory unit, no read of the full layer in it."""
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
            copy = jax.jit(paged_kv.copy_pages).lower(eng.cache, i32(1), i32(1), i32(1), i32(1))
    finally:
        eng.continue_generation()
    shared = ("embed", "attn_proj", "kv_write", "mlp")
    s6 = hybrid.SCOPES  # the selective scan reuses the state-space mixer's four
    scopes = {}
    for name, lowered, want in (
        ("chunk", chunk, hybrid.SAMBAY_SCOPES + s6 + shared + ("attn", "lm_head", "sampler")),
        ("prefill", prefill, ("attn_window", "attn_diff") + s6 + shared),
        ("copy_pages", copy, ("state_write", "kv_write")),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not (set(hybrid.GDN_SCOPES[:3]) | set(hybrid.CONV_SCOPES[:2]) | set(hybrid.MOE_SCOPES) | {"mla_proj"}) & have
        scopes[name] = have
    assert not {"attn_cross", "gmu", "attn", "lm_head"} & scopes["prefill"]


def test_refused_configurations():
    mcfg = pu.model_config(pu.tiny_model())
    for kw, msg in (
        (dict(speculative=SpeculativeConfig(enabled=True)), "speculative"),
        (dict(quantization="int8"), "int8"),
        (dict(kv_quantization="int8"), "rings"),
        (dict(mesh=MeshConfig(data=1, fsdp=1, seq=1, model=2)), "one chip"),
    ):
        scfg = _server_config(**kw)
        eng = DecodeEngine(scfg, params={"embed": jnp.zeros((2, 2))}, model_cfg=mcfg, mesh=_mesh(scfg))
        with pytest.raises(ValueError, match=msg):
            eng.initialize()
    eng, _ = _engine(max_batch_size=2, max_seq_len=64, attn_window_step=64)
    with pytest.raises(ValueError, match="speculative"):
        eng.set_speculative(True)
    assert eng._spec_cfg is None and eng.moe_status() is None and eng.sparse_attention_status() is None
    assert eng.kv_pools_status()["window"]["pages_held"] == 0
    with pytest.raises(NotImplementedError):
        eng.model.quantize_params_int8({})
