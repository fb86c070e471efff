"""The decode engine serving a model of the ``lfm2_moe`` family: short-conv
layers whose window is the second tenant of the slot-indexed recurrent state
(``inference/paged_kv.py`` STATE_LEAVES: a slot's state is the state after
exactly the tokens the host believes the slot has consumed), rotary attention
on the paged KV pool, and sparse experts whose load the decode chunk counts.

Tiny size of the benchmark configuration's shape (7 layers, 8 experts,
top-3), float32, seeded weights, against the benchmark's plain reference by
logprobs through prefill and paged decode, never by sampled tokens alone.

Tolerances: as tests/test_hybrid_engine.py (float32 on both sides, logits of
order 1 over a vocabulary of 512: 2e-5). A window one token off, a token fed
twice or a neighbour's window moves a logprob by 1e-2 and more."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_lfm2_util as lu  # noqa: E402

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 2e-5


def _server_config(**kw):
    base = dict(
        dtype="float32", max_batch_size=6, max_seq_len=512, page_size=16, decode_steps_per_call=4,
        attn_window_step=512, seed=3, mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    return ServerConfig(**{**base, **kw})


def _mesh(scfg):
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[: scfg.mesh.model])


def _engine(**kw):
    cfg = lu.tiny_model()
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=lu.make_params(cfg, 23), model_cfg=lu.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    from benchlib import lfm2_reference

    return lfm2_reference.token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_batched_prefill_then_decode_matches_reference(served):
    """Prompts of different lengths in one prefill bucket, then 40 sampled
    tokens each: the prefill leaves in every slot the window before the
    prompt's last token, decode feeds that token again and goes on."""
    eng, cfg = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (2, 33, 64)]
    g = GenerationHyperparameters(max_new_tokens=40, temperature=1.0, ignore_eos=True)
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


def test_group_siblings_start_from_the_primarys_window(served):
    """A GRPO group of 4 on one prompt: one prefill, three copies of the
    primary's post-prompt window (and of its last KV page)."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 37).tolist()
    g = GenerationHyperparameters(max_new_tokens=12, temperature=1.0, ignore_eos=True)
    copies, prefills = eng._obs.state_copies.get(), eng.stats["prefills"]
    _held(eng)
    box, done = {}, threading.Event()
    for i in range(4):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 4 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and eng._obs.state_copies.get() == copies + 3
    assert len({tuple(box[i].output_tokens) for i in range(4)}) > 1  # they do not walk one path
    for i in range(4):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err)


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its window; the same rid resumes from
    it with no prefill. A preempted slot loses both and prefills prompt +
    emitted again. Both then go on the reference's logprobs."""
    eng, cfg = served
    prompt = np.random.default_rng(4).integers(0, cfg["vocab_size"], 19).tolist()

    def interrupted(rid, interrupt):
        box, ev = [], threading.Event()
        g = GenerationHyperparameters(max_new_tokens=120, temperature=1.0, ignore_eos=True)
        eng.submit(ModelRequest(input_ids=prompt, rid=rid, gconfig=g), lambda r: (box.append(r), ev.set()))
        while not any(t is not None and t.req.rid == rid and len(t.out_tokens) >= 8 for t in eng._slot_task):
            time.sleep(0.01)
        interrupt(rid)
        assert ev.wait(120)
        first = box[0]
        assert first.stop_reason == StopReason.ABORT.value and 0 < len(first.output_tokens) < 120
        rest = _gen(eng, prompt + first.output_tokens, 120 - len(first.output_tokens), rid=rid, greedy=False)
        toks = first.output_tokens + rest.output_tokens
        assert len(toks) == 120
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


def test_expert_load_counts_live_rows_only(served):
    """One request decoding 24 tokens on an engine of 6 slots: the chunk's
    counts come back with its tokens and hold 3 experts x 5 expert layers a
    decode step of the ONE live slot; the five dead slots' rows add nothing.
    ``moe.load`` sums to the assignments counter, and an ended slot's window
    stays what it was while nothing runs."""
    eng, cfg = served
    _held(eng)
    load0 = np.asarray(eng.moe_status()["load"])
    a0, t0, s0 = eng._obs.moe_assignments.get(), eng._obs.moe_experts_touched.get(), eng._obs.moe_experts_streamed.get()
    chunks0 = eng.stats["chunks"]
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 21).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        load = np.asarray(eng.moe_status()["load"]) - load0
        steps = eng._obs.moe_assignments.get() - a0
        assert load.shape == (5, 8) and load.sum() == steps
        # every decode step the slot was active in: 24 tokens (the first from the prompt's last token)
        assert steps == 24 * 3 * 5 and (load.sum(axis=1) == 24 * 3).all()
        assert eng._obs.moe_experts_touched.get() - t0 == 24 * 3 * 5  # one live row touches exactly top-k experts a layer
        streamed = eng._obs.moe_experts_streamed.get() - s0  # and XLA's form reads all 8 a layer on every step of a chunk, live rows or none
        assert streamed >= 24 * 8 * 5 and streamed % (8 * 5) == 0
        assert eng.stats["chunks"] - chunks0 >= 6
        assert len(r.output_tokens) == 24
        conv = np.asarray(eng.cache["conv"])
        assert set(eng.cache) == {"k", "v", "conv"}  # the counts are no part of the cache
    finally:
        eng.continue_generation()
    time.sleep(0.3)
    _held(eng)
    try:
        assert np.array_equal(conv, np.asarray(eng.cache["conv"]))
    finally:
        eng.continue_generation()


def test_radix_cache_serves_nothing_and_the_ledger_counts_the_windows(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "recurrent_state"}
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 70).tolist()  # 4 whole pages
    first, again = _gen(eng, prompt, 4), _gen(eng, prompt, 4)
    assert again.output_tokens == first.output_tokens
    assert "cached_prefix_tokens" not in again.metadata and eng.stats["prefix_hit_tokens"] == 0
    led = eng.hbm_ledger()["components"]
    assert led["recurrent_state"] == 5 * 6 * 2 * 64 * 4 == eng._obs.state_bytes.get()  # 5 conv layers x 6 slots x 2 values x 64 channels
    assert led["kv_page_pool"] == 2 * 2 * 2 * eng.slots.pool.n_pages * 16 * 128 * 4  # two attention layers, lane-padded heads


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the short-conv and expert scopes beside the shared
    ones (docs/observability.md "Spans and scopes")."""
    import re

    from areal_tpu.inference import paged_kv
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
    shared = ("embed", "attn_proj", "kv_write", "attn", "mlp")
    for name, lowered, want in (
        ("chunk", chunk, hybrid.CONV_SCOPES + hybrid.MOE_SCOPES[:3] + shared + ("lm_head", "sampler")),
        ("prefill", prefill, hybrid.CONV_SCOPES + hybrid.MOE_SCOPES + shared),
        ("copy_pages", copy, ("state_write",)),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not set(hybrid.SCOPES[:3]) & have  # no state-space mixer here


def test_refused_configurations():
    mcfg = lu.model_config(lu.tiny_model())
    for kw, msg in (
        (dict(speculative=SpeculativeConfig(enabled=True)), "speculative"),
        (dict(quantization="int8"), "int8"),
        (dict(mesh=MeshConfig(data=1, fsdp=1, seq=1, model=2)), "one chip"),
    ):
        scfg = _server_config(**kw)
        eng = DecodeEngine(scfg, params={"embed": jnp.zeros((2, 2))}, model_cfg=mcfg, mesh=_mesh(scfg))
        with pytest.raises(ValueError, match=msg):
            eng.initialize()
    eng, _ = _engine(max_batch_size=2, max_seq_len=64, attn_window_step=64)
    with pytest.raises(ValueError, match="speculative"):
        eng.set_speculative(True)
    assert eng._spec_cfg is None and eng.moe_status() == {"load": [[0] * 8] * 5}
    for refused in (eng.model.forward_verify_paged, eng.model.forward_prefill_paged):
        with pytest.raises(NotImplementedError, match="short-conv"):
            refused()
    with pytest.raises(NotImplementedError):
        eng.model.quantize_params_int8({})
