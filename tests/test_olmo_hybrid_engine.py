"""The decode engine serving a model of the ``olmo_hybrid`` family:
gated-delta-rule layers whose matrix state and three conv windows are the
third tenant of the slot-indexed recurrent state (``inference/paged_kv.py``
STATE_LEAVES: a slot's state is the state after exactly the tokens the host
believes the slot has consumed), full attention without a rotary embedding
on the paged KV pool, and the chunk's count of state updates.

Tiny size of the benchmark configuration's shape (two periods of three
linear-attention layers and one attention layer), float32, seeded weights,
against the benchmark's plain reference (its delta rule token by token) by
logprobs through prefill and paged decode, never by sampled tokens alone.

Tolerances: float32 on both sides, logits of order 1 over a vocabulary of
512, the chunked scan and the masked step against a token loop: 1e-4
(measured 1e-5 to 3e-5). A state one token off, a token fed twice or a
neighbour's state moves a logprob by 1e-2 and more."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 1e-4
STATE_BYTES_A_SLOT = 6 * (4 * 24 * 64 + 3 * 4 * (24 + 24 + 64)) * 4  # 6 delta-rule layers: the state and the windows, float32


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
    cfg = ou.tiny_model()
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=ou.make_params(cfg, 23), model_cfg=ou.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    from benchlib import olmo_hybrid_reference

    return olmo_hybrid_reference.token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_batched_prefill_then_decode_matches_reference(served):
    """Prompts of different lengths in one prefill bucket (under a chunk of
    the scan, one chunk and a part, two whole), then 40 sampled tokens each:
    the prefill leaves in every slot the state before the prompt's last
    token, decode feeds that token again and goes on."""
    eng, cfg = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (2, 77, 128)]
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


def test_a_group_of_8_shares_one_prefill_by_state_copy(served):
    """A GRPO group of 8 on one prompt: one prefill, seven copies of the
    primary's post-prompt state and windows (and of its last KV page)."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 37).tolist()
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
    """Pause-abort parks the slot with its state; the same rid resumes from
    it with no prefill. A preempted slot loses it and prefills prompt +
    emitted again (the chunked scan rebuilds what the steps had built). Both
    then go on the reference's logprobs."""
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


def test_state_updates_count_live_slots_only(served):
    """One request decoding 24 tokens on an engine of 10 slots: the chunk's
    count comes back with its tokens and holds one update a delta-rule layer
    a decode step of the ONE live slot; the nine dead slots add nothing, and
    an ended slot's state stays what it was while nothing runs."""
    eng, cfg = served
    _held(eng)
    u0, chunks0 = eng._obs.gdn_state_updates.get(), eng.stats["chunks"]
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 21).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        assert eng._obs.gdn_state_updates.get() - u0 == 24 * 6  # 24 tokens (the first from the prompt's last token) x 6 layers
        assert eng.stats["chunks"] - chunks0 >= 6 and len(r.output_tokens) == 24
        state = {k: np.asarray(eng.cache[k]) for k in ("gdn", "conv")}
        assert set(eng.cache) == {"k", "v", "gdn", "conv"}  # the count is no part of the cache
    finally:
        eng.continue_generation()
    time.sleep(0.3)
    _held(eng)
    try:
        for k, v in state.items():
            assert np.array_equal(v, np.asarray(eng.cache[k]))
    finally:
        eng.continue_generation()


def test_radix_cache_serves_nothing_and_the_ledger_counts_the_state(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "recurrent_state"}
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 70).tolist()  # 4 whole pages
    first, again = _gen(eng, prompt, 4), _gen(eng, prompt, 4)
    assert again.output_tokens == first.output_tokens
    assert "cached_prefix_tokens" not in again.metadata and eng.stats["prefix_hit_tokens"] == 0
    led = eng.hbm_ledger()["components"]
    assert led["recurrent_state"] == 10 * STATE_BYTES_A_SLOT == eng._obs.state_bytes.get()
    assert led["kv_page_pool"] == 2 * 2 * 2 * eng.slots.pool.n_pages * 16 * 128 * 4  # two attention layers, 2 heads, lane-padded
    assert eng.attention_impl()["decode"] == "xla"  # off a TPU; on one the Pallas path takes 30/30 heads (test_tpu_compile)


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the delta-rule mixer's scopes beside the shared
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
        ("chunk", chunk, hybrid.GDN_SCOPES + shared + ("lm_head", "sampler")),
        ("prefill", prefill, hybrid.GDN_SCOPES + shared),
        ("copy_pages", copy, ("state_write",)),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not (set(hybrid.SCOPES[:3]) | set(hybrid.CONV_SCOPES[:2]) | set(hybrid.MOE_SCOPES)) & have


def test_refused_configurations():
    mcfg = ou.model_config(ou.tiny_model())
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
    assert eng._spec_cfg is None and eng.moe_status() is None
    for refused in (eng.model.forward_verify_paged, eng.model.forward_prefill_paged):
        with pytest.raises(NotImplementedError, match="delta-rule"):
            refused()
    with pytest.raises(NotImplementedError):
        eng.model.quantize_params_int8({})
