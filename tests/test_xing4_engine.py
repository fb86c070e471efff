"""The decode engine serving a model whose residual path is FOUR streams
(``xing4_0``: ``residual_form`` ``mhc`` over the latent-attention block with a
YaRN-scaled rotary key): the chunk program carries [slots, 4 x hidden] through
its steps, a GRPO group's siblings alias the primary's latent pages, a
preempted slot's re-prefill rebuilds its pages under the same rotary table; the
two counters of the stream mixes, ``/statusz``'s ``residual`` block, the scopes
and the latent branch's refusals.

Tiny size of the benchmark configuration's shape (2 dense + 4 expert layers,
4 of 16 experts held, top-4, one shared, YaRN's original length 32 against
contexts of 19-140 tokens), float32, seeded weights, against the benchmark's
plain reference by logprobs through prefill and paged decode, never by sampled
tokens alone.

Tolerances: float32 on both sides, logits of order 1 over a vocabulary of 500:
2e-5 (measured 3e-6). A stream mixed under a neighbour's coefficients, a
rotary key turned by the plain frequencies, or a sibling reading a stale page
moves a logprob by 1e-2 and more."""

import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_xing4_util as xu  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 2e-5
LAYERS = 6


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


@pytest.fixture(scope="module")
def served():
    cfg = xu.tiny_model()
    scfg = _server_config()
    eng = DecodeEngine(scfg, params=xu.make_params(cfg, 23), model_cfg=xu.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    return xu.reference().token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_a_group_of_8_carries_four_streams_over_one_prefill(served):
    """A GRPO group of 8 on one prompt of 77 tokens (past YaRN's original
    length of 32): ONE prefill whose (token, sublayer) mixes are counted from
    its length, seven siblings on the primary's latent pages with no slot
    tenant to copy; every sample goes on the reference's logprobs, the chunk
    program mixing its four streams a step and sublayer."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 77).tolist()
    g = GenerationHyperparameters(max_new_tokens=24, temperature=1.0, ignore_eos=True)
    _held(eng)
    prefills, mixed, rows = eng.stats["prefills"], eng._obs.prefill_mhc_token_sublayers.get(), eng._obs.mhc_row_sublayers.get()
    copies = eng._obs.state_copies.get()
    box, done = {}, threading.Event()
    for i in range(8):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    _held(eng)
    try:
        assert eng.stats["prefills"] == prefills + 1 and eng._obs.state_copies.get() == copies  # latent pages alias: nothing of a slot's to copy
        assert eng._obs.prefill_mhc_token_sublayers.get() - mixed == 77 * 2 * LAYERS
        # every credited token is a live row of one step: 2 sublayers x 6 layers each (a row may run a step past its end: at least)
        assert eng._obs.mhc_row_sublayers.get() - rows >= 8 * 24 * 2 * LAYERS
        assert (eng._obs.mhc_row_sublayers.get() - rows) % (2 * LAYERS) == 0
    finally:
        eng.continue_generation()
    assert len({tuple(box[i].output_tokens) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err)


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its pages; the same rid resumes from
    them with no prefill. A preempted slot loses them and prefills prompt +
    emitted again: the re-prefill's four-stream prompt pass writes the rows
    the decode steps had written, rotated by the same table at the same
    positions (the prompt crosses the original length of 32 while decoding)."""
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

    resumes, prefills = eng.stats["kv_resumes"], eng.stats["prefills"]
    err = interrupted("parked", park)
    assert eng.stats["kv_resumes"] == resumes + 1 and eng.stats["prefills"] == prefills + 1
    assert err.max() < REF_TOL, err
    err = interrupted("preempted", preempt)
    assert eng.stats["kv_resumes"] == resumes + 1 and eng.stats["prefills"] == prefills + 3  # the prompt, then prompt + emitted again
    assert err.max() < REF_TOL, err


def test_counters_status_and_the_latent_branchs_refusals(served):
    """One request of 9 prompt tokens decoding 24 on an engine of 10 slots:
    the stream mixes count the ONE live row, the latent rows read are its
    context x 6 layers, the experts' counts move beside them; ``/statusz``
    names the residual form and its streams; no radix reuse, no int8 pages, no
    verification over latent pages."""
    eng, cfg = served
    _held(eng)
    rows0, mixed0, read0, a0 = (
        eng._obs.mhc_row_sublayers.get(), eng._obs.prefill_mhc_token_sublayers.get(), eng._obs.latent_tokens_read.get(), eng._obs.moe_assignments.get(),
    )
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 9).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        assert eng._obs.mhc_row_sublayers.get() - rows0 == 24 * 2 * LAYERS and len(r.output_tokens) == 24
        assert eng._obs.prefill_mhc_token_sublayers.get() - mixed0 == 9 * 2 * LAYERS
        # the step that emits token i feeds position 8 + i: it reads 9 + i cached rows in each of the six layers
        assert eng._obs.latent_tokens_read.get() - read0 == LAYERS * sum(9 + i for i in range(24))
        assert eng._obs.moe_assignments.get() - a0 == 24 * 4 * 4  # 4 expert layers x top-4
        assert set(eng.cache) == {"k"}  # one latent pool, no slot tenant; the counts are no part of the cache
    finally:
        eng.continue_generation()
    assert eng.residual_status() == {"form": "mhc", "streams": 4, "sinkhorn_iters": 20}
    assert eng.moe_status()["held"] == [0, 4]
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "latent_pages"}
    cfg8 = _server_config(kv_quantization="int8")
    with pytest.raises(ValueError, match="latent pages"):
        DecodeEngine(cfg8, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(cfg8)).initialize()
    spec = _server_config(speculative=SpeculativeConfig(enabled=True))
    with pytest.raises(ValueError, match="latent"):
        DecodeEngine(spec, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(spec)).initialize()


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the five stream scopes beside the latent block's
    and the experts' (docs/observability.md "Spans and scopes"); the chunk
    hands back the mixes' count beside its tokens."""
    from areal_tpu.models import hybrid

    eng, _ = served
    _held(eng)  # the cache is the loop's while it runs
    try:
        S, psz = eng.config.max_batch_size, eng.config.page_size
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        with jax.set_mesh(eng.mesh):
            chunk = eng.programs.chunk_fn(4, 2, False, False, False).lower(eng.params, eng.cache, i32(S, 2), eng._dev_state, eng._rng)
            prefill = eng.programs.prefill_fn(1, 256).lower(eng.params, eng.cache, i32(1, 256), i32(1), i32(256 // psz), i32(1))
    finally:
        eng.continue_generation()
    assert "mhc_row_sublayers" in eng.model_cfg.count_shapes and eng.model_cfg.count_shapes["mhc_row_sublayers"] == (1,)
    assert eng.programs.prefill_sizes(256) == (8, 4, 2, 1)  # the tiny model's rows go eight at a time: 4 streams of 56 are small
    # (a decode step's dense expert form gathers nothing back and the streams' post-mix takes the residual add's place: no ``moe_combine``)
    shared = ("embed", "mla_q_lora", "mla_proj", "kv_write", "attn", "mlp", hybrid.MOE_SHARED_SCOPE) + hybrid.MOE_SCOPES[:3] + hybrid.MHC_SCOPES
    for name, lowered, want in (("chunk", chunk, shared + ("lm_head", "sampler")), ("prefill", prefill, tuple(s for s in shared if s != "mhc_merge"))):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not {"block_sum", "attn_window", "state_write", "dsa_select"} & have
