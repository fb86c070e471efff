"""The decode engine serving a model of the ``cohere2_moe`` family: the first
model whose ONLY slot-owned tenant is a ring (``inference/paged_kv.py``
RING_LEAVES: no recurrent state beside it). Rotary window layers read their
rings, the full layer the page table; a group's siblings get a COPY of the
primary's rings, a parked slot keeps them, a preempted one has them rebuilt
by its re-prefill; the radix cache stays off, for the reason ``window_rings``.

Tiny size of the benchmark configuration's shape (one period S S S F, a
window of 16 in two-page rings, 4 of 16 experts held, top-4, four shared),
float32, seeded weights, against the benchmark's plain reference by logprobs
through prefill and paged decode, never by sampled tokens alone.

Tolerances: float32 on both sides, logits of order 3 over a vocabulary of
500: 2e-4 (measured 1e-5). A ring one token off, a sibling reading the
primary's ring or a stale ring after a preemption moves a logprob by 1e-2 and
more."""

import json
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
import chipbench_cohere2_moe_util as cu  # noqa: E402
from chipbench_util import CHIP, load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402
from areal_tpu.inference.paged_kv import band_pairs  # noqa: E402

REF_TOL = 2e-4
W = cu.WINDOW
RING_BYTES_A_SLOT = 3 * 2 * 4 * 2 * 8 * 128 * 4  # 3 window layers x (K, V) x 4 KV heads x 2 pages of 8 x 128 lanes, float32


def _server_config(**kw):
    base = dict(
        dtype="float32", max_batch_size=10, max_seq_len=512, page_size=8, decode_steps_per_call=4,
        attn_window_step=512, seed=3, mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
        prefix_cache=PrefixCacheConfig(enabled=True),
    )
    return ServerConfig(**{**base, **kw})


def _mesh(scfg):
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[: scfg.mesh.model])


@pytest.fixture(scope="module")
def served():
    cfg = cu.tiny_model()
    scfg = _server_config()
    eng = DecodeEngine(scfg, params=cu.make_params(cfg, 23), model_cfg=cu.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    return cu.reference().token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_a_group_of_8_shares_one_prefill_by_ring_copy(served):
    """A GRPO group of 8 on one prompt of nearly five windows: one prefill,
    seven copies of the primary's rings (and of its last KV page), with no
    state leaf beside them; every sample goes on the reference's logprobs,
    past further windows of its own."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 77).tolist()
    g = GenerationHyperparameters(max_new_tokens=24, temperature=1.0, ignore_eos=True)
    copies, prefills, pairs = eng._obs.state_copies.get(), eng.stats["prefills"], eng._obs.window_prompt_pairs.get()
    _held(eng)
    box, done = {}, threading.Event()
    for i in range(8):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and eng._obs.state_copies.get() == copies + 7
    # ONE prompt pass: sum_t min(t + 1, 16) over 77 tokens, three window layers
    assert eng._obs.window_prompt_pairs.get() - pairs == 3 * band_pairs(77, W) == 3 * (W * (W + 1) // 2 + (77 - W) * W)
    assert len({tuple(box[i].output_tokens) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err)


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its rings; the same rid resumes from
    them with no prefill. A preempted slot loses them and prefills prompt +
    emitted again (the prompt pass rebuilds the ring the steps had written,
    wrapped at another place). Both then go on the reference's logprobs."""
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
    assert eng.stats["kv_resumes"] == resumes + 1 and eng._obs.state_prefills.get() == rebuilt + 1  # counted with no state leaf beside the rings
    assert err.max() < REF_TOL, err


def test_ring_reads_count_live_slots_only_and_the_radix_cache_is_off_for_window_rings(served):
    """One request of 9 prompt tokens decoding 24 on an engine of 10 slots: a
    step at context c reads min(c, 16) ring tokens in each of the three
    window layers of the ONE live slot; the experts' counts move beside it;
    an ended slot's rings stay what they were while nothing runs; the radix
    cache serves nothing and says why; int8 pages are refused beside rings."""
    eng, cfg = served
    _held(eng)
    r0, a0, p0 = eng._obs.window_tokens_read.get(), eng._obs.moe_assignments.get(), eng._obs.window_prompt_pairs.get()
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 9).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        # the step that emits token i feeds position 8 + i: it reads min(9 + i, 16) ring tokens a window layer
        assert eng._obs.window_tokens_read.get() - r0 == 3 * sum(min(9 + i, W) for i in range(24))
        assert eng._obs.moe_assignments.get() - a0 == 24 * 4 * 4  # 4 expert layers x top-4
        assert eng._obs.window_prompt_pairs.get() - p0 == 3 * (9 * 10 // 2) and len(r.output_tokens) == 24
        rings = {k: np.asarray(eng.cache[k]) for k in ("ring_k", "ring_v")}
        assert set(eng.cache) == {"k", "v", "ring_k", "ring_v"}  # no state leaf; the counts are no part of the cache
    finally:
        eng.continue_generation()
    time.sleep(0.3)
    _held(eng)
    try:
        for k, v in rings.items():
            assert np.array_equal(v, np.asarray(eng.cache[k]))
    finally:
        eng.continue_generation()
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "window_rings"}
    led = eng.hbm_ledger()["components"]
    assert led["window_rings"] == 11 * RING_BYTES_A_SLOT and led["recurrent_state"] == 0  # 10 slots and the padding row's block
    view = eng.kv_pools_status()
    assert view["serving_limit"] == "window_rings" and view["state_bytes"] == 0 and view["window_bytes"] == 11 * RING_BYTES_A_SLOT
    assert view["window"]["writers"] == [0, 1, 2] and view["window"]["keeps"] == W and view["window"]["pages_per_slot"] == 2
    assert view["full"]["writers"] == [3] and view["full"]["keeps"] is None
    cfg8 = _server_config(kv_quantization="int8")
    with pytest.raises(ValueError, match="rings"):
        DecodeEngine(cfg8, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(cfg8)).initialize()


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the window layers' scopes, the rotary embedding's
    and the parallel block's one sum beside the shared ones
    (docs/observability.md "Spans and scopes"); no second norm's ``mlp`` and
    no ``moe_combine`` of a serial block."""
    from areal_tpu.inference import paged_kv
    from areal_tpu.models import hybrid

    eng, _ = served
    _held(eng)  # the cache is the loop's while it runs
    try:
        S, psz = eng.config.max_batch_size, eng.config.page_size
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        with jax.set_mesh(eng.mesh):
            chunk = eng.programs.chunk_fn(4, 2, False, False, False).lower(eng.params, eng.cache, i32(S, 2), eng._dev_state, eng._rng)
            prefill = eng.programs.prefill_fn(1, 256).lower(eng.params, eng.cache, i32(1, 256), i32(1), i32(256 // psz), i32(1))
            copy = jax.jit(paged_kv.copy_pages).lower(eng.cache, i32(1), i32(1), i32(1), i32(1))
    finally:
        eng.continue_generation()
    with open(os.path.join(CHIP, "configs", cu.CONFIG + ".json")) as f:
        full = cu.model_config(json.load(f), dtype="bfloat16")
    # at the published widths a prompt goes alone: the stream and the ONE norm's output beside it, the queries, their
    # rotated copy and the attention's output (0.5 GB each at 16k tokens), a block of the shared experts' gate, up and product
    assert hybrid.ffn_block_rows(full, "moe", 16384) == 4096
    assert hybrid.prefill_row_bytes(full, 16384) == 2 * 16384 * 4096 * 2 + 3 * 16384 * 16384 * 2 + 3 * 4096 * 16384 * 2 > 64 << 20
    assert eng.programs.prefill_sizes(256) == (8, 4, 2, 1)  # the tiny model's rows go eight at a time
    shared = ("embed", "attn_proj", "kv_write", "attn", "attn_window", hybrid.MOE_SHARED_SCOPE) + hybrid.PARALLEL_SCOPES
    shared += tuple(s for s in hybrid.MOE_SCOPES if s != "moe_combine")
    for name, lowered, want in (
        ("chunk", chunk, shared + ("lm_head", "sampler")),
        ("prefill", prefill, shared),
        ("copy_pages", copy, ("kv_write",)),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not ({"mlp", "state_write", "attn_diff"} | set(hybrid.SCOPES[:3])) & have
