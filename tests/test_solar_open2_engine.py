"""The decode engine serving a model of the ``solar_open2`` family: ``kda``
layers whose matrix state (a decay of its own every key channel) and three
conv windows are the fifth tenant of the slot-indexed recurrent state
(``inference/paged_kv.py`` STATE_LEAVES) beside an expert share in EVERY
layer, gated attention without a positional embedding on the paged KV pool,
and the chunk's count of state updates.

Tiny size of the benchmark configuration's shape (two periods G K K K, 4 of
16 experts held, top-4), float32, seeded weights, against the benchmark's
plain reference (its delta rule token by token) by logprobs through prefill
and paged decode, never by sampled tokens alone.

Tolerances: float32 on both sides, logits of order 3 over a vocabulary of
500, the chunked scan and the masked step against a token loop: 2e-4
(measured 2e-5). A state one token off, a token fed twice or a neighbour's
state moves a logprob by 1e-2 and more."""

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
import chipbench_solar_open2_util as su  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402

REF_TOL = 2e-4
STATE_BYTES_A_SLOT = 6 * (8 * 16 * 16 + 3 * 3 * 128) * 4  # 6 kda layers: the state and the three windows, float32


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
    cfg = su.tiny_model()
    scfg = _server_config()
    eng = DecodeEngine(scfg, params=su.make_params(cfg, 23), model_cfg=su.model_config(cfg), mesh=_mesh(scfg))
    eng.initialize()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    return su.reference().token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def test_a_group_of_8_shares_one_prefill_by_state_copy(served):
    """A GRPO group of 8 on one prompt (past a chunk of the scan): one
    prefill, seven copies of the primary's post-prompt kda state and windows
    (and of its last KV page); every sample goes on the reference's
    logprobs."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 77).tolist()
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


@pytest.mark.parametrize("spacing", [0.0, 0.004])
def test_a_group_that_trickles_in_at_an_idle_start_is_admitted_whole(served, monkeypatch, spacing):
    """No hold fence here: the group's requests arrive some milliseconds
    apart at an idle engine, as a rollout client's do, and the first of them
    wakes the loop. The admission waits for the siblings (no prefix cache: a
    late one would pay a prompt pass of its own), so the group still costs
    ONE prefill and seven state copies, and the wait ends a gap after the
    last arrival, not at its cap."""
    from areal_tpu.inference import decode_engine as de

    eng, cfg = served
    assert eng.slots.radix is None
    monkeypatch.setattr(de, "SIBLING_GAP_S_PER_TOKEN", 1e-3)  # 40 tokens: a gap of 40 ms, wide for a CPU under test
    waits = []
    real = eng._await_siblings
    monkeypatch.setattr(eng, "_await_siblings", lambda: waits.append(real()) or waits[-1])
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 40).tolist()
    g = GenerationHyperparameters(max_new_tokens=6, temperature=1.0, ignore_eos=True)
    copies, prefills = eng._obs.state_copies.get(), eng.stats["prefills"]
    box, done = {}, threading.Event()
    for i in range(8):
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
        time.sleep(spacing)
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and eng._obs.state_copies.get() == copies + 7
    assert waits and 0.03 < max(waits) < de.SIBLING_WAIT_S
    for i in range(8):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err.max() < REF_TOL, (i, err)


def test_the_wait_for_siblings_is_short_against_the_prompt(served):
    """The gap follows the longest queued prompt (5 us a token, 50 ms at
    most: a 4k prompt waits 20 ms and more, a 1k prompt 5); nothing queued,
    nothing waited. (An engine WITH a prefix cache never waits:
    tests/test_commit_point.py.)"""
    from areal_tpu.inference import decode_engine as de

    eng, _ = served
    assert eng._await_siblings() < 0.005
    assert de.SIBLING_GAP_S_PER_TOKEN * 16384 > de.SIBLING_GAP_S > de.SIBLING_GAP_S_PER_TOKEN * 4096 > 0.02
    assert de.SIBLING_GAP_S_PER_TOKEN * 1024 < 0.006 and de.SIBLING_WAIT_S >= 5 * de.SIBLING_GAP_S


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its kda state; the same rid resumes
    from it with no prefill. A preempted slot loses it and prefills prompt +
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


def test_state_updates_count_live_slots_only_and_the_ledger_counts_the_state(served):
    """One request decoding 24 tokens on an engine of 10 slots: the chunk's
    count holds one update a kda layer a decode step of the ONE live slot (6
    a step); the experts' counts move beside it; an ended slot's state stays
    what it was while nothing runs; the radix cache serves nothing."""
    eng, cfg = served
    _held(eng)
    u0, chunks0, a0 = eng._obs.kda_state_updates.get(), eng.stats["chunks"], eng._obs.moe_assignments.get()
    eng.continue_generation()
    prompt = np.random.default_rng(7).integers(0, cfg["vocab_size"], 21).tolist()
    r = _gen(eng, prompt, 24)
    _held(eng)
    try:
        assert eng._obs.kda_state_updates.get() - u0 == 24 * 6  # 24 tokens (the first from the prompt's last token) x 6 layers
        assert eng._obs.moe_assignments.get() - a0 == 24 * 8 * 4  # 8 expert layers x top-4
        assert eng.stats["chunks"] - chunks0 >= 6 and len(r.output_tokens) == 24
        state = {k: np.asarray(eng.cache[k]) for k in ("kda", "conv")}
        assert set(eng.cache) == {"k", "v", "kda", "conv"}  # the counts are no part of the cache
    finally:
        eng.continue_generation()
    time.sleep(0.3)
    _held(eng)
    try:
        for k, v in state.items():
            assert np.array_equal(v, np.asarray(eng.cache[k]))
    finally:
        eng.continue_generation()
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "recurrent_state"}
    led = eng.hbm_ledger()["components"]
    assert led["recurrent_state"] == 10 * STATE_BYTES_A_SLOT == eng._obs.state_bytes.get()


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the kda mixer's scopes, the attention gate's and
    the expert block's beside the shared ones (docs/observability.md "Spans
    and scopes")."""
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
    import json

    from chipbench_util import CHIP

    with open(os.path.join(CHIP, "configs", su.CONFIG + ".json")) as f:
        full = su.model_config(json.load(f), dtype="bfloat16")
    # at the published widths a row's scan temporaries (0.4 GB a block of 1,024 tokens) make every prompt go alone
    assert hybrid.prefill_row_bytes(full, 256) == 12 * 256 * 8192 * 4 and hybrid.prefill_row_bytes(full, 16384) == 12 * 1024 * 8192 * 4 > 64 << 20
    shared = ("embed", "attn_proj", "kv_write", "attn", hybrid.ATTN_GATE_SCOPE, hybrid.MOE_SHARED_SCOPE) + hybrid.MOE_SCOPES
    for name, lowered, want in (
        ("chunk", chunk, hybrid.KDA_SCOPES + shared + ("lm_head", "sampler")),
        ("prefill", prefill, hybrid.KDA_SCOPES + shared),
        ("copy_pages", copy, ("state_write",)),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))
        assert not (set(hybrid.SCOPES[:3]) | set(hybrid.GDN_SCOPES[:3]) | {"mlp"}) & have


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_prefill_programs_scan_follows_kda_prefill_launch(served, monkeypatch, backend):
    """``hybrid.kda_prefill_launch`` from the backend and the head's widths
    alone; a prefill program's blocks scan under ``ops/kda_prompt_scan`` where
    it says so and under ``kda_chunked_scan`` elsewhere (this backend, and
    heads that are no whole lane tiles), and the engine counts
    ``areal_decode_prefill_kda_launch_tokens_total`` by it where it
    dispatches the program."""
    import json

    from chipbench_util import CHIP

    from areal_tpu.models import hybrid, qwen
    from areal_tpu.observability import catalog
    from areal_tpu.ops import kda_prompt_scan as kps

    eng, cfg = served
    with open(os.path.join(CHIP, "configs", su.CONFIG + ".json")) as f:
        full = su.model_config(json.load(f), dtype="bfloat16")
    assert catalog.engine_metrics().prefill_kda_launch_tokens.name == "areal_decode_prefill_kda_launch_tokens_total"
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tiny = eng.model_cfg
    assert (tiny.kda_k_dim, tiny.kda_v_dim, full.kda_k_dim, full.kda_v_dim) == (16, 16, 128, 128)
    assert hybrid.kda_prefill_launch(full, 16384) == hybrid.kda_prefill_launch(full, 256) == (backend == "tpu")
    assert not hybrid.kda_prefill_launch(tiny, 256)  # heads of 16 x 16 keep the XLA form on any backend
    assert not qwen.kda_prefill_launch(None, 256)
    # the choice inside the program: the launch's entry is reached exactly where the predicate says
    calls = []
    monkeypatch.setattr(kps, "kda_prompt_scan", lambda *a, **kw: calls.append(a[0].shape) or hybrid.kda_chunked_scan(*a, **kw))
    says = []
    real = hybrid.kda_prefill_launch
    monkeypatch.setattr(hybrid, "kda_prefill_launch", lambda c, L: says.append(L) or real(c, L) or backend == "tpu")
    psz = eng.config.page_size
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    bucket = 128 if backend == "cpu" else 384  # a bucket no other test has traced
    _held(eng)
    try:
        with jax.set_mesh(eng.mesh):
            jax.make_jaxpr(
                lambda *x: hybrid.prefill_into_cache(eng.params, tiny, *x, page_size=psz)
            )(eng.cache, i32(1, bucket), i32(1), i32(bucket // psz), i32(1))
    finally:
        eng.continue_generation()
    assert says and set(says) == {bucket}
    # one launch site a run of kda layers, a block of whole chunks (384 tokens go in blocks of 128)
    assert calls == ([(128, 8, 16)] * 2 if backend == "tpu" else [])
    monkeypatch.undo()
    # the counter: nothing on this backend; every prompt token of a program whose module says it scans under the launch
    launched, prefilled = eng._obs.prefill_kda_launch_tokens.get(), eng._obs.prefill_tokens.get()
    rng = np.random.default_rng(48)
    _gen(eng, rng.integers(0, cfg["vocab_size"], 21).tolist(), 2)
    assert eng._obs.prefill_tokens.get() == prefilled + 21 and eng._obs.prefill_kda_launch_tokens.get() == launched
    if backend == "tpu":
        buckets = []
        monkeypatch.setattr(eng.model, "kda_prefill_launch", lambda mcfg, b: buckets.append(b) or mcfg is eng.model_cfg)
        _gen(eng, rng.integers(0, cfg["vocab_size"], 23).tolist(), 2)
        assert eng._obs.prefill_kda_launch_tokens.get() == launched + 23 and len(buckets) == 1


def test_refused_configurations():
    mcfg = su.model_config(su.tiny_model())
    for kw, msg in (
        (dict(speculative=SpeculativeConfig(enabled=True)), "speculative"),
        (dict(quantization="int8"), "int8"),
        (dict(mesh=MeshConfig(data=1, fsdp=1, seq=1, model=2)), "one chip"),
    ):
        scfg = _server_config(**kw)
        eng = DecodeEngine(scfg, params={"embed": jnp.zeros((2, 2))}, model_cfg=mcfg, mesh=_mesh(scfg))
        with pytest.raises(ValueError, match=msg):
            eng.initialize()
