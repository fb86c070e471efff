"""The decode engine serving a model with recurrent (state-space) layers: the
invariant of ``inference/paged_kv.py`` (a slot's state is the state after
exactly the tokens the host believes the slot has consumed) through prefill,
decode, group admission, parking, preemption and the refusals.

Tiny size of the benchmark configuration's shape (2 Mamba-2 layers around 1
attention layer), float32, seeded weights, against the benchmark's plain
reference by logprobs, never by sampled tokens alone.

Tolerances: the engine's float32 logprobs against the reference's full
forward agree to float32 rounding of logits of order 1 summed over a
vocabulary of 512: 2e-5. A token fed twice to a state, a missed token or a
neighbour's state moves a logprob by 1e-2 and more;
states that must not have been touched are compared bit for bit."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_hybrid_util as hu  # noqa: E402

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


def _engine(**kw):
    hu.load_run()
    from benchlib import hybrid_weights

    cfg = hu.tiny_model()
    mcfg = hu.model_config(cfg)
    params = hybrid_weights.make_params(cfg, 23, jnp.float32)
    scfg = _server_config(**kw)
    eng = DecodeEngine(scfg, params=params, model_cfg=mcfg, mesh=_mesh(scfg))
    eng.initialize()
    return eng, cfg


def _mesh(scfg):
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[: scfg.mesh.model])


@pytest.fixture(scope="module")
def served():
    eng, cfg = _engine()
    eng.start()
    yield eng, cfg
    eng.stop()


def _reference(eng, cfg, prompt, out):
    from benchlib import hybrid_reference

    return hybrid_reference.token_logprobs(eng.params, cfg, list(prompt) + list(out), pad_to=512)[len(prompt) - 1 :]


def _gen(eng, prompt, n, rid="", greedy=True):
    g = GenerationHyperparameters(max_new_tokens=n, greedy=greedy, temperature=1.0, ignore_eos=True)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _count(metric) -> float:
    return metric.get()


def test_batched_prefill_then_decode_matches_reference(served):
    """Prompts of different lengths in one prefill bucket (one batch: the
    engine pads it with a row of its own), then 40 sampled tokens each."""
    eng, cfg = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (5, 33, 64)]
    g = GenerationHyperparameters(max_new_tokens=40, temperature=1.0, ignore_eos=True)
    before = eng.stats["prefill_batches"]
    eng.pause_generation("hold")  # so that all three are admitted in one wave
    assert eng.wait_fence_ack(30)
    box = {}
    done = threading.Event()
    for i, p in enumerate(prompts):
        eng.submit(ModelRequest(input_ids=p, gconfig=g), lambda r, i=i: (box.__setitem__(i, r), len(box) == 3 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefill_batches"] == before + 2  # 3 rows: a batch of 2 and one of 1
    for i, p in enumerate(prompts):
        r = box[i]
        assert len(r.output_tokens) == 40 and r.stop_reason == StopReason.LENGTH.value
        err = np.abs(np.asarray(r.output_logprobs) - _reference(eng, cfg, p, r.output_tokens))
        assert err.max() < REF_TOL, (i, err.max())


def test_group_siblings_start_from_the_primarys_state(served):
    """A GRPO group of 4 on one prompt: one prefill, three state copies. Each
    sibling samples its own tokens from the primary's post-prompt state: its
    first-step logprob is the reference's log p(token | prompt), the same
    distribution for all four, and so is every later one."""
    eng, cfg = served
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 37).tolist()
    g = GenerationHyperparameters(max_new_tokens=12, temperature=1.0, ignore_eos=True)
    copies, prefills = _count(eng._obs.state_copies), eng.stats["prefills"]
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)
    reqs = [ModelRequest(input_ids=prompt, gconfig=g) for _ in range(4)]
    box, done = {}, threading.Event()
    for i, r in enumerate(reqs):
        eng.submit(r, lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 4 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and _count(eng._obs.state_copies) == copies + 3
    assert len({tuple(box[i].output_tokens) for i in range(4)}) > 1  # they do not walk one path
    for i in range(4):
        err = np.abs(np.asarray(box[i].output_logprobs) - _reference(eng, cfg, prompt, box[i].output_tokens))
        assert err[0] < REF_TOL and err.max() < REF_TOL, (i, err)


def test_ended_slot_keeps_its_state_while_neighbours_decode(served):
    eng, cfg = served
    rng = np.random.default_rng(2)
    g = lambda n: GenerationHyperparameters(max_new_tokens=n, greedy=True, ignore_eos=True)  # noqa: E731
    short_done, long_box = threading.Event(), []
    eng.submit(ModelRequest(input_ids=rng.integers(0, 512, 9).tolist(), rid="short", gconfig=g(6)), lambda r: short_done.set())
    eng.submit(ModelRequest(input_ids=rng.integers(0, 512, 21).tolist(), rid="long", gconfig=g(440)), long_box.append)
    assert short_done.wait(120)

    def held_snapshot():
        eng.pause_generation("hold")
        assert eng.wait_fence_ack(30)
        snap = {k: np.asarray(eng.cache[k]) for k in ("ssm", "conv")}
        long_slot = next(i for i, t in enumerate(eng._slot_task) if t is not None and t.req.rid == "long")
        n_out = len(eng._slot_task[long_slot].out_tokens)
        eng.continue_generation()
        return snap, long_slot, n_out

    a, long_slot, n_a = held_snapshot()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        time.sleep(0.05)
        b, _, n_b = held_snapshot()
        if n_b >= n_a + 8:  # two chunks and more
            break
    assert n_b >= n_a + 8, "the long request did not advance"
    others = [s for s in range(eng.config.max_batch_size) if s != long_slot]
    for k in ("ssm", "conv"):
        assert np.array_equal(a[k][:, others], b[k][:, others]), k  # bit for bit, the ended slot among them
        assert not np.array_equal(a[k][:, long_slot], b[k][:, long_slot]), k
    while not long_box:
        time.sleep(0.05)


def test_interrupted_generation_equals_its_uninterrupted_twin(served):
    """Pause-abort parks the slot with its state; the same rid resumes from it
    with no prefill. A preempted slot loses both and prefills prompt +
    emitted again. Both then go on as the uninterrupted twin does: on the
    reference's logprobs of their own sampled tokens (the engine reports 0
    for a greedy token, so the twins are held to the reference, not to each
    other's samples)."""
    eng, cfg = served
    prompt = np.random.default_rng(4).integers(0, cfg["vocab_size"], 19).tolist()
    twin = _gen(eng, prompt, 120, greedy=False)
    assert np.abs(np.asarray(twin.output_logprobs) - _reference(eng, cfg, prompt, twin.output_tokens)).max() < REF_TOL

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
        eng.pause_generation("hold")
        assert eng.wait_fence_ack(30)  # the loop idles: its bookkeeping is ours for a moment
        slot = next(i for i, t in enumerate(eng._slot_task) if t is not None and t.req.rid == rid)
        eng._apply_slot_updates([eng._preempt(slot)])
        eng.continue_generation()

    resumes, rebuilt = eng.stats["kv_resumes"], _count(eng._obs.state_prefills)
    err = interrupted("parked", park)
    assert eng.stats["kv_resumes"] == resumes + 1 and _count(eng._obs.state_prefills) == rebuilt
    assert err.max() < REF_TOL, err
    err = interrupted("preempted", preempt)
    assert eng.stats["kv_resumes"] == resumes + 1 and _count(eng._obs.state_prefills) == rebuilt + 1
    assert err.max() < REF_TOL, err


def test_radix_cache_serves_nothing_to_a_recurrent_model(served):
    eng, cfg = served
    assert eng.config.prefix_cache.enabled and eng.slots.radix is None
    assert eng.prefix_cache_stats() == {"enabled": False, "disabled_by": "recurrent_state"}
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 70).tolist()  # 4 whole pages
    first = _gen(eng, prompt, 4)
    again = _gen(eng, prompt, 4)
    assert again.output_tokens == first.output_tokens
    assert "cached_prefix_tokens" not in again.metadata and eng.stats["prefix_hit_tokens"] == 0
    led = eng.hbm_ledger()["components"]
    mcfg = eng.model_cfg
    assert led["recurrent_state"] == 2 * 6 * (8 * 16 * 16 * 4 + 3 * mcfg.conv_dim * 4)
    assert led["kv_page_pool"] == 2 * 1 * 2 * eng.slots.pool.n_pages * 16 * 128 * 4  # one attention layer, lane-padded
    assert _count(eng._obs.state_bytes) == led["recurrent_state"]


def test_lowered_programs_hold_the_familys_scopes(served):
    """The decode chunk and the prefill program keep their names (``chunk``,
    ``prefill``) and carry the four scopes of this family beside the shared
    ones (docs/observability.md "Spans and scopes")."""
    import re

    from areal_tpu.inference import paged_kv
    from areal_tpu.models import hybrid

    eng, _ = served
    eng.pause_generation("hold")  # the cache is the loop's while it runs
    assert eng.wait_fence_ack(30)
    try:
        S, psz = eng.config.max_batch_size, eng.config.page_size
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        with jax.set_mesh(eng.mesh):
            chunk = eng.programs.chunk_fn(4, 2, False, False, False).lower(eng.params, eng.cache, i32(S, 2), eng._dev_state, eng._rng)
            prefill = eng.programs.prefill_fn(2, 256).lower(eng.params, eng.cache, i32(2, 256), i32(2), i32(2 * 256 // psz), i32(2))
            copy = jax.jit(paged_kv.copy_pages).lower(eng.cache, i32(1), i32(1), i32(1), i32(1))
    finally:
        eng.continue_generation()
    for name, lowered, want in (
        ("chunk", chunk, hybrid.SCOPES + ("embed", "attn_proj", "kv_write", "attn", "mlp", "lm_head", "sampler")),
        ("prefill", prefill, hybrid.SCOPES + ("embed", "attn_proj", "kv_write", "attn", "mlp")),
        ("copy_pages", copy, ("state_write",)),
    ):
        text = lowered.as_text(debug_info=True)
        assert f"@jit_{name}" in text  # the names the benchmark's readers find the programs by
        have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
        assert not set(want) - have, (name, sorted(set(want) - have))


def test_refused_configurations():
    hu.load_run()
    mcfg = hu.model_config(hu.tiny_model())
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
    assert eng._spec_cfg is None
    with pytest.raises(NotImplementedError):
        eng.model.forward_verify_paged()
