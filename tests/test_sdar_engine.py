"""The decode engine serving a block-diffusion model (``sdar_moe``): a chunk is
``decode_steps_per_call`` PASSES, a slot emits 0 to 4 tokens a pass by block,
slots at different phases of their blocks share a pass; ``/generate`` returns
the pass that committed each token beside its logprob and weights' version; a
GRPO group's siblings alias the primary's prompt pages up to a block boundary;
an abort mid-block returns whole blocks and the same rid resumes on a block
boundary; the counters of passes, blocks and emitted tokens, and the scopes.

Tiny size of the benchmark configuration's shape (``chipbench_sdar_util``),
float32, seeded weights, against the benchmark's plain reference by logprobs
at the reported passes (``trace_logprobs``), never by sampled tokens alone.

Tolerance: float32 on both sides, logits of order 1 over a vocabulary of 300:
2e-5 (measured 3e-6). A sibling reading a page a commit pass had not written,
or a resumed request restarting inside a block, moves a logprob by 1e-2 and
more."""

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
import chipbench_sdar_util as su  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from benchlib import loadgen  # noqa: E402

from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402
from areal_tpu.inference.server import ServerThread  # noqa: E402

REF_TOL = 2e-5
STEPS = 4  # passes a chunk


def _server_config(**kw):
    base = dict(
        dtype="float32", max_batch_size=10, max_seq_len=256, page_size=16, decode_steps_per_call=STEPS,
        attn_window_step=256, seed=3, host="127.0.0.1", mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
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
    server = ServerThread(scfg, eng)
    server.start()
    yield eng, cfg, server.address
    server.stop()


def _trace_err(eng, cfg, prompt, resp):
    assert len(resp.output_denoise_pass) == len(resp.output_tokens) == len(resp.output_logprobs) == len(resp.output_versions)
    want = su.reference().trace_logprobs(eng.params, cfg, list(prompt) + list(resp.output_tokens), len(prompt), resp.output_denoise_pass, pad_to=256)
    return np.abs(np.asarray(resp.output_logprobs) - want)


def _gen(eng, prompt, n, rid="", **kw):
    g = GenerationHyperparameters(max_new_tokens=n, temperature=1.0, ignore_eos=True, **kw)
    return eng.generate_sync(ModelRequest(input_ids=list(prompt), rid=rid, gconfig=g), timeout=300)


def _held(eng):
    eng.pause_generation("hold")
    assert eng.wait_fence_ack(30)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 300, n).tolist()


def test_generate_returns_the_trace_and_the_counters_count_passes_blocks_and_emitted_tokens(served):
    """One request over HTTP: 11 prompt tokens (3 past a block boundary), 22
    new tokens under each rule as a request parameter. ``max_new_tokens``
    tokens come back with the pass of each, which the reference confirms;
    ``areal_decode_generated_tokens_total`` counts the EMITTED tokens,
    ``areal_decode_steps_total`` passes, and the block counters the
    slot-passes: blocks of 1 + 4 x 5 + 1 open positions = 7 blocks, 7 commit
    passes, and under ``sequential`` at two positions a pass 1 + 2 x 5 + 1 = 12
    denoise passes."""
    eng, cfg, addr = served
    prompt = _prompt(11, 1)
    load0, assigned_all = np.asarray(eng.moe_status()["load"]), 0
    for rule in ("sequential", "low_confidence_static", "low_confidence_dynamic"):
        _held(eng)
        obs = eng._obs
        before = [c.get() for c in (obs.generated_tokens, obs.steps, obs.block_denoise_passes, obs.block_commit_passes, obs.blocks, obs.moe_assignments)]
        ledger = dict(eng._row_steps)
        eng.continue_generation()
        sampling = {"max_new_tokens": 22, "temperature": 1.0, "ignore_eos": True, "remasking_strategy": rule, "denoising_steps": 2, "confidence_threshold": 0.02}
        status, out = loadgen.post(addr, "/generate", {"input_ids": prompt, "sampling_params": sampling})
        assert status == 200 and len(out["output_tokens"]) == 22 and out["stop_reason"] == "length"
        assert len(out["output_denoise_pass"]) == len(out["output_logprobs"]) == len(out["output_versions"]) == 22
        want = su.reference().trace_logprobs(eng.params, cfg, prompt + out["output_tokens"], 11, out["output_denoise_pass"], pad_to=256)
        assert np.abs(np.asarray(out["output_logprobs"]) - want).max() < REF_TOL
        _held(eng)
        try:
            tokens, passes, denoise, commit, blocks, assigned = (
                c.get() - b for c, b in zip((obs.generated_tokens, obs.steps, obs.block_denoise_passes, obs.block_commit_passes, obs.blocks, obs.moe_assignments), before)
            )
            assert tokens == 22 and commit == blocks == 7
            assert passes % STEPS == 0 and passes >= denoise + commit  # whole chunks of passes; the last one runs on past the end
            if rule == "sequential":
                assert out["output_denoise_pass"] == [0] + [0, 0, 1, 1] * 5 + [0] and denoise == 12
            else:
                assert 12 <= denoise <= 1 + 4 * 5 + 1
            assert assigned == (denoise + commit) * 4 * 2 * cfg["num_hidden_layers"]  # a live slot-pass: 4 rows x top-2, a layer
            assigned_all += assigned
            led = {k: eng._row_steps[k] - ledger[k] for k in ledger}
            assert led["steps"] == passes and led["rows"] % 4 == 0  # a pass steps a slot for 4 rows
            assert led["spent"] == led["rows"] * STEPS - 22 and led["dropped"] == 0
        finally:
            eng.continue_generation()
    load = np.asarray(eng.moe_status()["load"])
    assert load.shape == (cfg["num_hidden_layers"], cfg["num_experts"]) and (load - load0).sum() == assigned_all  # /statusz's loads: the same rows


def test_a_group_of_8_shares_its_prompt_pages_and_every_sample_reads_the_reference(served):
    """A GRPO group of 8 on one prompt of 37 tokens (two pages and 5 tokens;
    one past a block boundary): ONE prefill, the siblings on the primary's
    whole prompt pages with a copy of the page the first block is committed
    into; 8 slots at different phases of their blocks in the same passes
    (outputs of 9 to 30 tokens); each goes on the reference at its passes."""
    eng, cfg, _ = served
    prompt = _prompt(37, 2)
    _held(eng)
    prefills, shared = eng.stats["prefills"], eng.stats.get("prefix_shared", 0)
    box, done = {}, threading.Event()
    for i in range(8):
        g = GenerationHyperparameters(max_new_tokens=9 + 3 * i, temperature=1.0, ignore_eos=True)
        eng.submit(ModelRequest(input_ids=prompt, gconfig=g), lambda resp, i=i: (box.__setitem__(i, resp), len(box) == 8 and done.set()))
    eng.continue_generation()
    assert done.wait(300)
    assert eng.stats["prefills"] == prefills + 1 and eng.stats["prefix_shared"] == shared + 7
    assert len({tuple(box[i].output_tokens[:9]) for i in range(8)}) > 1  # they do not walk one path
    for i in range(8):
        assert len(box[i].output_tokens) == 9 + 3 * i
        err = _trace_err(eng, cfg, prompt, box[i])
        assert err.max() < REF_TOL, (i, err)
    # a later request with the same first two pages hits the radix cache: its suffix prompt pass attends block-causally
    again = prompt[:32] + _prompt(7, 3)
    hits = eng.stats["prefix_cache_hits"]
    r = _gen(eng, again, 10)
    assert eng.stats["prefix_cache_hits"] == hits + 1 and _trace_err(eng, cfg, again, r).max() < REF_TOL


def test_an_abort_mid_block_returns_whole_blocks_and_the_rid_resumes_on_the_boundary(served):
    """Pause-abort while a request decodes: what comes back ends on a block
    boundary (prompt of 10: 2 + 4m tokens) with every block's trace, the
    in-flight block is dropped with its candidates, and the same rid resumes
    from its parked pages with no prefill, a fresh block at the boundary."""
    eng, cfg, _ = served
    prompt = _prompt(10, 4)
    box, ev = [], threading.Event()
    g = GenerationHyperparameters(max_new_tokens=150, temperature=1.0, ignore_eos=True)
    eng.submit(ModelRequest(input_ids=prompt, rid="parked", gconfig=g), lambda r: (box.append(r), ev.set()))
    while not any(t is not None and t.req.rid == "parked" and len(t.out_tokens) >= 6 for t in eng._slot_task):
        time.sleep(0.005)
    resumes, prefills = eng.stats["kv_resumes"], eng.stats["prefills"]
    eng.pause_generation("abort")
    assert eng._pause_ack.wait(60)
    eng.continue_generation()
    assert ev.wait(120)
    first = box[0]
    n = len(first.output_tokens)
    assert first.stop_reason == StopReason.ABORT.value and 6 <= n < 150 and (10 + n) % 4 == 0
    assert _trace_err(eng, cfg, prompt, first).max() < REF_TOL
    rest = _gen(eng, prompt + first.output_tokens, 31, rid="parked")
    assert eng.stats["kv_resumes"] == resumes + 1 and eng.stats["prefills"] == prefills
    assert len(rest.output_tokens) == 31 and rest.output_denoise_pass[:4] == [0, 0, 1, 1]
    assert _trace_err(eng, cfg, prompt + first.output_tokens, rest).max() < REF_TOL


def test_a_weight_update_between_passes_tags_tokens_by_the_pass_that_committed_them(served):
    """The commit behind a hold fence lands between two chunks: a token
    committed by a pass of the chunk before it carries the old version even
    where its block is emitted after it; nothing before the fence carries the
    new one."""
    eng, cfg, _ = served
    prompt = _prompt(12, 5)
    v0 = eng.get_version()
    box, ev = [], threading.Event()
    g = GenerationHyperparameters(max_new_tokens=120, temperature=1.0, ignore_eos=True)
    eng.submit(ModelRequest(input_ids=prompt, rid="", gconfig=g), lambda r: (box.append(r), ev.set()))
    while not any(t is not None and len(t.out_tokens) >= 8 for t in eng._slot_task):
        time.sleep(0.005)
    _held(eng)
    seen = max(len(t.out_tokens) for t in eng._slot_task if t is not None)
    eng.update_weights_from_params(eng.params, version=v0 + 1)  # the same weights under a new number
    eng.continue_generation()
    assert ev.wait(120)
    r = box[0]
    vers = r.output_versions
    assert len(vers) == 120 and set(vers) == {v0, v0 + 1} and vers == sorted(vers)
    assert all(v == v0 for v in vers[:seen]) and vers[-1] == v0 + 1
    assert _trace_err(eng, cfg, prompt, r).max() < REF_TOL
    eng.set_version(v0)


def test_what_a_block_model_refuses_and_what_its_programs_carry(served):
    """No speculative round and no frequency penalty for a model whose step
    is no token step; the chunk keeps its name and carries ``block_select``
    beside the scopes the block pass keeps; page and context sizes must be
    whole blocks."""
    eng, _, _ = served
    assert eng.programs.block == 4 and eng.programs.chunk_ahead == (STEPS // 2 + 1) * 4 and eng.programs.update_cols == 19 + 4 + 4
    spec = _server_config(speculative=SpeculativeConfig(enabled=True))
    with pytest.raises(ValueError, match="block-diffusion"):
        DecodeEngine(spec, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(spec)).initialize()
    odd = _server_config(max_seq_len=254)
    with pytest.raises(ValueError, match="whole blocks"):
        DecodeEngine(odd, params=eng.params, model_cfg=eng.model_cfg, mesh=_mesh(odd)).initialize()
    _held(eng)
    try:
        S = eng.config.max_batch_size
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        with jax.set_mesh(eng.mesh):
            chunk = eng.programs.chunk_fn(STEPS, 16, False, False, False).lower(eng.params, eng.cache, i32(S, 16), eng._dev_state, eng._rng, i32())
    finally:
        eng.continue_generation()
    text = chunk.as_text(debug_info=True)
    assert "@jit_chunk" in text
    have = {part for loc in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", loc)}
    want = {"embed", "attn_proj", "attn", "kv_write", "mlp", "moe_router", "moe_dispatch", "moe_experts", "lm_head", "sampler", "block_select"}
    assert not want - have, sorted(want - have)
    assert ("chunk", STEPS, 16, False, False, False) in eng.programs.warm_keys()
