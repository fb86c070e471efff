"""DecodeEngine correctness: incremental KV decode == full forward; abort /
pause / weight-update protocol (replaces reference test_inference_engines.py)."""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import MeshConfig, ServerConfig
from areal_tpu.api.io_struct import (
    GenerationHyperparameters,
    ModelRequest,
    StopReason,
)
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.models import qwen

from tpu_testing import TINY_QWEN2


def _make_engine(n_slots=4, max_len=256, steps=8, mesh=None):
    mesh = mesh or MeshConfig(data=-1, fsdp=1, seq=1, model=2)
    cfg = ServerConfig(
        max_batch_size=n_slots,
        max_seq_len=max_len,
        decode_steps_per_call=steps,
        mesh=mesh,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    return eng


@functools.lru_cache(maxsize=None)
def _full_forward(cfg):
    return jax.jit(lambda params, ids, seg, pos: qwen.compute_logits(params, cfg, qwen.forward(params, cfg, ids, seg, pos)))


def _naive_greedy(params, cfg, prompt, n_new):
    """Greedy decoding by a full forward over the tokens so far, a token a
    forward. Every forward is ONE program: the row is padded to its final
    length (padding is segment 0, after every real token of a causal pass) and
    the logits are read at the last real token."""
    total = len(prompt) + n_new
    pos = np.arange(total, dtype=np.int32)[None]
    ids = list(prompt)
    for _ in range(n_new):
        a = np.zeros((1, total), np.int32)
        a[0, : len(ids)] = ids
        logits = _full_forward(cfg)(params, a, (pos < len(ids)).astype(np.int32), pos)
        ids.append(int(np.argmax(np.asarray(logits)[0, len(ids) - 1])))
    return ids[len(prompt):]


@pytest.fixture(scope="module")
def engine():
    eng = _make_engine()
    eng.start()
    yield eng
    eng.stop()


def test_greedy_matches_full_forward(engine):
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 12).tolist()
    want = _naive_greedy(engine.params, engine.model_cfg, prompt, 16)
    req = ModelRequest(
        input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=16, greedy=True),
    )
    resp = engine.generate_sync(req, timeout=120)
    assert resp.stop_reason == StopReason.LENGTH.value
    assert resp.output_tokens == want, (resp.output_tokens, want)
    assert len(resp.output_logprobs) == 16
    assert len(resp.output_versions) == 16
    assert all(v == 0 for v in resp.output_versions)


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_concurrent_greedy_matches(engine):
    """Several slots decoding together must not interfere."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, int(rng.integers(4, 20))).tolist() for _ in range(4)]
    wants = [_naive_greedy(engine.params, engine.model_cfg, p, 10) for p in prompts]
    results = {}
    lock = threading.Lock()
    done = threading.Event()

    def cb_for(i):
        def cb(resp):
            with lock:
                results[i] = resp
                if len(results) == len(prompts):
                    done.set()

        return cb

    for i, p in enumerate(prompts):
        engine.submit(
            ModelRequest(
                input_ids=p,
                gconfig=GenerationHyperparameters(max_new_tokens=10, greedy=True),
            ),
            cb_for(i),
        )
    assert done.wait(120)
    for i, want in enumerate(wants):
        assert results[i].output_tokens == want, i


def test_stop_token(engine):
    """Generation halts at a stop token and includes it in the output."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 256, 8).tolist()
    free_run = engine.generate_sync(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=24, greedy=True),
        ),
        timeout=120,
    )
    # pick the 5th generated token as the "eos"
    eos = free_run.output_tokens[4]
    first_idx = free_run.output_tokens.index(eos)
    resp = engine.generate_sync(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(
                max_new_tokens=24, greedy=True, stop_token_ids=[eos]
            ),
        ),
        timeout=120,
    )
    assert resp.stop_reason == StopReason.STOP.value
    assert resp.output_tokens == free_run.output_tokens[: first_idx + 1]


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_pause_aborts_and_resume(engine):
    """pause_generation() completes in-flight requests with stop_reason=abort;
    after continue_generation() new requests run (the §3.4 protocol)."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, 8).tolist()
    box = []
    ev = threading.Event()
    engine.submit(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=2048, greedy=True),
        ),
        lambda r: (box.append(r), ev.set()),
    )
    time.sleep(0.3)  # let some chunks run
    engine.pause_generation()
    assert ev.wait(60), "pause must complete the in-flight request"
    resp = box[0]
    assert resp.stop_reason == StopReason.ABORT.value
    engine.continue_generation()
    # resume: resubmit with accumulated tokens (what the client loop does)
    resumed = engine.generate_sync(
        ModelRequest(
            input_ids=prompt + resp.output_tokens,
            gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
        ),
        timeout=120,
    )
    want = _naive_greedy(
        engine.params, engine.model_cfg, prompt, len(resp.output_tokens) + 8
    )
    assert resp.output_tokens + resumed.output_tokens == want


def test_weight_update_bumps_version(engine):
    new_params = jax.tree.map(lambda x: x * 1.01, engine.params)
    engine.update_weights_from_params(
        jax.tree.map(np.asarray, new_params), version=3
    )
    assert engine.get_version() == 3
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 256, 6).tolist()
    resp = engine.generate_sync(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
        ),
        timeout=120,
    )
    assert all(v == 3 for v in resp.output_versions)
    engine.set_version(0)


def test_per_slot_sampling_isolation(engine):
    """A concurrent request with top_p/top_k filtering must not change a
    greedy request's output (round-1 bug: engine-global top_k/top_p were
    compiled into the chunk for ALL slots)."""
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 256, 10).tolist()
    want = _naive_greedy(engine.params, engine.model_cfg, prompt, 12)

    results = {}
    done = threading.Event()
    lock = threading.Lock()

    def cb_for(name):
        def cb(resp):
            with lock:
                results[name] = resp
                if len(results) == 2:
                    done.set()

        return cb

    engine.submit(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=12, greedy=True),
        ),
        cb_for("greedy"),
    )
    engine.submit(
        ModelRequest(
            input_ids=rng.integers(0, 256, 10).tolist(),
            gconfig=GenerationHyperparameters(
                max_new_tokens=12, temperature=2.0, top_p=0.7, top_k=5
            ),
        ),
        cb_for("filtered"),
    )
    assert done.wait(120)
    assert results["greedy"].output_tokens == want
    assert len(results["filtered"].output_tokens) == 12


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_kv_resume_after_abort(engine):
    """Same-rid resubmission after pause resumes from the parked slot KV
    (zero re-prefill) and continues the greedy trajectory exactly."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 256, 8).tolist()
    box = []
    ev = threading.Event()
    engine.submit(
        ModelRequest(
            input_ids=prompt,
            rid="resume-me",
            gconfig=GenerationHyperparameters(max_new_tokens=2048, greedy=True),
        ),
        lambda r: (box.append(r), ev.set()),
    )
    time.sleep(0.3)
    engine.pause_generation()
    assert ev.wait(60)
    resp = box[0]
    assert resp.stop_reason == StopReason.ABORT.value
    engine.continue_generation()
    resumes_before = engine.stats["kv_resumes"]
    resumed = engine.generate_sync(
        ModelRequest(
            input_ids=prompt + resp.output_tokens,
            rid="resume-me",
            gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
        ),
        timeout=120,
    )
    assert engine.stats["kv_resumes"] == resumes_before + 1
    want = _naive_greedy(
        engine.params, engine.model_cfg, prompt, len(resp.output_tokens) + 8
    )
    assert resp.output_tokens + resumed.output_tokens == want


def test_release_resume_memory(engine):
    """Colocated-mode HBM handoff: release drops params+KV, resume restores
    and generation still matches the full forward."""
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 256, 8).tolist()
    want = _naive_greedy(engine.params, engine.model_cfg, prompt, 6)
    engine.pause_generation()
    engine.release_memory()
    assert engine.cache is None
    engine.resume_memory()
    engine.continue_generation()
    resp = engine.generate_sync(
        ModelRequest(
            input_ids=prompt,
            gconfig=GenerationHyperparameters(max_new_tokens=6, greedy=True),
        ),
        timeout=120,
    )
    assert resp.output_tokens == want


def test_temperature_sampling_varies(engine):
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, 6).tolist()
    outs = set()
    for _ in range(4):
        resp = engine.generate_sync(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(max_new_tokens=12, temperature=5.0),
            ),
            timeout=120,
        )
        outs.add(tuple(resp.output_tokens))
    assert len(outs) > 1, "high-temperature sampling should vary"


def test_grpo_prefix_sharing():
    """Identical prompts (a GRPO group) prefill once; duplicates get KV row
    copies and still decode correctly (greedy outputs identical). Drives the
    admission/dispatch cycle directly so all four requests land in ONE
    admission round (the sharing window)."""
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest

    eng = _make_engine()
    prompt = [3, 1, 4, 1, 5]
    results = []
    g = GenerationHyperparameters(max_new_tokens=8, greedy=True)
    for _ in range(4):
        eng.submit(ModelRequest(input_ids=list(prompt), gconfig=g), results.append)
    rows = eng._admit_pending()
    eng._apply_slot_updates(rows)
    assert eng.stats["prefix_shared"] == 3, eng.stats
    assert eng.stats["prefills"] == 1  # ONE forward for the whole group
    for _ in range(10):
        if not any(t is not None for t in eng._slot_task):
            break
        eng._drain(eng._dispatch_chunk())
    assert len(results) == 4
    outs = [tuple(r.output_tokens) for r in results]
    assert len(set(outs)) == 1, outs  # same prompt + greedy -> same tokens
    assert len(outs[0]) == 8
    # matches an unshared single-request run end-to-end
    eng2 = _make_engine()
    eng2.start()
    try:
        ref = eng2.generate_sync(
            ModelRequest(input_ids=list(prompt), gconfig=g), timeout=300
        )
        assert tuple(ref.output_tokens) == outs[0]
    finally:
        eng2.stop()


def test_inverse_cdf_sampler_distribution():
    """The one-uniform-per-row sampler draws from the exact softmax
    distribution and reports exact logprobs (it replaced per-vocab gumbel
    noise, which was ~80% of the decode step at S=128 x V=152k)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference.decode_programs import _inverse_cdf_sample

    n = 4000
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 0.5]] * n, jnp.float32)
    want = np.asarray(jax.nn.softmax(logits[0]))
    ids, logp, _ = jax.jit(_inverse_cdf_sample)(logits, jax.random.PRNGKey(0))
    ids_np, logp_np = np.asarray(ids), np.asarray(logp)
    np.testing.assert_allclose(logp_np, np.log(want[ids_np]), rtol=1e-5)
    freq = np.bincount(ids_np, minlength=5) / n
    np.testing.assert_allclose(freq, want, atol=0.03)


def test_hierarchical_sampler_two_level_path():
    """A row wider than one block of the sampler's partition engages the
    two-level CDF decomposition (block pick + in-block pick, crossing block
    boundaries, the last block partial); the draw must still follow the
    exact softmax and report exact logprobs."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference.decode_programs import _inverse_cdf_sample
    from areal_tpu.ops.vocab_block_stats import block_width

    V = 4500
    W = block_width(V)
    assert W < V and V % W  # two-level path engaged: three blocks, the last of 404 columns
    base = np.full(V, -6.0, np.float32)
    # peaks straddling the block boundaries, and the row's two ends
    peaks = {0: 1.0, W - 1: 2.0, W: 1.5, 2 * W - 1: 1.8, 2 * W: 2.2, V - 1: 1.0}
    for k, v in peaks.items():
        base[k] = v
    n = 4000
    logits = jnp.asarray(np.tile(base, (n, 1)))
    want = np.asarray(jax.nn.softmax(jnp.asarray(base)))
    ids, logp, lse = jax.jit(_inverse_cdf_sample)(logits, jax.random.PRNGKey(1))
    ids_np, logp_np = np.asarray(ids), np.asarray(logp)
    log_softmax = base - np.asarray(lse)[0, 0]
    np.testing.assert_allclose(logp_np, log_softmax[ids_np], rtol=1e-4, atol=1e-5)
    freq = np.bincount(ids_np, minlength=V) / n
    for k in peaks:
        assert abs(freq[k] - want[k]) < 0.03, (k, freq[k], want[k])
    # total mass on non-peak tokens also matches
    mask = np.ones(V, bool)
    mask[list(peaks)] = False
    assert abs(freq[mask].sum() - want[mask].sum()) < 0.03
