"""The decode engine's programs are plain ``jax.jit`` callables under the names
the benchmark's readers look for.

``benchmarks/chip`` finds the programs in a device trace by module name
(``jit_chunk``, ``jit_prefill``; ``jit_step`` is held by
``test_trace_spans.py``: PERF.md section 3) and the
speculative phases by span name; a renamed program turns a per-layer metric
into ``null``. And a jitted program retraces when the weights it is handed
change dtype, which a held ahead-of-time executable could not."""

import glob
import time

import jax
import jax.numpy as jnp
import pytest

from areal_tpu.api.config import SpeculativeConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference import speculative
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.server import flatten_params

from tpu_testing import tiny_decode_engine

PSZ = 16


def _engine(**kw) -> DecodeEngine:
    return tiny_decode_engine(max_batch_size=2, max_seq_len=256, **kw)


@pytest.fixture(scope="module")
def spec_engine():
    eng = _engine(speculative=SpeculativeConfig(enabled=True))
    eng.start()
    yield eng
    eng.stop()


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _lower(eng: DecodeEngine, program: str):
    S, wp = eng.config.max_batch_size, 4
    pt = _i32(S, wp)
    if program == "chunk":
        return eng.programs.chunk_fn(4, wp, False, True, False).lower(eng.params, eng.cache, pt, eng._dev_state, eng._rng)
    if program == "prefill":
        return eng.programs.prefill_fn(1, 256).lower(eng.params, eng.cache, _i32(1, 256), _i32(1), _i32(256 // PSZ), _i32(1))
    if program == "prefill_sfx":
        return eng.programs.prefill_paged_fn(1, 256, wp).lower(
            eng.params, eng.cache, _i32(1, 256), _i32(1), _i32(1), _i32(256 // PSZ), _i32(1, wp)
        )
    B = eng._spec_cfg.max_nodes()
    bundle = speculative.empty_bundle(S, B - 1)
    drafts = {k: jnp.asarray(getattr(bundle, k)) for k in ("tokens", "parent_row", "depth", "mask", "n_draft")}
    return eng.programs.spec_fn(B, wp, False, True).lower(eng.params, eng.cache, pt, eng._dev_state, eng._rng, drafts)


@pytest.mark.parametrize(
    "program,module",
    [("chunk", "jit_chunk"), ("prefill", "jit_prefill"), ("prefill_sfx", "jit_prefill"), ("spec", "jit_spec")],
)
def test_lowered_module_carries_the_name_the_readers_match(spec_engine, program, module):
    with jax.set_mesh(spec_engine.mesh):
        text = _lower(spec_engine, program).as_text()
    assert f"module @{module} " in text, text[:200]


def test_speculative_round_emits_draft_and_verify_spans(spec_engine, tmp_path):
    """``areal.decode.draft`` / ``.verify`` (PERF.md section 3's name table),
    each inside an ``areal.decode.pass``, from one served request."""
    prompt = [5, 8, 1, 5, 8, 1, 5, 8, 1, 5, 8]  # periodic: the n-gram drafter proposes
    req = ModelRequest(input_ids=prompt, gconfig=GenerationHyperparameters(max_new_tokens=16, greedy=True))
    spec_engine.generate_sync(req, timeout=120)  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    rounds = spec_engine.stats["spec_rounds"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        spec_engine.generate_sync(req, timeout=120)
        time.sleep(0.3)  # the response leaves from inside the last pass
    finally:
        jax.profiler.stop_trace()
    assert spec_engine.stats["spec_rounds"] > rounds
    (path,) = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")
    spans: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("areal.decode."):
                    spans.setdefault(ev.name, []).append((i, ev.start_ns, ev.start_ns + ev.duration_ns))
    passes = spans["areal.decode.pass"]
    for name in ("areal.decode.draft", "areal.decode.verify"):
        assert spans.get(name), sorted(spans)
        for line, s, e in spans[name]:
            assert any(p[0] == line and p[1] <= s and e <= p[2] for p in passes), f"{name} outside every pass"


def _commit_in_dtype(eng: DecodeEngine, leaves, dtype, version: int) -> None:
    """A staged weight commit whose named leaves (all, for None) arrive in
    ``dtype``: ``stage_weight_bucket`` casts every leaf to the serving dtype,
    so the bucket is laid into the staging area as a caller holding device
    arrays of its own would hand it over."""
    flat = flatten_params(eng.params)
    eng.begin_staged_update()
    with eng._weight_lock:
        eng._staged_flat.update(
            {k: jnp.asarray(v, dtype if leaves is None or k in leaves else None) for k, v in flat.items()}
        )
    eng.commit_staged_weights(version)


@pytest.mark.parametrize("leaves", [("embed",), None], ids=["one_leaf", "whole_tree"])
def test_programs_survive_a_dtype_change_of_the_weights(leaves):
    """Prefill and chunk were built for float32 weights; after a commit that
    changes a leaf's dtype their executables do not take the call, and the
    same jitted functions (retraced, under the same ``_fn_cache`` keys) serve
    the next request, to the tokens and logprobs of an engine with the same
    history whose programs were built anew for the new weights."""
    req = ModelRequest(input_ids=list(range(3, 40)), gconfig=GenerationHyperparameters(max_new_tokens=12))
    served = _engine()
    rebuilt = _engine()
    served.start()
    rebuilt.start()
    try:
        first = served.generate_sync(req, timeout=120)
        assert rebuilt.generate_sync(req, timeout=120).output_logprobs == first.output_logprobs
        programs = dict(served.programs._fn_cache)
        assert {k[0] for k in programs} >= {"prefill", "chunk", "upd"}
        _commit_in_dtype(served, leaves, jnp.bfloat16, 1)
        _commit_in_dtype(rebuilt, leaves, jnp.bfloat16, 1)
        rebuilt.programs._fn_cache.clear()
        flat = flatten_params(served.params)
        assert {k for k, v in flat.items() if v.dtype == jnp.bfloat16} == set(leaves or flat)
        after = served.generate_sync(req, timeout=120)
        want = rebuilt.generate_sync(req, timeout=120)
        # retraced, not rebuilt: a program that takes weights is now its jitted function, the others as they were
        now = served.programs._fn_cache
        assert all(now[k] is (fn._fn if k[0] in ("prefill", "chunk") else fn) for k, fn in programs.items())
        assert after.output_versions == [1] * 12
        assert after.output_tokens == want.output_tokens
        assert after.output_logprobs == want.output_logprobs and all(lp < 0 for lp in after.output_logprobs)
    finally:
        served.stop()
        rebuilt.stop()
