"""``DecodeEngine.precompile()`` is complete: after it, serving compiles nothing.

Every benchmark cell's "0 compilations inside the window" rests on the warm
set ``precompile()`` derives. Each case builds a tiny engine, precompiles,
then serves a wave that touches every prompt bucket, the greedy / sampled /
top-k chunk variants, a group admission (page copies), a preemption on an
exhausted pool and a drain, and holds the count of XLA compilations
(``utils/compile_cache.compile_stats``) and their program names to what the
case allows. Two cases pin what ``precompile()`` does NOT warm today (PERF.md
section 7): the suffix-only prefill of a radix hit, and every speculative
program."""

import collections
import dataclasses
import logging
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
import chipbench_hybrid_util as hu  # noqa: E402

from areal_tpu.api.config import PrefixCacheConfig, SpeculativeConfig  # noqa: E402
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason  # noqa: E402
from areal_tpu.inference.decode_engine import DecodeEngine  # noqa: E402
from areal_tpu.utils import compile_cache, perf_tracer  # noqa: E402

from tpu_testing import TINY_QWEN2, tiny_decode_engine  # noqa: E402

JITTED = type(jax.jit(lambda: 0))
_FINISHED = re.compile(r"Finished XLA compilation of (\S+)")


class _CompiledNames(logging.Handler):
    """Program names of the XLA compilations ``jax._src.dispatch`` logs."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record):
        m = _FINISHED.search(record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.fixture()
def compiled_names():
    handler = _CompiledNames()
    log = logging.getLogger("jax._src.dispatch")
    level, propagate = log.level, log.propagate
    log.setLevel(logging.DEBUG)  # the compile lines are DEBUG without jax_log_compiles
    log.propagate = False  # and stay out of pytest's capture
    log.addHandler(handler)
    yield handler.names
    log.removeHandler(handler)
    log.setLevel(level)
    log.propagate = propagate


def _engine(case: str) -> DecodeEngine:
    kw = {
        "bf16": dict(dtype="bfloat16"),
        "int8kv": dict(kv_quantization="int8"),
        "spec": dict(speculative=SpeculativeConfig(enabled=True)),
        "radix": dict(prefix_cache=PrefixCacheConfig(enabled=True)),
        "hybrid": dict(dtype="float32"),
    }[case]
    if case == "hybrid":
        hu.load_run()
        from benchlib import hybrid_weights

        cfg = hu.tiny_model()
        return tiny_decode_engine(
            hu.model_config(cfg), hybrid_weights.make_params(cfg, 23, jnp.float32), max_batch_size=2, **kw
        )
    mcfg = dataclasses.replace(TINY_QWEN2, dtype="bfloat16") if case == "bf16" else TINY_QWEN2
    return tiny_decode_engine(mcfg, max_batch_size=2, **kw)


def _request(ids, n, **g) -> ModelRequest:
    return ModelRequest(input_ids=list(ids), gconfig=GenerationHyperparameters(max_new_tokens=n, **g))


def _wave(eng, reqs, timeout=300.0):
    done = threading.Event()
    got = []

    def cb(resp):
        got.append(resp)
        if len(got) == len(reqs):
            done.set()

    for r in reqs:
        eng.submit(r, cb)
    assert done.wait(timeout), f"{len(got)}/{len(reqs)} finished"
    return got


def _serve_everything(eng: DecodeEngine, case: str) -> None:
    """The traffic every case sends after ``precompile()``."""
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(1, 200, n).tolist()

    # every prompt bucket (256 and the 512 cap), each chunk variant
    assert eng.programs.reachable_prompt_buckets() == [256, 512]
    _wave(eng, [_request(prompt(100), 8, greedy=True)])
    _wave(eng, [_request(prompt(300), 8, greedy=True)])
    # the other three (capped, greedy) variants, one request each so that the
    # variant does not depend on which pass admits what
    for g in (dict(), dict(top_k=5), dict(top_k=5, greedy=True)):
        _wave(eng, [_request(prompt(40), 8, **g)])
    # a group admission: one prompt, two samples (a page copy; a state copy
    # for the recurrent family)
    shared0 = eng.stats.get("prefix_shared", 0)
    group = prompt(70)
    _wave(eng, [_request(group, 6) for _ in range(2)])
    assert eng.stats["prefix_shared"] - shared0 == 1
    if case == "radix":
        base = prompt(64)
        _wave(eng, [_request(base + prompt(20), 4, greedy=True)])
        hit0 = eng.stats["prefix_hit_tokens"]
        _wave(eng, [_request(base + prompt(20), 4, greedy=True)])
        assert eng.stats["prefix_hit_tokens"] - hit0 == 64
    # a preemption: the pool is held down to 24 pages, two requests want 17 each
    eng.flush_prefix_cache()  # or the radix tree's pages would be reclaimed first
    hostage = eng.slots.pool.alloc(eng.slots.pool.available - 24)
    got = _wave(eng, [_request(prompt(60), 200, ignore_eos=True) for _ in range(2)])
    eng.slots.pool.free(hostage)
    assert eng.stats.get("preempted", 0) >= 1
    assert sorted(r.stop_reason for r in got).count(StopReason.ABORT.value) == eng.stats["preempted"]
    # a drain with requests in flight: they are parked or aborted, none is lost
    ended = []
    for _ in range(2):
        eng.submit(_request(prompt(30), 300, ignore_eos=True), ended.append)
    deadline = time.monotonic() + 60
    while eng.admission_snapshot()["active_slots"] < 2:
        assert time.monotonic() < deadline, "the two requests were never admitted"
        time.sleep(0.01)
    summary = eng.drain(budget_s=0.05)
    assert summary["leaked_pages"] == 0
    assert summary["unterminated_timelines"] == 0
    assert len(ended) == 2


# what each case may compile after precompile(): nothing, or the named gap
ALLOWED = {
    "bf16": {},
    "int8kv": {},
    "hybrid": {},
    # one suffix-only prefill shape (suffix bucket 256 x 4 prefix pages): the
    # docstring of precompile() says these compile lazily, on the first hit
    "radix": {"jit(prefill)": 1},
    # the four (capped, greedy) rounds: precompile() warms no speculative program
    "spec": {"jit(spec)": 4},
}


@pytest.mark.parametrize("case", sorted(ALLOWED))
def test_nothing_compiles_after_precompile(case, compiled_names):
    assert compile_cache.install_compile_counters()
    eng = _engine(case)
    try:
        eng.precompile()
        # the warm set: each program's loaded executable, built as its first
        # call would build it (``compile_cache.FirstCall``), over the jitted
        # callable under the name a device trace shows (jit_chunk,
        # jit_prefill: PERF.md section 3); 1 window x 4 (capped, greedy)
        # chunks, 2 scatter + 2 clamp sizes, 1 page-copy size, 2 prompt
        # buckets x 4 group sizes
        built = eng.programs._fn_cache.values()
        assert all(type(fn) is compile_cache.BuiltProgram and type(fn._fn) is JITTED for fn in built)
        assert all(type(fn._compiled) is jax.stages.Compiled for fn in built)
        builds = [e for e in perf_tracer.get_tracer().record().entries if e.name == "areal.program.build"][-len(built):]
        assert len(builds) == 17 and {b.args["served"] for b in builds} == {"jit"}  # no store on a CPU
        assert {(key[0], fn.__name__) for key, fn in eng.programs._fn_cache.items()} == {
            ("chunk", "chunk"), ("prefill", "prefill"), ("upd", "apply"), ("clamp", "clamp"), ("pagecopy", "copy_pages"),
        }
        assert collections.Counter(k[0] for k in eng.programs._fn_cache) == {
            "chunk": 4, "upd": 2, "clamp": 2, "pagecopy": 1, "prefill": 8,
        }
        before = compile_cache.compile_stats()["compiles"]
        compiled_names.clear()
        eng.start()
        _serve_everything(eng, case)
        compiled = collections.Counter(compiled_names)
        assert dict(compiled) == ALLOWED[case]
        assert compile_cache.compile_stats()["compiles"] - before == sum(ALLOWED[case].values())
        # and the runtime path traced none of the warm set again: its calls went through the executables
        assert all(type(eng.programs._fn_cache[k]) is compile_cache.BuiltProgram for k in eng.programs.warm_keys())
    finally:
        eng.stop()
