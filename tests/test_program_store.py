"""The program store (``utils/compile_cache.py``): a built program's executable
on disk beside the XLA cache, read at the program's first call in place of its
trace and lowering. On the CPU the store is off, so every case hands one its
directory by the constructor."""

import dataclasses
import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.utils import compile_cache, perf_tracer
from areal_tpu.utils.compile_cache import FirstCall, ProgramStore

from tpu_testing import TINY_QWEN2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _step(x, cache):
    return x * 2.0 + cache["k"].sum(), {"k": cache["k"] + x.sum(), "n": cache["n"] + 1}


def _args(n=4, dtype=jnp.float32):
    return jnp.arange(n, dtype=dtype), {"k": jnp.ones((2, n), dtype), "n": jnp.zeros((), jnp.int32)}


def _first_call(store, cfg=TINY_QWEN2, fn=_step, key=("step", 4)):
    """What a builder does at a miss of its own cache: (cache, the first call)."""
    cache = {key: jax.jit(fn, donate_argnames=("cache",))}
    return cache, FirstCall(cache, key, store, compile_cache.describe(("test_program_store", cfg, key)))


def _served(n_before: int) -> list[str]:
    builds = [e for e in perf_tracer.get_tracer().record().entries if e.name == "areal.program.build"]
    return [b.args["served"] for b in builds[n_before:]]


def _n_builds() -> int:
    return len(_served(0))


@pytest.fixture(autouse=True)
def _no_summary_line_after_the_test():
    """The line that says what set-up built comes from a timer a few seconds
    after the last build: by then pytest has closed the stream it would go to."""
    yield
    compile_cache._say_when_settled(False)


@pytest.fixture()
def said():
    """WARNING lines of the module's logger (it does not reach pytest's capture)."""
    lines: list[str] = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: lines.append(record.getMessage())
    log = logging.getLogger("areal_tpu.compile_cache")
    log.addHandler(handler)
    yield lines
    log.removeHandler(handler)


def test_a_donated_argument_program_round_trips_bit_for_bit(tmp_path):
    n0, stats0 = _n_builds(), compile_cache.store_stats()
    x, cache = _args()
    kept = jax.tree.map(np.array, cache)  # copies: a view of a CPU buffer would hold it against the donation
    programs, first = _first_call(ProgramStore(str(tmp_path)))
    y1, c1 = first(x, cache)
    assert cache["k"].is_deleted() and len(os.listdir(tmp_path)) == 1  # donated; one entry, no temporary file left
    # another builder (another process would do the same): the entry's executable, with its donation
    again, first = _first_call(ProgramStore(str(tmp_path)))
    cache2 = jax.tree.map(jnp.asarray, kept)
    y2, c2 = first(x, cache2)
    assert _served(n0) == ["jit", "store"]
    assert cache2["k"].is_deleted() and cache2["n"].is_deleted()
    assert np.array_equal(y1, y2) and all(np.array_equal(c1[k], c2[k]) for k in c1)
    # the cache keeps the loaded executable: the next call goes through it, and still donates
    built = again["step", 4]
    assert type(built).__name__ == "BuiltProgram" and type(built._compiled).__name__ == "Compiled"
    y3, c3 = built(x, c2)
    assert c2["k"].is_deleted() and int(c3["n"]) == 2
    stats = compile_cache.store_stats()
    assert (stats["hits"] - stats0["hits"], stats["misses"] - stats0["misses"], stats["refused"] - stats0["refused"]) == (1, 1, 0)
    assert stats["load_seconds"] > stats0["load_seconds"] and stats["write_seconds"] > stats0["write_seconds"]


def test_a_program_sharded_over_a_mesh_loads_onto_the_devices_it_was_built_for(tmp_path):
    """An executable loads onto the devices it is told: the entry keeps the
    ids it was built for (here four that are not the process's first four)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n0 = _n_builds()
    mesh = Mesh(np.array(jax.devices()[2:6]).reshape(2, 2), ("data", "model"))

    def step(w, x):
        return jnp.dot(x, w).sum(), w + 1.0

    def call():
        w = jax.device_put(jnp.ones((8, 8)), NamedSharding(mesh, P(None, "model")))
        x = jax.device_put(jnp.ones((4, 8)), NamedSharding(mesh, P("data", None)))
        cache = {("step",): jax.jit(step, donate_argnums=(0,))}
        with jax.set_mesh(mesh):
            total, w2 = FirstCall(cache, ("step",), ProgramStore(str(tmp_path)), compile_cache.describe(("sharded", mesh)))(w, x)
        assert w.is_deleted() and w2.sharding == NamedSharding(mesh, P(None, "model"))
        return float(total), np.asarray(w2)

    first, second = call(), call()
    assert _served(n0) == ["jit", "store"]
    assert first[0] == second[0] == 256.0 and np.array_equal(first[1], second[1])


def _placed(args, device=0):
    return jax.tree.map(lambda a: jax.device_put(a, jax.devices()[device]), args)


CHANGES = {
    # name -> (arguments, configuration): each changes ONE thing the executable is a function of
    "nothing": lambda mp: (_placed(_args()), TINY_QWEN2),
    "source_digest": lambda mp: (mp.setattr(compile_cache, "source_digest", lambda: "another tree"), (_placed(_args()), TINY_QWEN2))[1],
    "argument_shape": lambda mp: (_placed(_args(8)), TINY_QWEN2),
    "argument_dtype": lambda mp: (_placed(_args(dtype=jnp.bfloat16)), TINY_QWEN2),
    "argument_sharding": lambda mp: (_placed(_args(), device=1), TINY_QWEN2),
    "model_config_field": lambda mp: (_placed(_args()), dataclasses.replace(TINY_QWEN2, rope_theta=5e5)),
    "jax_version": lambda mp: (mp.setattr(jax, "__version__", "0.0.0+other"), (_placed(_args()), TINY_QWEN2))[1],
}


@pytest.mark.parametrize("change", list(CHANGES))
def test_a_change_of_what_the_executable_is_a_function_of_is_a_miss(tmp_path, monkeypatch, change):
    n0 = _n_builds()
    _first_call(ProgramStore(str(tmp_path)))[1](*_placed(_args()))
    (x, cache), cfg = CHANGES[change](monkeypatch)
    y, c = _first_call(ProgramStore(str(tmp_path)), cfg)[1](x, cache)  # a new store: it reads its environment anew
    assert _served(n0) == ["jit", "store" if change == "nothing" else "jit"]
    assert len(os.listdir(tmp_path)) == (1 if change == "nothing" else 2)  # the miss wrote its own entry beside the first
    assert int(c["n"]) == 1 and y.dtype == x.dtype


def test_a_cut_payload_is_a_miss_with_one_warning_and_is_written_anew(tmp_path, said):
    n0 = _n_builds()
    _first_call(ProgramStore(str(tmp_path)))[1](*_args())
    (entry,) = os.listdir(tmp_path)
    path = os.path.join(tmp_path, entry)
    whole = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(whole[: len(whole) // 2])
    y, c = _first_call(ProgramStore(str(tmp_path)))[1](*_args())
    assert _served(n0) == ["jit", "jit"] and int(c["n"]) == 1
    assert len(said) == 1 and "did not load" in said[0] and entry in said[0]
    assert abs(os.path.getsize(path) - len(whole)) < 64  # the build that followed wrote it anew (packed: to a few bytes)
    _first_call(ProgramStore(str(tmp_path)))[1](*_args())
    assert _served(n0)[-1] == "store" and len(said) == 1


def test_a_program_that_closes_over_an_array_is_refused_and_counted(tmp_path, said):
    n0, refused0 = _n_builds(), compile_cache.store_stats()["refused"]
    weights = jnp.arange(64.0)  # its values would be constants of the executable, and are in no key

    def closes_over(x, cache):
        return x + weights[: x.shape[0]], {**cache, "n": cache["n"] + 1}

    for _ in range(2):  # never stored: the second builder traces it again
        y, c = _first_call(ProgramStore(str(tmp_path)), fn=closes_over)[1](*_args())
        assert np.array_equal(y, 2 * np.arange(4.0)) and int(c["n"]) == 1
    assert _served(n0) == ["refused", "refused"] and os.listdir(tmp_path) == []
    assert compile_cache.store_stats()["refused"] - refused0 == 2
    assert len(said) == 2 and all("closes over 64 array elements" in line for line in said)
    # a scalar made by the code (a constant of the source, which the key digests) is no closure over data
    scale = jnp.float32(3.0)
    _first_call(ProgramStore(str(tmp_path)), fn=lambda x, cache: (x * scale, cache))[1](*_args())
    assert _served(n0)[-1] == "jit" and len(os.listdir(tmp_path)) == 1


def _table(n, dtype=np.float32):
    """A traced function that makes a host table of ``n`` elements (as the flash kernels' tile lists and the scaled
    rotary table are made: numpy, from the configuration and the shapes) and reads its first rows."""
    return lambda x, cache: (x + jnp.asarray(np.arange(n, dtype=dtype))[: x.shape[0]], {**cache, "n": cache["n"] + 1})


def _closed_over(n):
    device = jnp.arange(float(n))  # made outside the trace, on the device: some process's values
    return lambda x, cache: (x + device[: x.shape[0]], {**cache, "n": cache["n"] + 1})


def _tile_tables(nb):
    """The flash launch's tables as ``ops/flash_kernels.py`` makes them (which key tiles a query tile runs, and the inner
    index), at two call sites: ``bool[1, nb, nb]`` and ``int32[nb]`` twice."""

    def site(x):
        runs, inner = np.tril(np.ones((1, nb, nb), bool)), np.arange(nb, dtype=np.int32)
        return 0.5 * jnp.where(jnp.asarray(runs)[0, nb - 1, : x.shape[0]], jnp.asarray(inner)[: x.shape[0]], 0)

    return lambda x, cache: (x + site(x) + site(x), {**cache, "n": cache["n"] + 1})


def _both(f, g):
    return lambda x, cache: f(*g(x, cache))


_BOUND = compile_cache._MAX_HOST_CONST_BYTES
CONSTANTS = {
    # name -> (the traced function, stored?, what the WARNING says, the output over arange(4))
    "host_table_112": (lambda: _table(112), True, None, 2.0),  # the train step's tile tables at 3 x 4,096
    "host_table_2048": (lambda: _table(2048), True, None, 2.0),
    "host_table_16": (lambda: _table(16), True, None, 2.0),
    "host_tile_tables_bool_and_int32": (lambda: _tile_tables(16), True, None, 2.0),  # a 16,384 prompt's, as cells 10 and 11 trace them
    "host_table_at_the_byte_bound": (lambda: _table(_BOUND, np.int8), True, None, 2.0),
    "device_array_16": (lambda: _closed_over(16), True, None, 2.0),  # as before: a few scalars
    "device_array_17": (lambda: _closed_over(17), False, "closes over 17 array elements", 2.0),
    "device_array_beside_a_host_table": (lambda: _both(_closed_over(17), _table(112)), False, "closes over 17 array elements", 3.0),
    "host_constant_over_the_byte_bound": (lambda: _table(_BOUND // 4 + 1), False, "bytes of host constants", 2.0),  # a weight handed in as numpy
    "host_tables_over_the_bound_together": (lambda: _both(_table(_BOUND // 8 + 1), _table(_BOUND // 8)), False, "bytes of host constants", 3.0),
}


@pytest.mark.parametrize("case", list(CONSTANTS))
def test_host_tables_the_traced_code_made_are_stored_and_device_arrays_are_not(tmp_path, said, case):
    make, stored, warning, times = CONSTANTS[case]
    n0, refused0 = _n_builds(), compile_cache.store_stats()["refused"]
    outs = []
    for _ in range(2):  # two builders, as two processes would be
        y, c = _first_call(ProgramStore(str(tmp_path)), fn=make())[1](*_args())
        outs.append(np.asarray(y))
    assert all(np.array_equal(y, times * np.arange(4.0)) for y in outs)
    if stored:
        assert _served(n0) == ["jit", "store"] and len(os.listdir(tmp_path)) == 1 and said == []
        assert compile_cache.store_stats()["refused"] == refused0
    else:
        assert _served(n0) == ["refused", "refused"] and os.listdir(tmp_path) == []
        assert len(said) == 2 and all(warning in line for line in said)
        assert compile_cache.store_stats()["refused"] - refused0 == 2


def test_what_has_no_description_is_refused_and_an_unstored_program_still_runs(tmp_path, said):
    n0 = _n_builds()
    assert compile_cache.describe(object()) is None and compile_cache.describe(jnp.ones(2)) is None
    assert compile_cache.describe((TINY_QWEN2, np.dtype("int8"), {"a": (1, "x")}, jnp, _step)) is not None
    key = ("step", 4)
    cache = {key: jax.jit(_step, donate_argnames=("cache",))}
    y, c = FirstCall(cache, key, ProgramStore(str(tmp_path)), compile_cache.describe((object(), key)))(*_args())
    assert _served(n0) == ["refused"] and int(c["n"]) == 1 and os.listdir(tmp_path) == []
    assert len(said) == 1 and "no process-independent description" in said[0]
    # a store that is off (every CPU run) reads and writes nothing, and says nothing
    y, c = _first_call(ProgramStore(None))[1](*_args())
    assert _served(n0) == ["refused", "jit"] and int(c["n"]) == 1 and len(said) == 1


def test_a_mismatched_later_call_goes_to_the_jitted_function_with_its_donated_arguments_intact(tmp_path, said):
    refused0 = compile_cache.store_stats()["refused"]
    programs, first = _first_call(ProgramStore(str(tmp_path)))
    first(*_args())
    built = programs["step", 4]
    x8, cache8 = _args(8)  # the key's shape field lied: the executable was built for 4
    kept = jax.tree.map(np.array, cache8)
    y, c = built(x8, cache8)
    # the executable refused BEFORE it ran (nothing was donated to it); the jitted function then took the same arrays
    assert np.array_equal(y, 2 * np.arange(8.0) + 16) and np.array_equal(c["k"], kept["k"] + 28) and int(c["n"]) == 1
    assert cache8["k"].is_deleted()  # donated once, to the call that ran
    assert type(programs["step", 4]).__name__ == "PjitFunction"  # the key's executable is not used again in the process
    assert compile_cache.store_stats()["refused"] - refused0 == 1
    assert len(said) == 1 and "does not match the executable" in said[0]
    y, c = programs["step", 4](*_args())
    assert int(c["n"]) == 1
    # arguments already donated are an error, as they always were: there is nothing to call again with
    x, gone = _args()
    programs, first = _first_call(ProgramStore(str(tmp_path)))
    first(x, gone)
    with pytest.raises((RuntimeError, ValueError, TypeError)):
        programs["step", 4](x, gone)


_CHILD = r"""
import json, os, sys
sys.path[:0] = [os.path.join({repo!r}, "tests"), os.path.join({repo!r}, "tests", "benchmark_harness")]
import jax, jax.numpy as jnp
from jax import set_mesh
from tpu_testing import TINY_QWEN2, tiny_decode_engine
from areal_tpu.inference.decode_programs import DecodePrograms
from areal_tpu.utils import compile_cache, perf_tracer

compile_cache.install_compile_counters()
if {family!r} == "hybrid":
    import chipbench_hybrid_util as hu
    hu.load_run()
    from benchlib import hybrid_weights
    cfg = hu.tiny_model()
    eng = tiny_decode_engine(hu.model_config(cfg), hybrid_weights.make_params(cfg, 23, jnp.float32), max_batch_size=2, dtype="float32")
else:
    eng = tiny_decode_engine(TINY_QWEN2, max_batch_size=2)
assert eng.programs.store.directory is None  # a CPU: the engine's own store is off
# a second DecodePrograms over the same model, with a store handed to it
progs = DecodePrograms(eng.model, eng.model_cfg, eng.config, eng.mesh, store=compile_cache.ProgramStore({store!r}))
S, psz = eng.config.max_batch_size, eng.config.page_size
i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
with set_mesh(eng.mesh):
    cache = progs.prefill_fn(2, 256)(eng.params, eng.cache, i32(2, 256) + 5, i32(2) + 9, i32(2 * 256 // psz), jnp.arange(2, dtype=jnp.int32))
    cache, state, rng, packed = progs.chunk_fn(4, 2, False, True, False)(eng.params, cache, i32(S, 2), eng._dev_state, eng._rng)
    state = progs.update_fn(2)(state, jnp.zeros((2, 11 + 8), jnp.float32))
entries = perf_tracer.get_tracer().record().entries
builds = [e for e in entries if e.name == "areal.program.build"]
inside = lambda b, name: sum(1 for e in entries if e.name == name and b.start_ns <= e.start_ns and e.end_ns <= b.end_ns)
print("RESULT " + json.dumps({{
    "builds": [[b.args["program"], b.args["served"], inside(b, "areal.xla.trace"), inside(b, "areal.xla.lower"), inside(b, "areal.xla.cache_load")] for b in builds],
    "kept": sorted(type(v).__name__ for v in progs._fn_cache.values()),
    "packed": [int(v) for v in jax.device_get(packed).reshape(-1)],
    "stats": compile_cache.store_stats(),
}}))
"""


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_a_second_decode_programs_in_a_fresh_process_is_served_from_the_store_and_traces_nothing(tmp_path, family):
    def child():
        p = subprocess.run(
            [sys.executable, "-c", _CHILD.format(repo=REPO, family=family, store=str(tmp_path / "programs"))],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600,
        )
        assert p.returncode == 0, p.stderr[-3000:]
        (line,) = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
        return json.loads(line[len("RESULT "):])

    first, second = child(), child()
    assert [b[:2] for b in first["builds"]] == [["prefill", "jit"], ["chunk", "jit"], ["upd", "jit"]]
    assert all(b[2] >= 1 and b[3] == 1 and b[4] == 0 for b in first["builds"])  # traced and lowered there
    assert first["stats"]["misses"] == 3 and first["stats"]["hits"] == 0 and first["stats"]["refused"] == 0
    assert len(os.listdir(tmp_path / "programs")) == 3
    # the fresh process: every program's executable read and loaded, nothing traced, nothing lowered
    assert second["builds"] == [["prefill", "store", 0, 0, 1], ["chunk", "store", 0, 0, 1], ["upd", "store", 0, 0, 1]]
    assert second["stats"]["hits"] == 3 and second["stats"]["misses"] == 0 and second["stats"]["refused"] == 0
    assert second["kept"] == ["BuiltProgram"] * 3 and second["packed"] == first["packed"]  # the same tokens, bit for bit
