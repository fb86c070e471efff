"""The span record (``utils/perf_tracer.py``): every span, instant and jax
compile event of the process, kept in memory with the tracer not enabled and
no profiler session running (docs/observability.md "Spans and scopes").
Nothing here asserts a time."""

import gc
import json
import logging
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PerfTracerConfig,
    ServerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, GenerationHyperparameters, ModelRequest
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.models import qwen
from areal_tpu.utils import compile_cache, perf_tracer
from areal_tpu.utils.perf_tracer import Category, PerfTracer, SlowSpanWatch

from tpu_testing import TINY_QWEN2, random_batch


@pytest.fixture()
def tracer(monkeypatch):
    """A tracer of its own as the process's, NOT enabled."""
    tr = PerfTracer(PerfTracerConfig(enabled=False))
    monkeypatch.setattr(perf_tracer, "_TRACER", tr)
    return tr


def _named(tr, name):
    return [e for e in tr.record().entries if e.name == name]


def test_spans_and_instants_land_in_the_record_with_the_tracer_off(tracer):
    assert not tracer.enabled and perf_tracer.device_profile_active() is None
    perf_tracer.set_task_context(task_id="task-3", session_id="sess-3")
    try:
        with perf_tracer.trace_scope("areal.decode.pass", args={"active": 2}) as outer:
            with perf_tracer.trace_scope("areal.decode.admission", Category.SCHEDULER):
                perf_tracer.instant("areal.request.admitted", args={"queue_wait_us": 7})
            outer.set(tokens=64)
    finally:
        perf_tracer.clear_task_context()
    t = threading.Thread(target=lambda: perf_tracer.instant("areal.elsewhere"), name="other")
    t.start()
    t.join()
    rec = tracer.record()
    inst, inner, outer, other = rec.entries  # in the order they ended
    assert (inst.name, inst.ph, inst.end_ns) == ("areal.request.admitted", "i", inst.start_ns)
    assert inst.args == {"queue_wait_us": 7}
    assert (inner.name, inner.ph, inner.category) == ("areal.decode.admission", "X", Category.SCHEDULER)
    # nesting by time on one thread; args set at the end and the trace ids ride along
    assert outer.start_ns <= inner.start_ns <= inst.start_ns <= inner.end_ns <= outer.end_ns
    assert outer.thread == inner.thread == inst.thread == threading.get_ident() != other.thread
    assert outer.args == {"active": 2, "task_id": "task-3", "session_id": "sess-3", "tokens": 64}
    assert inner.args == {"task_id": "task-3", "session_id": "sess-3"}
    assert rec.threads[threading.get_ident()] == threading.current_thread().name
    # the process began before its first span, on the same clock
    assert 0 < rec.process_start_ns <= inst.start_ns


def test_the_record_is_bounded_and_keeps_the_newest():
    tr = PerfTracer(PerfTracerConfig(max_events=5))
    for i in range(12):
        tr.instant("e", args={"i": i})
    assert [e.args["i"] for e in tr.record().entries] == [7, 8, 9, 10, 11]
    tr.clear()
    assert tr.record().entries == []


def test_configure_hands_the_record_on(tracer, tmp_path):
    with perf_tracer.trace_scope("before.configure"):
        pass
    try:
        perf_tracer.configure(PerfTracerConfig(enabled=True, output_dir=str(tmp_path)), rank=1)
        assert perf_tracer.get_tracer() is not tracer
        perf_tracer.instant("after.configure")
        names = [e.name for e in perf_tracer.get_tracer().record().entries]
        assert names == ["before.configure", "after.configure"]
        perf_tracer.save(force=True)
    finally:
        perf_tracer.configure(PerfTracerConfig(enabled=False))
    data = json.load(open(os.path.join(str(tmp_path), "trace_rank1.json")))
    assert [e["name"] for e in data["traceEvents"]] == names


def test_save_writes_the_chrome_events_it_always_wrote(tmp_path):
    tr = PerfTracer(PerfTracerConfig(enabled=True, output_dir=str(tmp_path)), rank=2, role="actor")
    with tr.trace_scope("step", Category.COMM, args={"global_step": 4}):
        tr.instant("marker", args={"k": 1})
    tr.save(force=True)
    marker, step = json.load(open(os.path.join(str(tmp_path), "trace_actor_rank2.json")))["traceEvents"]
    assert set(step) == {"name", "ph", "pid", "tid", "ts", "cat", "dur", "args"}
    assert (step["ph"], step["cat"], step["args"], step["pid"]) == ("X", "comm", {"global_step": 4}, os.getpid())
    assert set(marker) == {"name", "ph", "pid", "tid", "ts", "cat", "s", "args"}
    assert (marker["ph"], marker["s"], marker["cat"]) == ("i", "t", "instr")
    assert step["ts"] <= marker["ts"] <= step["ts"] + step["dur"] and step["tid"] == marker["tid"]
    # a tracer that is not enabled keeps the record and writes no file
    off = PerfTracer(PerfTracerConfig(enabled=False, output_dir=str(tmp_path / "off")))
    off.instant("x")
    off.save(force=True)
    assert not os.path.exists(tmp_path / "off") and len(off.record().entries) == 1


def test_a_full_collection_is_one_gc_span_and_a_young_one_is_none(tracer):
    gc.collect(0)
    gc.collect(1)
    assert _named(tracer, "areal.gc") == []
    gc.collect()
    (ev,) = _named(tracer, "areal.gc")
    assert ev.ph == "X" and set(ev.args) == {"collected", "uncollectable"} and ev.thread == threading.get_ident()


def test_the_first_call_of_a_program_is_a_build_span_with_the_compile_events_inside(tracer):
    assert compile_cache.install_compile_counters()
    cache: dict = {}

    def builder(n):
        key = ("double", n)
        if key not in cache:
            cache[key] = jax.jit(lambda x: x * 2 + n)
            return compile_cache.FirstCall(cache, key)
        return cache[key]

    fn = builder(3)
    assert _named(tracer, "areal.program.build") == []
    assert float(fn(jnp.ones(3))[0]) == 5.0 and float(builder(3)(jnp.ones(3))[1]) == 5.0
    assert fn.lower(jnp.zeros(3)).as_text()  # everything but the call is the jitted function's own
    (build,) = _named(tracer, "areal.program.build")
    # no store on a CPU: traced, lowered and compiled here, once and explicitly
    assert build.args == {"program": "double", "key": "(3,)", "served": "jit"}
    # the cache keeps the loaded executable, which no call traces, with the jitted function behind it
    built = cache[("double", 3)]
    assert type(built).__name__ == "BuiltProgram" and type(built._compiled).__name__ == "Compiled"
    assert built.lower(jnp.zeros(3)).as_text() and type(built._fn).__name__ == "PjitFunction"
    for kind in ("trace", "lower", "compile"):
        inside = [e for e in _named(tracer, "areal.xla." + kind) if build.start_ns <= e.start_ns and e.end_ns <= build.end_ns]
        assert inside and all(e.thread == build.thread and e.ph == "X" for e in inside), kind
        assert any("lambda" in str((e.args or {}).get("fun")) for e in inside), kind
    assert _named(tracer, "areal.xla.cache_load") == []
    # the second call of the same shapes traced and compiled nothing
    n = len(_named(tracer, "areal.xla.trace")), len(_named(tracer, "areal.xla.compile"))
    builder(3)(jnp.ones(3))
    assert (len(_named(tracer, "areal.xla.trace")), len(_named(tracer, "areal.xla.compile"))) == n


def test_a_program_the_store_serves_is_a_build_with_a_cache_load_inside_and_no_trace(tracer, tmp_path):
    assert compile_cache.install_compile_counters()
    store = compile_cache.ProgramStore(str(tmp_path))

    def first_call(cache):
        cache["triple", 3] = jax.jit(lambda x: x * 3)
        return compile_cache.FirstCall(cache, ("triple", 3), store, compile_cache.describe(("test_span_record", "triple", 3)))

    x = jnp.ones(3)  # made before either build: its own trace is nobody's
    assert float(first_call({})(x)[0]) == 3.0  # a miss: built here, written
    cache: dict = {}
    assert float(first_call(cache)(x)[0]) == 3.0  # the hit
    missed, hit = _named(tracer, "areal.program.build")
    compile_cache._say_when_settled(False)  # the line that says what set-up built comes seconds later: the stream is pytest's
    assert (missed.args["served"], hit.args["served"]) == ("jit", "store")
    (load,) = _named(tracer, "areal.xla.cache_load")  # the store's read and load: the act the persistent cache's hit is
    assert hit.start_ns <= load.start_ns and load.end_ns <= hit.end_ns and load.thread == hit.thread
    assert load.args == {"fun": "triple", "from": "program_store"}
    for kind in ("trace", "lower", "compile"):
        assert [e for e in _named(tracer, "areal.xla." + kind) if hit.start_ns <= e.start_ns and e.end_ns <= hit.end_ns] == [], kind
    assert type(cache["triple", 3]._compiled).__name__ == "Compiled"


class _Ended:
    """A span that ended, with the duration a test wants."""

    def __init__(self, tr, name, start_ns, dur_ns, args=None):
        self._tracer, self.name, self.args = tr, name, args
        self.start_ns, self.end_ns = start_ns, start_ns + dur_ns
        tr.add_span(name, self.start_ns, self.end_ns, args=args)


def test_the_slow_line_fires_over_three_medians_and_not_under(tracer):
    ms = 1_000_000
    watch = SlowSpanWatch("areal.decode.pass")
    t = 10_000 * ms
    for i in range(8):  # not judged before it has seen a few
        assert watch.observe(_Ended(tracer, "areal.decode.pass", t, (5000 if i == 3 else 100) * ms)) is None
        t += 6000 * ms
    assert watch.observe(_Ended(tracer, "areal.decode.pass", t, 299 * ms)) is None  # under 3 x 100
    t += 1000 * ms
    # what the record holds of the slow pass: two phases of its own thread, a
    # collection and a program built on other threads, and one entry long over
    tracer.add_span("areal.gone.by", t - 50 * ms, t - 40 * ms)
    tracer.add_span("areal.decode.admission", t + 1 * ms, t + 21 * ms)
    tracer.add_span("areal.decode.device_wait", t + 30 * ms, t + 330 * ms)
    other = threading.Thread(
        target=lambda: (
            tracer.add_span("areal.gc", t + 40 * ms, t + 290 * ms, args={"collected": 9}),
            tracer.add_span("areal.program.build", t + 300 * ms, t + 320 * ms, args={"program": "upd"}),
        )
    )
    other.start()
    other.join()
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append((record.levelname, record.getMessage()))
    log = logging.getLogger("areal_tpu.perf_tracer")  # does not propagate to pytest's capture
    log.addHandler(handler)
    try:
        line = watch.observe(_Ended(tracer, "areal.decode.pass", t, 350 * ms, {"active": 3, "held_us": 0, "cpu_us": 900}))
    finally:
        log.removeHandler(handler)
    assert line is not None and said == [("WARNING", line)]  # ONE line of the program's log
    assert "slow areal.decode.pass: 350.000 ms, 3.5 x the median 100.000 ms of the last 9" in line
    assert "'cpu_us': 900" in line and "'held_us': 0" in line
    assert "self ms by phase: decode.device_wait 300.000, decode.admission 20.000" in line
    assert "2 overlapping entries" in line and "areal.gc 250.000 ms at +40.000" in line and "{'collected': 9}" in line
    assert "areal.program.build 20.000 ms at +300.000" in line and "gone.by" not in line
    # a span under the floor is nobody's stall, whatever the median
    quick = SlowSpanWatch("areal.decode.pass")
    for d in [1] * 8 + [40]:
        assert quick.observe(_Ended(tracer, "areal.decode.pass", t, d * ms)) is None


def sft_loss(outputs, b):
    lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
    loss = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
    return loss, {"ppl_loss": jax.lax.stop_gradient(loss)}


def test_a_decode_run_leaves_its_set_up_and_every_pass_in_the_record(tracer):
    cfg = ServerConfig(max_batch_size=4, max_seq_len=256, decode_steps_per_call=8, mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1))
    eng = DecodeEngine(cfg, params=qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2), model_cfg=TINY_QWEN2)
    eng.initialize()
    eng.start()
    try:
        req = ModelRequest(input_ids=list(range(3, 15)), gconfig=GenerationHyperparameters(max_new_tokens=20, greedy=True))
        assert len(eng.generate_sync(req, timeout=120).output_tokens) == 20
    finally:
        eng.stop()
    (init,) = _named(tracer, "areal.setup.engine_init")
    assert init.args["engine"] == "decode" and init.args["param_bytes"] > 0 and init.args["kv_page_bytes"] > 0
    assert init.args["recurrent_state_bytes"] == 0
    passes = _named(tracer, "areal.decode.pass")
    assert sum(p.args["tokens"] for p in passes) >= 20
    assert all(p.args["cpu_us"] >= 0 and "held_us" in p.args for p in passes)
    for phase in ("admission", "prefill", "dispatch", "device_wait", "bookkeeping"):
        kids = _named(tracer, "areal.decode." + phase)
        assert kids and all(any(p.thread == k.thread and p.start_ns <= k.start_ns and k.end_ns <= p.end_ns for p in passes) for k in kids), phase
    # one build a program of the engine first called, each inside a pass, the compile inside the build
    builds = _named(tracer, "areal.program.build")
    assert {b.args["program"] for b in builds} == {"prefill", "upd", "chunk"}
    assert len(builds) == len({(b.args["program"], b.args["key"]) for b in builds}) == len(eng.programs._fn_cache)
    compiles = _named(tracer, "areal.xla.compile")
    for b in builds:
        assert any(p.start_ns <= b.start_ns and b.end_ns <= p.end_ns for p in passes)
        assert any(b.start_ns <= c.start_ns and c.end_ns <= b.end_ns and c.thread == b.thread for c in compiles), b.args
    assert _named(tracer, "areal.request.first_token")


def test_a_train_step_is_a_span_over_its_phases_with_its_cpu_time(tracer):
    cfg = TrainEngineConfig(
        init_from_scratch=True, dtype="float32", param_dtype="float32", mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant"), mb_spec=MicroBatchSpec(max_tokens_per_mb=1024), bucket_step=64,
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 128, 16))
    weight = lambda d: float((np.asarray(d["loss_mask"]) > 0).sum())  # noqa: E731
    for _ in range(2):
        eng.train_batch(random_batch(seed=1), sft_loss, weight)
    (init,) = _named(tracer, "areal.setup.engine_init")
    assert init.args["engine"] == "train" and init.args["opt_state_bytes"] > init.args["param_bytes"] > 0
    first, second = _named(tracer, "areal.train.step")
    assert first.args["cpu_us"] > 0 and second.args["cpu_us"] >= 0
    for step in (first, second):
        kids = [e for e in tracer.record().entries if e.name.startswith("areal.train.") and e != step and step.start_ns <= e.start_ns and e.end_ns <= step.end_ns]
        assert {k.name for k in kids} == {"areal.train.host_prep", "areal.train.forward_backward"}
        assert all(k.thread == step.thread for k in kids)
    (build,) = _named(tracer, "areal.program.build")  # the fused step, inside the first step alone
    assert build.args["program"] == "fused" and first.start_ns <= build.start_ns and build.end_ns <= first.end_ns
