"""Perf tracer tests (reference tests/test_perf_tracer.py role)."""

import asyncio
import json
import os

from areal_tpu.api.config import PerfTracerConfig
from areal_tpu.utils import perf_tracer
from areal_tpu.utils.perf_tracer import Category, PerfTracer, SessionTracer


def test_trace_events_chrome_format(tmp_path):
    tr = PerfTracer(
        PerfTracerConfig(enabled=True, output_dir=str(tmp_path)), rank=3, role="actor"
    )
    with tr.trace_scope("step", Category.COMPUTE, args={"global_step": 1}):
        with tr.trace_scope("inner", Category.COMM):
            pass
    tr.instant("marker")
    tr.save(force=True)
    path = os.path.join(str(tmp_path), "trace_actor_rank3.json")
    data = json.load(open(path))
    evs = data["traceEvents"]
    names = [e["name"] for e in evs]
    assert {"step", "inner", "marker"} <= set(names)
    step = next(e for e in evs if e["name"] == "step")
    assert step["ph"] == "X" and step["dur"] > 0 and step["cat"] == "compute"
    assert step["args"]["global_step"] == 1


def test_disabled_tracer_is_noop(tmp_path):
    tr = PerfTracer(PerfTracerConfig(enabled=False, output_dir=str(tmp_path)))
    with tr.trace_scope("x"):
        pass
    tr.save(force=True)
    assert not os.listdir(tmp_path)


def test_trace_scope_around_an_await(tmp_path):
    perf_tracer.configure(
        PerfTracerConfig(enabled=True, output_dir=str(tmp_path)), rank=0
    )
    try:

        async def afn():
            with perf_tracer.trace_scope("afn", Category.IO):
                await asyncio.sleep(0)
                return 42

        assert asyncio.run(afn()) == 42
        perf_tracer.save(force=True)
    finally:
        perf_tracer.configure(PerfTracerConfig(enabled=False))
    data = json.load(open(os.path.join(str(tmp_path), "trace_rank0.json")))
    assert any(e["name"] == "afn" and e["cat"] == "io" for e in data["traceEvents"])


def test_session_tracer_lifecycle(tmp_path):
    st = SessionTracer(output_dir=str(tmp_path))
    st.start_session("s1")
    with st.phase("generate", "s1"):
        pass
    with st.phase("reward", "s1"):
        pass
    st.finalize("s1", "accepted")
    rows = [json.loads(x) for x in open(os.path.join(str(tmp_path), "sessions.jsonl"))]
    assert rows[0]["session_id"] == "s1"
    assert rows[0]["status"] == "accepted"
    assert [p["name"] for p in rows[0]["phases"]] == ["generate", "reward"]


def test_merge_traces(tmp_path):
    for r in range(2):
        tr = PerfTracer(
            PerfTracerConfig(enabled=True, output_dir=str(tmp_path)), rank=r
        )
        with tr.trace_scope(f"work{r}"):
            pass
        tr.save(force=True)
    out = os.path.join(str(tmp_path), "merged.json")
    perf_tracer.merge_traces(
        [os.path.join(str(tmp_path), f"trace_rank{r}.json") for r in range(2)], out
    )
    data = json.load(open(out))
    pids = {e["pid"] for e in data["traceEvents"]}
    assert pids == {0, 1}
