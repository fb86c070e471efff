"""Trace-context propagation (ISSUE 1 satellite): ContextVar task/session
ids survive asyncio.create_task boundaries, and the x-areal-trace header
round-trips through the RPC layer onto the worker's engine thread."""

import asyncio
import contextvars
import threading

import pytest

from areal_tpu.api.scheduler_api import Scheduler, Worker
from areal_tpu.infra.rpc.echo_engine import EchoEngine
from areal_tpu.infra.rpc.rpc_server import RpcWorkerServer
from areal_tpu.observability import tracecontext
from areal_tpu.utils import perf_tracer


def _in_fresh_context(fn, *args):
    """Run fn in a clean ContextVar context (no leakage between tests)."""
    return contextvars.copy_context().run(fn, *args)


# -- ContextVar survival across async boundaries ---------------------------


def test_context_survives_create_task():
    async def main():
        perf_tracer.set_task_context(task_id="t-1", session_id="s-1")

        async def child():
            # a created task COPIES the parent context at creation time
            return perf_tracer.get_task_context()

        async def grandchild_spawner():
            return await asyncio.create_task(child())

        got_child = await asyncio.create_task(child())
        got_nested = await asyncio.create_task(grandchild_spawner())
        return got_child, got_nested

    got_child, got_nested = _in_fresh_context(asyncio.run, main())
    assert got_child == ("t-1", "s-1")
    assert got_nested == ("t-1", "s-1")


def test_sibling_tasks_are_isolated():
    async def main():
        async def rollout(i):
            perf_tracer.set_task_context(task_id=f"t-{i}", session_id=f"s-{i}")
            await asyncio.sleep(0)  # interleave with siblings
            return perf_tracer.get_task_context()

        return await asyncio.gather(*(rollout(i) for i in range(4)))

    results = _in_fresh_context(asyncio.run, main())
    assert results == [(f"t-{i}", f"s-{i}") for i in range(4)]


# -- header encode/decode ---------------------------------------------------


def test_header_roundtrip():
    assert tracecontext.format_trace_header(None, None) is None
    assert tracecontext.format_trace_header("a", None) == "task=a"
    assert tracecontext.format_trace_header("a", "b") == "task=a;session=b"
    assert tracecontext.parse_trace_header("task=a;session=b") == ("a", "b")
    assert tracecontext.parse_trace_header("session=b") == (None, "b")
    # malformed fragments never raise, unknown keys ignored
    assert tracecontext.parse_trace_header("junk;x=1;task=t") == ("t", None)
    assert tracecontext.parse_trace_header("") == (None, None)


def test_inject_extract_cycle():
    def scenario():
        perf_tracer.set_task_context(task_id="tid", session_id="sid")
        headers = tracecontext.inject({"Content-Type": "application/json"})
        assert headers[tracecontext.TRACE_HEADER] == "task=tid;session=sid"

        def receiver():
            # a receiver process starts with empty context
            assert perf_tracer.get_task_context() == (None, None)
            got = tracecontext.extract(headers)
            assert got == ("tid", "sid")
            assert perf_tracer.get_task_context() == ("tid", "sid")

        contextvars.Context().run(receiver)

    _in_fresh_context(scenario)


def test_extract_is_case_insensitive():
    def scenario():
        tracecontext.extract({"X-Areal-Trace": "task=T;session=S"})
        assert perf_tracer.get_task_context() == ("T", "S")

    _in_fresh_context(scenario)


def test_inject_without_context_adds_nothing():
    def scenario():
        assert tracecontext.inject({"a": "b"}) == {"a": "b"}

    contextvars.Context().run(scenario)


def test_extract_without_header_clears_stale_context():
    """Keep-alive connections reuse one handler task: a request WITHOUT the
    header must clear ids seated by the previous request, not inherit them."""

    def scenario():
        tracecontext.extract({"x-areal-trace": "task=old;session=old-s"})
        assert perf_tracer.get_task_context() == ("old", "old-s")
        assert tracecontext.extract({"content-type": "json"}) == (None, None)
        assert perf_tracer.get_task_context() == (None, None)
        # a partial header seats exactly what it carries
        tracecontext.extract({"x-areal-trace": "task=old;session=old-s"})
        tracecontext.extract({"x-areal-trace": "session=only-s"})
        assert perf_tracer.get_task_context() == (None, "only-s")

    _in_fresh_context(scenario)


# -- live RPC round-trip ----------------------------------------------------


class _DirectScheduler(Scheduler):
    """Concrete Scheduler exercising the base-class call_engine (the code
    path that injects x-areal-trace) against an in-process RpcWorkerServer."""

    def create_workers(self, job):  # pragma: no cover - unused
        raise NotImplementedError

    def get_workers(self, role):  # pragma: no cover - unused
        raise NotImplementedError

    def delete_workers(self, role=None):  # pragma: no cover - unused
        raise NotImplementedError

    def set_worker_env(self, role, env):  # pragma: no cover - unused
        raise NotImplementedError


@pytest.fixture()
def rpc_worker():
    server = RpcWorkerServer(host="127.0.0.1")
    server.engines["engine"] = EchoEngine()
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.astart())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(30)
    yield server
    asyncio.run_coroutine_threadsafe(server.astop(), loop).result(10)
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=10)


def test_trace_header_rides_rpc_onto_engine_thread(rpc_worker):
    sched = _DirectScheduler()
    worker = Worker(
        id="w0", role="test", ip="127.0.0.1", ports=[rpc_worker.port]
    )

    def with_context():
        perf_tracer.set_task_context(task_id="rpc-task", session_id="rpc-sess")
        return sched.call_engine(worker, "trace_context")

    # EchoEngine.trace_context reads the ContextVars ON THE ENGINE THREAD
    # — the header must survive serialization, the aiohttp handler, and
    # the handler->engine-thread context handoff
    got = _in_fresh_context(with_context)
    assert got == {"task_id": "rpc-task", "session_id": "rpc-sess"}

    # a caller with no trace context must not inherit the previous one
    got = contextvars.Context().run(
        sched.call_engine, worker, "trace_context"
    )
    assert got == {"task_id": None, "session_id": None}


def test_two_process_perfetto_trace_correlates_by_session(tmp_path):
    """Acceptance: a merged Perfetto trace from a two-process run contains
    spans from BOTH processes carrying the same session id."""
    import json
    import os
    import subprocess
    import sys
    import time
    import urllib.request

    from areal_tpu.api.config import PerfTracerConfig
    from areal_tpu.utils.network import find_free_port
    from areal_tpu.utils.perf_tracer import merge_traces

    port = find_free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "areal_tpu.infra.rpc.rpc_server",
            "--port",
            str(port),
            "--host",
            "127.0.0.1",
        ],
        env=env,
    )
    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/health", timeout=2
                ) as r:
                    if r.status == 200:
                        break
            except Exception:
                assert proc.poll() is None, "worker died during startup"
                assert time.monotonic() < deadline, "worker never healthy"
                time.sleep(0.2)

        sched = _DirectScheduler()
        worker = Worker(id="w0", role="test", ip="127.0.0.1", ports=[port])
        sched.create_engine(
            worker, "areal_tpu.infra.rpc.echo_engine.EchoEngine"
        )

        def run_client_side():
            perf_tracer.configure(
                PerfTracerConfig(enabled=True, output_dir=str(tmp_path)),
                rank=0,
                role="client",
            )
            perf_tracer.set_task_context(
                task_id="task-2p", session_id="sess-2p"
            )
            with perf_tracer.trace_scope("client.dispatch"):
                worker_trace = sched.call_engine(
                    worker, "traced_work", str(tmp_path)
                )
            perf_tracer.save(force=True)
            return worker_trace

        try:
            worker_trace = _in_fresh_context(run_client_side)
        finally:
            perf_tracer.configure(PerfTracerConfig(enabled=False))
        client_trace = str(tmp_path / "trace_client_rank0.json")
        merged = str(tmp_path / "merged.json")
        merge_traces([client_trace, worker_trace], merged)
        data = json.load(open(merged))
        by_session = [
            e
            for e in data["traceEvents"]
            if e.get("args", {}).get("session_id") == "sess-2p"
        ]
        # spans from BOTH processes (merge_traces remaps pid per file)
        assert {e["pid"] for e in by_session} == {0, 1}
        names = {e["name"] for e in by_session}
        assert {"client.dispatch", "worker.work"} <= names
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_rpc_metrics_recorded(rpc_worker):
    from areal_tpu.observability.metrics import get_registry

    sched = _DirectScheduler()
    worker = Worker(
        id="w0", role="test", ip="127.0.0.1", ports=[rpc_worker.port]
    )
    before = (
        rpc_worker._metrics.requests.labels(method="echo").get(),
        rpc_worker._metrics.errors.labels(method="boom").get(),
    )
    assert sched.call_engine(worker, "echo", 1)["args"] == [1]
    with pytest.raises(RuntimeError):
        sched.call_engine(worker, "boom")
    assert rpc_worker._metrics.requests.labels(method="echo").get() == before[0] + 1
    assert rpc_worker._metrics.errors.labels(method="boom").get() == before[1] + 1
    # unknown method names from the wire must NOT mint new label children
    # (unbounded cardinality); they land under the fixed "_unknown" label
    card = rpc_worker._metrics.requests.cardinality
    with pytest.raises(RuntimeError):
        sched.call_engine(worker, "no_such_method_xyz")
    assert rpc_worker._metrics.requests.cardinality == card
    assert rpc_worker._metrics.errors.labels(method="_unknown").get() >= 1
    # the worker /metrics endpoint exposes them as Prometheus text
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{rpc_worker.port}/metrics", timeout=10
    ) as r:
        text = r.read().decode()
    assert 'areal_rpc_requests_total{method="echo"}' in text
    registry_names = {f.name for f in get_registry().families()}
    assert "areal_rpc_request_seconds" in registry_names
