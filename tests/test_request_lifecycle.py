"""Request lifecycle manager (docs/request_lifecycle.md): deadlines,
cancellation, admission control, load shedding, and the per-slot watchdog —
plus the overload acceptance scenario (2x sustained load with chaos stalls:
bounded latency for admitted work, clean 429s for shed work, zero leaked KV
pages, and byte-identical greedy outputs for unaffected requests)."""

import asyncio
import threading
import time

import aiohttp
import jax
import numpy as np
import pytest

from areal_tpu.api.config import (
    ChaosConfig,
    FaultToleranceConfig,
    InferenceEngineConfig,
    MeshConfig,
    RequestLifecycleConfig,
    ServerConfig,
)
from areal_tpu.api.io_struct import (
    GenerationHyperparameters,
    ModelRequest,
    StopReason,
)
from areal_tpu.inference.client import RemoteJaxEngine
from areal_tpu.inference.decode_engine import DecodeEngine, _Task
from areal_tpu.inference.server import ServerThread
from areal_tpu.infra.workflow_executor import WorkflowExecutor
from areal_tpu.models import qwen
from areal_tpu.openai.proxy.gateway import GatewayState, SessionRoute, create_gateway_app
from areal_tpu.api.workflow_api import RolloutWorkflow
from areal_tpu.robustness import CLOSED, FaultInjector

from tpu_testing import TINY_QWEN2


@pytest.fixture(scope="module")
def tiny_params():
    return qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)


def _server_cfg(**kw) -> ServerConfig:
    defaults = dict(
        max_batch_size=2,
        max_seq_len=256,
        decode_steps_per_call=4,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    defaults.update(kw)
    return ServerConfig(**defaults)


def _greedy(n=8, **kw) -> GenerationHyperparameters:
    return GenerationHyperparameters(max_new_tokens=n, greedy=True, **kw)


def _long(n=100_000) -> GenerationHyperparameters:
    return GenerationHyperparameters(
        max_new_tokens=n, greedy=True, ignore_eos=True
    )


def _leaked(eng: DecodeEngine) -> int:
    """PagePool refcount audit: pages in use that are NOT accounted for by
    the radix tree (the only legitimate holder once all requests ended)."""
    held = eng.prefix_cache_stats()["pages_held"] if eng.slots.radix is not None else 0
    return eng.slots.pool.used - held


def _wait_decoding(eng: DecodeEngine, rid: str, timeout=30.0) -> None:
    """Wait until ``rid`` occupies a slot and has emitted >= 1 token (the
    per-task counter — cumulative engine stats would race earlier tests)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for t in eng._slot_task:
            if t is not None and t.req.rid == rid and t.out_tokens:
                return
        time.sleep(0.02)
    raise TimeoutError(f"rid {rid} never started decoding")


def _settle(eng: DecodeEngine, timeout=30.0) -> None:
    """Wait until the engine has no queued/active/parked work."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = eng.admission_snapshot()
        if (
            snap["queue_depth"] == 0
            and snap["active_slots"] == 0
            and not eng.slots.parked
        ):
            return
        time.sleep(0.05)
    raise TimeoutError("engine never drained")


# ---------------------------------------------------------------------------
# engine-level: deadlines / cancellation / watchdog / admission inputs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(tiny_params):
    cfg = _server_cfg(lifecycle=RequestLifecycleConfig())
    eng = DecodeEngine(cfg, params=tiny_params, model_cfg=TINY_QWEN2)
    eng.initialize()
    eng.start()
    yield eng
    eng.stop()


def test_deadline_reaps_mid_decode(engine):
    t0 = time.time()
    resp = engine.generate_sync(
        ModelRequest(input_ids=[5, 6, 7], deadline=t0 + 1.2, gconfig=_long()),
        timeout=60,
    )
    elapsed = time.time() - t0
    assert resp.stop_reason == StopReason.DEADLINE.value
    assert resp.truncated_by == "deadline"
    assert len(resp.output_tokens) > 0  # partial output, not nothing
    # per-token version tags stay consistent on the partial output
    assert len(resp.output_versions) == len(resp.output_tokens)
    assert elapsed < 10, f"reap took {elapsed:.1f}s for a 1.2s deadline"
    _settle(engine)
    assert _leaked(engine) == 0


def test_deadline_expired_in_queue_never_prefills(engine):
    before = engine.stats["prefills"] if "prefills" in engine.stats else None
    resp = engine.generate_sync(
        ModelRequest(input_ids=[1, 2], deadline=time.time() - 1.0, gconfig=_greedy()),
        timeout=30,
    )
    assert resp.stop_reason == StopReason.DEADLINE.value
    assert resp.output_tokens == []
    if before is not None:
        assert engine.stats["prefills"] == before
    _settle(engine)
    assert _leaked(engine) == 0


def test_abort_request_mid_decode(engine):
    done = threading.Event()
    box = {}
    req = ModelRequest(input_ids=[9, 9, 9], gconfig=_long())
    engine.submit(req, lambda r: (box.update(r=r), done.set()))
    _wait_decoding(engine, req.rid)
    assert engine.abort_request(req.rid)
    assert done.wait(30), "abort never resolved the callback"
    resp = box["r"]
    assert resp.stop_reason == StopReason.CANCEL.value
    assert resp.truncated_by == "cancelled"
    _settle(engine)
    assert _leaked(engine) == 0


def test_abort_request_while_parked(engine):
    """A parked rid (abort-pause retained KV) cancelled via abort_request
    drops the parking and returns every page."""
    done = threading.Event()
    req = ModelRequest(input_ids=[3, 1, 4, 1, 5], gconfig=_long())
    engine.submit(req, lambda r: done.set())
    _wait_decoding(engine, req.rid)
    engine.pause_generation()  # abort-pause: the rid parks with its KV
    assert done.wait(30)
    assert req.rid in engine.slots.parked
    engine.abort_request(req.rid)
    engine.continue_generation()
    deadline = time.monotonic() + 30
    while req.rid in engine.slots.parked and time.monotonic() < deadline:
        time.sleep(0.02)
    assert req.rid not in engine.slots.parked
    _settle(engine)
    assert _leaked(engine) == 0


def test_generate_sync_timeout_cancels_server_side(engine):
    """The wasted-work fix: a generate_sync timeout aborts the engine-side
    request instead of letting it decode to completion for a caller that
    is gone. The engine either returns the partial inside the grace window
    (preferred) or raises TimeoutError with the slot reclaimed."""
    cancelled_before = engine.stats["cancelled"]
    # saturate both slots + queue so the timed request cannot complete
    # inside its timeout (it is either still queued or mid-decode). Every
    # request here ends at max_seq_len after ~250 tokens, a fraction of a
    # second on a fast host: the timeout must be shorter than ONE such
    # request, not than the whole queue
    fills = []
    for _ in range(4):
        done = threading.Event()
        freq = ModelRequest(input_ids=[6, 1, 6], gconfig=_long())
        engine.submit(freq, lambda r, d=done: d.set())
        fills.append((freq, done))
    try:
        try:
            resp = engine.generate_sync(
                ModelRequest(input_ids=[2, 7, 1], gconfig=_long()), timeout=0.1
            )
            assert resp.stop_reason == StopReason.CANCEL.value
        except TimeoutError:
            pass
    finally:
        for freq, _ in fills:
            engine.abort_request(freq.rid)
        for _, done in fills:
            assert done.wait(60)
    _settle(engine)
    assert engine.stats["cancelled"] >= cancelled_before + 1
    assert _leaked(engine) == 0


def test_watchdog_reaps_stalled_slot(tiny_params):
    """White-box on a non-running engine (a healthy decode loop refreshes
    progress every chunk, so a real stall cannot be produced): stage an
    ACTIVE slot whose progress timestamp is older than watchdog_s and run
    one reap pass — the slot is aborted with truncated_by="watchdog"."""
    cfg = _server_cfg(lifecycle=RequestLifecycleConfig(watchdog_s=1.0))
    eng = DecodeEngine(cfg, params=tiny_params, model_cfg=TINY_QWEN2)
    eng.initialize()
    box = {}
    task = _Task(
        req=ModelRequest(input_ids=[8, 8], gconfig=_long()),
        callback=lambda r: box.update(r=r),
        slot=0,
    )
    eng._slot_task[0] = task
    eng._state["active"][0] = True
    eng._slot_progress[0] = time.monotonic() - 10.0  # stalled 10s ago
    assert eng._reap_lifecycle(None) is None
    resp = box["r"]
    assert resp.truncated_by == "watchdog"
    assert resp.stop_reason == StopReason.CANCEL.value
    assert eng.stats["watchdog_fired"] == 1
    assert eng._slot_task[0] is None
    assert not eng._state["active"][0]
    assert _leaked(eng) == 0
    # a slot with FRESH progress is never touched
    box2 = {}
    task2 = _Task(
        req=ModelRequest(input_ids=[4, 4], gconfig=_long()),
        callback=lambda r: box2.update(r=r),
        slot=1,
    )
    eng._slot_task[1] = task2
    eng._state["active"][1] = True
    eng._slot_progress[1] = time.monotonic()
    eng._reap_lifecycle(None)
    assert not box2 and eng._slot_task[1] is task2
    eng._slot_task[1] = None
    eng._state["active"][1] = False


def test_wedge_detector(tiny_params):
    """is_wedged: stale loop heartbeat + pending work + live thread = wedged;
    idle or fresh loops are not."""

    class _AliveThread:
        def is_alive(self):
            return True

    cfg = _server_cfg(
        lifecycle=RequestLifecycleConfig(engine_stall_escalate_s=1.0)
    )
    eng = DecodeEngine(cfg, params=tiny_params, model_cfg=TINY_QWEN2)
    assert not eng.is_wedged()  # no thread at all
    eng._thread = _AliveThread()
    assert not eng.is_wedged()  # no pending work
    eng._backlog.append(_Task(req=ModelRequest(input_ids=[1]), callback=lambda r: None))
    eng._last_loop_ts = time.monotonic() - 30.0
    assert eng.is_wedged()
    eng._last_loop_ts = time.monotonic()
    assert not eng.is_wedged()  # fresh heartbeat
    eng.config.lifecycle.engine_stall_escalate_s = 0.0
    eng._last_loop_ts = time.monotonic() - 30.0
    assert not eng.is_wedged()  # detector off
    eng._thread = None  # don't let stop() join the fake


# ---------------------------------------------------------------------------
# HTTP server: admission 429, deadline header, /abort_request, wedged /health
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def http_server(tiny_params):
    cfg = _server_cfg(lifecycle=RequestLifecycleConfig())
    eng = DecodeEngine(cfg, params=tiny_params, model_cfg=TINY_QWEN2)
    eng.initialize()
    st = ServerThread(cfg, eng)
    st.start()
    yield st
    st.stop()


def _post(addr: str, path: str, payload: dict, headers: dict | None = None):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://{addr}{path}", json=payload, headers=headers or {}
            ) as r:
                return r.status, dict(r.headers), await r.json()

    return asyncio.run(go())


def _get(addr: str, path: str):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://{addr}{path}") as r:
                return r.status, await r.json()

    return asyncio.run(go())


def _gen_payload(ids, n=4, **sp):
    params = {"max_new_tokens": n, "greedy": True}
    params.update(sp)
    return {"input_ids": ids, "sampling_params": params}


def test_http_page_headroom_gate_rejects_429(http_server):
    lc = http_server.engine.config.lifecycle
    lc.min_free_pages = 10**6  # impossible headroom: reject everything
    try:
        status, headers, body = _post(
            http_server.address, "/generate", _gen_payload([1, 2, 3])
        )
        assert status == 429
        assert body["reason"] == "page_headroom"
        assert "Retry-After" in headers
        assert float(headers["Retry-After"]) > 0
        assert "queue_depth" in body and "free_pages" in body
    finally:
        lc.min_free_pages = 0


def test_http_queue_depth_gate_rejects_429(http_server):
    eng = http_server.engine
    eng.config.lifecycle.max_queue_depth = 1
    fills = []
    try:
        # occupy both slots + leave one queued so depth >= 1
        for _ in range(3):
            done = threading.Event()
            req = ModelRequest(input_ids=[6, 6, 6], gconfig=_long())
            eng.submit(req, lambda r, d=done: d.set())
            fills.append((req, done))
        deadline = time.monotonic() + 30
        while eng.admission_snapshot()["queue_depth"] < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        status, headers, body = _post(
            http_server.address, "/generate", _gen_payload([1, 2])
        )
        assert status == 429
        assert body["reason"] == "queue_depth"
        assert "Retry-After" in headers
    finally:
        eng.config.lifecycle.max_queue_depth = 0
        for req, _ in fills:
            eng.abort_request(req.rid)
        for _, done in fills:
            assert done.wait(30)
        _settle(eng)
        assert _leaked(eng) == 0


def test_http_deadline_header_reaps(http_server):
    # subject: the header -> req.deadline plumbing (mid-decode reaping
    # itself is test_deadline_reaps_mid_decode). The deadline must be
    # tighter than a WARM full-window run — AOT-compiled decode finishes
    # all ~254 tokens in ~0.2s on this box, and a deadline the engine can
    # beat ends the request at "length" before the reap ever looks at it.
    status, _, body = _post(
        http_server.address,
        "/generate",
        _gen_payload([4, 5], n=100_000, ignore_eos=True),
        headers={"x-areal-deadline": f"{time.time() + 0.05:.6f}"},
    )
    assert status == 200
    assert body["stop_reason"] == StopReason.DEADLINE.value
    assert body["truncated_by"] == "deadline"
    _settle(http_server.engine)
    assert _leaked(http_server.engine) == 0


def test_http_bad_deadline_header_400(http_server):
    status, _, _ = _post(
        http_server.address,
        "/generate",
        _gen_payload([1]),
        headers={"x-areal-deadline": "not-a-number"},
    )
    assert status == 400


def test_http_abort_request_endpoint(http_server):
    addr = http_server.address
    status, _, _ = _post(addr, "/abort_request", {})
    assert status == 400  # rid required
    status, _, body = _post(addr, "/abort_request", {"rid": "no-such-rid"})
    assert status == 200  # idempotent no-op
    # live cancellation over HTTP
    eng = http_server.engine
    done = threading.Event()
    box = {}
    req = ModelRequest(input_ids=[7, 7], gconfig=_long())
    eng.submit(req, lambda r: (box.update(r=r), done.set()))
    _wait_decoding(eng, req.rid)
    status, _, body = _post(addr, "/abort_request", {"rid": req.rid})
    assert status == 200 and body["queued"]
    assert done.wait(30)
    assert box["r"].stop_reason == StopReason.CANCEL.value
    _settle(eng)
    assert _leaked(eng) == 0


def test_http_health_turns_503_when_wedged(http_server):
    eng = http_server.engine
    status, body = _get(http_server.address, "/health")
    assert status == 200 and body["status"] == "ok"
    eng.is_wedged = lambda: True  # instance attr shadows the method
    try:
        status, body = _get(http_server.address, "/health")
        assert status == 503
        assert body["status"] == "wedged"
    finally:
        del eng.is_wedged
    status, body = _get(http_server.address, "/health")
    assert status == 200


def test_statusz_reports_lifecycle_snapshot(http_server):
    status, body = _get(http_server.address, "/statusz")
    assert status == 200
    lc = body["lifecycle"]
    assert {"queue_depth", "free_pages", "radix_pages", "active_slots"} <= set(lc)


# ---------------------------------------------------------------------------
# client: 429 backpressure semantics + default deadline stamping
# ---------------------------------------------------------------------------


def _client(addresses, **cfg_kw):
    defaults = dict(
        max_concurrent_rollouts=4,
        consumer_batch_size=2,
        max_head_offpolicyness=100,
        request_timeout=120,
        fault_tolerance=FaultToleranceConfig(
            backoff_base_s=0.05, backoff_max_s=0.2, probe_interval_s=60.0
        ),
    )
    defaults.update(cfg_kw)
    c = RemoteJaxEngine(InferenceEngineConfig(**defaults), addresses=list(addresses))
    c.initialize()
    return c


def test_client_429_is_backpressure_not_failure(http_server):
    """Admission rejections honor Retry-After under their own wall-clock
    budget (backpressure_wait_s) without burning failure-retry attempts,
    and never trip the circuit breaker (a saturated fleet must not cascade
    into eviction)."""
    eng = http_server.engine
    eng.config.lifecycle.min_free_pages = 10**6  # reject everything
    eng.config.lifecycle.retry_after_s = 0.05
    client = _client(
        [http_server.address],
        request_retries=2,
        lifecycle=RequestLifecycleConfig(backpressure_wait_s=0.4),
    )
    try:
        req = ModelRequest(input_ids=[1, 2, 3], gconfig=_greedy())
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="failed after retries"):
            asyncio.run(client.agenerate(req))
        # several Retry-After waits fit the budget: the client rode the
        # backpressure loop (not the 3-attempt failure budget) before
        # giving up at backpressure_wait_s
        assert 0.3 < time.monotonic() - t0 < 10
        assert client.fleet.state(http_server.address) == CLOSED
    finally:
        eng.config.lifecycle.min_free_pages = 0
        eng.config.lifecycle.retry_after_s = 1.0
        client.destroy()


def test_client_stamps_default_deadline(http_server):
    client = _client(
        [http_server.address],
        # tight enough that even a warm engine cannot finish 250+ tokens
        # before it expires (the point is the stamp + propagation, not
        # where exactly the reap lands)
        lifecycle=RequestLifecycleConfig(default_deadline_s=0.05),
    )
    try:
        req = ModelRequest(input_ids=[2, 4, 6], gconfig=_long(), deadline=None)
        t0 = time.time()
        resp = asyncio.run(client.agenerate(req))
        assert resp.stop_reason == StopReason.DEADLINE.value
        assert resp.truncated_by == "deadline"
        assert time.time() - t0 < 15
        _settle(http_server.engine)
        assert _leaked(http_server.engine) == 0
    finally:
        client.destroy()


# ---------------------------------------------------------------------------
# gateway load shedding: two priority classes
# ---------------------------------------------------------------------------


def test_gateway_admit_priority_classes():
    st = GatewayState(
        ["http://b1"], admin_api_key="k", max_inflight=4, interactive_headroom=2
    )
    # rollout traffic sheds once max_inflight - headroom (= 2) fill
    assert st.admit("rollout")
    st.on_admitted("rollout")
    st.on_admitted("rollout")
    assert not st.admit("rollout")  # rollout cap reached
    assert st.admit("interactive")  # headroom reserved for interactive
    st.on_admitted("interactive")
    st.on_admitted("interactive")
    assert not st.admit("interactive")  # full cap reached
    st.on_done("rollout", 0.1)
    assert not st.admit("rollout")  # 3 in flight, rollout cap is still 2
    assert st.admit("interactive")
    # unbounded when the knob is off
    st2 = GatewayState(["http://b1"], admin_api_key="k")
    assert all(st2.admit(p) for p in ("interactive", "rollout"))


def test_gateway_classify_defaults_to_interactive():
    st = GatewayState(["http://b1"], admin_api_key="k")

    class _R:
        def __init__(self, h):
            self.headers = h

    assert st.classify(_R({})) == "interactive"
    assert st.classify(_R({"x-areal-priority": "rollout"})) == "rollout"
    assert st.classify(_R({"x-areal-priority": "ROLLOUT"})) == "rollout"
    assert st.classify(_R({"x-areal-priority": "bogus"})) == "interactive"


def test_gateway_sheds_rollout_with_429_over_http():
    """Full HTTP path: a saturated gateway sheds rollout-class requests with
    429 + Retry-After while still forwarding interactive ones (deadline and
    priority headers pass through to the backend)."""

    async def go():
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        seen_headers = {}

        async def backend_handler(request):
            seen_headers.update(request.headers)
            await asyncio.sleep(0.2)  # hold the inflight slot
            return web.json_response({"ok": True})

        backend = web.Application()
        backend.router.add_post("/v1/chat/completions", backend_handler)
        backend_srv = TestServer(backend)
        await backend_srv.start_server()

        state = GatewayState(
            [f"http://127.0.0.1:{backend_srv.port}"],
            admin_api_key="adm",
            max_inflight=1,
            interactive_headroom=1,
            retry_after_s=0.25,
        )
        state.routes["key-1"] = SessionRoute(
            backend=f"http://127.0.0.1:{backend_srv.port}", session_id="s1"
        )
        gw = TestClient(TestServer(create_gateway_app(state)))
        await gw.start_server()
        try:
            auth = {"Authorization": "Bearer key-1"}
            # rollout is shed immediately: cap(1) - headroom(1) = 0 slots
            r = await gw.post(
                "/v1/chat/completions",
                json={},
                headers={**auth, "x-areal-priority": "rollout"},
            )
            assert r.status == 429
            assert float(r.headers["Retry-After"]) == 0.25
            body = await r.json()
            assert body["reason"] == "gateway_overload"
            # interactive passes, and lifecycle headers reach the backend
            r2 = await gw.post(
                "/v1/chat/completions",
                json={},
                headers={**auth, "x-areal-deadline": "123.5"},
            )
            assert r2.status == 200
            assert seen_headers.get("x-areal-deadline") == "123.5"
            assert state.shed["rollout"] == 1
            assert state.shed["interactive"] == 0
        finally:
            await gw.close()
            await backend_srv.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# workflow executor: quarantine cancels the task's in-flight generations
# ---------------------------------------------------------------------------


class _AbortRecordingEngine:
    def __init__(self):
        self.aborted_tasks = []

    def get_version(self):
        return 0

    def abort_task_requests(self, task_id: str) -> int:
        self.aborted_tasks.append(task_id)
        return 1


class _PoisonWorkflow(RolloutWorkflow):
    async def arun_episode(self, engine, data):
        await asyncio.sleep(0.001)
        raise RuntimeError("poison episode")


def test_quarantine_cancels_inflight_generations():
    fake = _AbortRecordingEngine()
    cfg = InferenceEngineConfig(
        max_concurrent_rollouts=4,
        consumer_batch_size=2,
        max_head_offpolicyness=100,
        fault_tolerance=FaultToleranceConfig(
            task_max_retries=0, task_quarantine_strikes=1
        ),
    )
    ex = WorkflowExecutor(cfg, fake)
    ex.initialize()
    try:
        tid = ex.submit({"k": "p"}, workflow=_PoisonWorkflow())
        assert ex.wait_for_task(tid, timeout=30) is None  # quarantined
        assert fake.aborted_tasks == [tid]
    finally:
        ex.destroy()


def test_client_tracks_and_aborts_task_rids(http_server):
    """abort_task_requests cancels every rid the task still owns, server
    side, and clears the registry."""
    from areal_tpu.infra import workflow_context

    eng = http_server.engine
    client = _client([http_server.address])
    try:
        async def run_in_task_ctx():
            workflow_context.set(
                workflow_context.WorkflowContext(task_id="task-77")
            )
            req = ModelRequest(input_ids=[5, 5, 5], gconfig=_long())
            gen = asyncio.ensure_future(client.agenerate(req))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not any(
                t is not None and t.req.rid == req.rid and t.out_tokens
                for t in eng._slot_task
            ):
                await asyncio.sleep(0.02)
            assert client._task_rids.get("task-77"), "rid never registered"
            n = client.abort_task_requests("task-77")
            assert n == 1
            resp = await gen
            return resp

        resp = asyncio.run(run_in_task_ctx())
        assert resp.stop_reason == StopReason.CANCEL.value
        assert "task-77" not in client._task_rids
        _settle(eng)
        assert _leaked(eng) == 0
    finally:
        client.destroy()


# ---------------------------------------------------------------------------
# overload acceptance: 2x load + chaos stalls
# ---------------------------------------------------------------------------


def test_overload_acceptance(tiny_params):
    """The acceptance scenario (ISSUE 6): at ~2x sustained capacity with the
    chaos stall injector running, admitted interactive requests keep a
    bounded p99, shed requests get clean 429 + Retry-After, every
    deadline-expired request frees its KV pages (zero-leak audit), and
    greedy outputs of unaffected requests are byte-identical with the
    lifecycle manager enabled vs. disabled."""
    # lifecycle ENABLED server under overload
    cfg_on = _server_cfg(
        max_batch_size=2,
        lifecycle=RequestLifecycleConfig(
            max_queue_depth=3, retry_after_s=0.1, watchdog_s=30.0
        ),
    )
    eng_on = DecodeEngine(cfg_on, params=tiny_params, model_cfg=TINY_QWEN2)
    eng_on.initialize()
    srv_on = ServerThread(cfg_on, eng_on)
    srv_on.start()
    # lifecycle DISABLED twin (same params/config otherwise): the greedy
    # baseline the unaffected requests must match byte-for-byte
    cfg_off = _server_cfg(
        max_batch_size=2, lifecycle=RequestLifecycleConfig(enabled=False)
    )
    eng_off = DecodeEngine(cfg_off, params=tiny_params, model_cfg=TINY_QWEN2)
    eng_off.initialize()
    srv_off = ServerThread(cfg_off, eng_off)
    srv_off.start()

    # the chaos stall injector: slow-but-successful latency faults applied
    # in front of every post (the client-boundary placement chaos.py uses)
    inj = FaultInjector(
        ChaosConfig(enabled=True, seed=99, stall_prob=0.3, stall_s=0.15)
    )
    prompts = [[3 + i, 14 + i, 15] for i in range(4)]  # the unaffected set
    P99_BOUND_S = 60.0  # generous CPU bound; overload without shedding would
    # grow this with queue depth instead of holding it flat

    async def drive(addr: str, shed_expected: bool):
        stats = {"s429": 0, "retry_after_ok": True, "latency": [], "out": {}}

        async def one(i: int, ids, n_new: int, deadline_s: float | None, tag):
            payload = {
                "input_ids": ids,
                "rid": f"{tag}-{i}",
                "sampling_params": {"max_new_tokens": n_new, "greedy": True},
            }
            headers = {}
            if deadline_s is not None:
                headers["x-areal-deadline"] = f"{time.time() + deadline_s:.6f}"
            t0 = time.monotonic()
            async with aiohttp.ClientSession() as s:
                for _ in range(200):  # bounded retry: no hung client
                    await inj.aperturb(addr, "/generate")
                    async with s.post(
                        f"http://{addr}/generate", json=payload, headers=headers
                    ) as r:
                        if r.status == 429:
                            stats["s429"] += 1
                            ra = r.headers.get("Retry-After")
                            if ra is None or float(ra) <= 0:
                                stats["retry_after_ok"] = False
                            await asyncio.sleep(float(ra or 0.1))
                            continue
                        assert r.status == 200, await r.text()
                        body = await r.json()
                        break
                else:
                    raise AssertionError("client starved: 200 rejections")
            stats["latency"].append(time.monotonic() - t0)
            if tag == "interactive":
                stats["out"][i] = body["output_tokens"]
            return body

        # 2x capacity: 2 slots, queue cap 3 -> 10 concurrent requests is
        # sustained ~2x what the engine admits at once
        tasks = [
            one(i, ids, 8, None, "interactive")
            for i, ids in enumerate(prompts)
        ]
        if shed_expected:
            # rollout flood: long generations on short deadlines — they
            # monopolize slots briefly, then the reaper frees them
            tasks += [
                one(i, [40 + i, 2, 2], 100_000, 2.0, "rollout")
                for i in range(6)
            ]
        res = await asyncio.gather(*tasks)
        return stats, res

    try:
        stats_on, _ = asyncio.run(drive(srv_on.address, shed_expected=True))
        stats_off, _ = asyncio.run(drive(srv_off.address, shed_expected=False))

        # clean 429s were actually exercised, each with a Retry-After hint
        assert stats_on["s429"] > 0, "overload never shed — not a 2x run"
        assert stats_on["retry_after_ok"]
        # bounded p99 (== max at this sample count) for admitted work
        assert max(stats_on["latency"]) < P99_BOUND_S
        # deadline reaping fired on the flood
        assert eng_on.stats["deadline_exceeded"] > 0
        # greedy outputs of the unaffected requests are byte-identical
        # with the lifecycle manager enabled vs. disabled
        for i in range(len(prompts)):
            assert stats_on["out"][i] == stats_off["out"][i], f"prompt {i}"
        # no engine crash, no leaked pages anywhere
        _settle(eng_on)
        _settle(eng_off)
        assert _leaked(eng_on) == 0, "lifecycle server leaked KV pages"
        assert _leaked(eng_off) == 0
        assert inj.stats()["stall"] > 0, "chaos stalls never fired"
    finally:
        srv_on.stop()
        srv_off.stop()
