"""Request timeline observatory + fleet flight recorder
(observability/timeline.py), the perf_trace_converter multi-rank/role
merge, the postmortem fleet merge, and the gateway goodput bench smoke
(docs/observability.md "Request timelines" / "Flight recorder")."""

import asyncio
import json
import time

import pytest

from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability import timeline as tl_mod
from areal_tpu.observability.metrics import Registry
from areal_tpu.observability.timeline import (
    FlightRecorder,
    RequestTimeline,
    TimelineRecorder,
    flight_to_trace_events,
    timelines_to_trace_events,
)
from areal_tpu.tools import perf_trace_converter, postmortem


# ---------------------------------------------------------------------------
# RequestTimeline: breakdown accounting
# ---------------------------------------------------------------------------


def _fabricated_timeline(**stamps) -> RequestTimeline:
    """Timeline with hand-placed stage timestamps (seconds after queued) —
    breakdown math must be testable without sleeping through real stages."""
    tl = RequestTimeline(rid="r1")
    t0 = tl.queued_ts
    for stage, dt in stamps.items():
        tl.events.append((stage, t0 + dt, None))
    return tl


def test_breakdown_identity_named_stages_plus_other_equals_total():
    tl = _fabricated_timeline(
        admitted=0.2, prefill_start=0.3, prefill_end=0.5,
        first_token=0.6, terminal=1.5,
    )
    tl.fence_stall_s = 0.1
    bd = tl.breakdown()
    assert bd["total_s"] == pytest.approx(1.5)
    assert bd["queue_wait_s"] == pytest.approx(0.2)
    assert bd["prefill_s"] == pytest.approx(0.2)
    assert bd["ttft_s"] == pytest.approx(0.6)
    # decode runs prefill_end -> terminal minus the fence stall (the first
    # token is a milestone inside decode, not its start)
    assert bd["decode_s"] == pytest.approx(1.5 - 0.5 - 0.1)
    # the residual is EXACTLY the admitted -> prefill_start gap: named
    # stages + other always reconstruct the wall time
    assert bd["other_s"] == pytest.approx(0.1)
    named = (
        bd["queue_wait_s"] + bd["prefill_s"] + bd["decode_s"]
        + bd["fence_stall_s"] + bd["other_s"]
    )
    assert named == pytest.approx(bd["total_s"])


def test_breakdown_zero_prefill_resume_path():
    # a parked-KV resume re-admits with no prefill: decode anchors on the
    # admitted mark and nothing goes negative
    tl = _fabricated_timeline(admitted=0.1, first_token=0.4, terminal=1.0)
    bd = tl.breakdown()
    assert bd["prefill_s"] == 0.0
    assert bd["decode_s"] == pytest.approx(0.9)
    assert bd["other_s"] == pytest.approx(0.0)


def test_event_cap_drops_chunks_but_never_the_terminal():
    tl = RequestTimeline(rid="r1")
    for _ in range(400):
        tl.mark(tl_mod.DECODE_CHUNK, n_tokens=4)
    assert len(tl.events) == tl_mod.MAX_EVENTS_PER_TIMELINE
    assert tl.dropped_events == 400 - (tl_mod.MAX_EVENTS_PER_TIMELINE - 1)
    tl.mark(tl_mod.TERMINAL, reason="stop")
    assert tl.ts_of(tl_mod.TERMINAL) is not None  # cap-exempt


def test_recorder_completion_and_leak_detector():
    reg = Registry()
    rec = TimelineRecorder(max_recent=4)
    rec._obs = obs_catalog.timeline_metrics(reg)
    tls = [rec.start(f"r{i}") for i in range(6)]
    assert rec.unterminated() == 6
    for tl in tls[:5]:
        # rebase 1s into the past so first_token precedes the (imminent)
        # terminal mark — ttft and the tpot tail must both come out > 0
        tl.queued_ts -= 1.0
        tl.events[0] = (tl_mod.QUEUED, tl.queued_ts, None)
        tl.events.append((tl_mod.FIRST_TOKEN, tl.queued_ts + 0.1, None))
        rec.complete(tl, "stop", n_tokens=8)
    stats = rec.stats()
    assert stats["unterminated"] == 1  # tls[5] never terminated: the leak
    assert stats["recent"] == 4  # bounded deque kept the newest 4
    assert [r["rid"] for r in rec.recent(2)] == ["r3", "r4"]
    # completed timelines observed the stage histograms
    text = reg.render_prometheus()
    assert "areal_request_queue_wait_seconds_count 5" in text
    assert 'areal_request_ttft_seconds_count{priority="interactive"} 5' in text
    assert "areal_request_tpot_seconds_count 5" in text


# ---------------------------------------------------------------------------
# FlightRecorder: ring overflow + atomic dump
# ---------------------------------------------------------------------------


def test_flight_ring_overflow_keeps_newest_and_counts_drops():
    fr = FlightRecorder(capacity=8, role="test")
    for i in range(20):
        fr.record("evt", i=i)
    snap = fr.snapshot()
    assert len(snap["events"]) == 8
    assert snap["dropped"] == 12
    # the newest events survive, seq keeps global ordering across the drop
    assert [e["data"]["i"] for e in snap["events"]] == list(range(12, 20))
    assert [e["seq"] for e in snap["events"]] == list(range(13, 21))


def test_flight_dump_is_atomic_and_json_complete(tmp_path):
    fr = FlightRecorder(capacity=4, role="test")
    fr.record("watchdog", severity="error", slot=3)
    path = tmp_path / "sub" / "flight.json"
    fr.dump(str(path), reason="wedge")
    snap = json.loads(path.read_text())
    assert snap["dump_reason"] == "wedge"
    assert snap["role"] == "test"
    assert snap["events"][0]["kind"] == "watchdog"
    # atomic_io leaves no tmp droppings next to the dump
    assert [p.name for p in path.parent.iterdir()] == ["flight.json"]


def test_engine_wedge_escalation_dumps_flight_ring_once(monkeypatch, tmp_path):
    """is_wedged() -> True must persist the flight ring to disk exactly
    once (supervision is about to evict the replica; the postmortem needs
    the events even if the process never answers another scrape)."""
    import jax

    from areal_tpu.api.config import MeshConfig, RequestLifecycleConfig, ServerConfig
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine, _Task
    from areal_tpu.models import qwen
    from tpu_testing import TINY_QWEN2

    monkeypatch.setenv("AREAL_FLIGHT_DIR", str(tmp_path))

    class _AliveThread:
        def is_alive(self):
            return True

    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=256,
        decode_steps_per_call=4,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        lifecycle=RequestLifecycleConfig(engine_stall_escalate_s=1.0),
    )
    eng = DecodeEngine(
        cfg,
        params=qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2),
        model_cfg=TINY_QWEN2,
    )
    eng._thread = _AliveThread()
    eng._backlog.append(
        _Task(req=ModelRequest(input_ids=[1]), callback=lambda r: None)
    )
    eng._last_loop_ts = time.monotonic() - 30.0
    assert eng.is_wedged()
    dumps = list(tmp_path.glob("flight_*_wedge.json"))
    assert len(dumps) == 1
    snap = json.loads(dumps[0].read_text())
    assert snap["dump_reason"] == "wedge"
    assert any(e["kind"] == "wedge" for e in snap["events"])
    # the escalation dump fires once, not on every /health poll
    dumps[0].unlink()
    assert eng.is_wedged()
    assert list(tmp_path.glob("flight_*_wedge.json")) == []
    eng._thread = None  # don't let stop() join the fake


# ---------------------------------------------------------------------------
# perf_trace_converter: multi-rank/role merge
# ---------------------------------------------------------------------------


def _ev(name, ts=1.0, pid=99, tid=7):
    return {"name": name, "ph": "i", "s": "t", "ts": ts, "pid": pid, "tid": tid}


def test_converter_merges_ranks_and_roles_into_distinct_pids(tmp_path):
    (tmp_path / "trainer-r0.json").write_text(
        json.dumps({"traceEvents": [_ev("step")]})
    )
    (tmp_path / "trainer-r1.jsonl").write_text(
        json.dumps(_ev("step")) + "\n" + json.dumps(_ev("sync")) + ",\n"
    )
    (tmp_path / "inference_server-r0.json").write_text(
        json.dumps([_ev("decode")])  # bare-list form
    )
    (tmp_path / "notes.txt").write_text("ignored")
    out = perf_trace_converter.convert(tmp_path, tmp_path / "merged.json")
    merged = json.loads(out.read_text())["traceEvents"]
    metas = {e["pid"]: e["args"]["name"] for e in merged if e["ph"] == "M"}
    assert sorted(metas.values()) == [
        "inference_server r0", "trainer r0", "trainer r1",
    ]
    by_pid = {}
    for e in merged:
        if e["ph"] != "M":
            by_pid.setdefault(e["pid"], []).append(e["name"])
    # every event was remapped onto its file's pid (original pid=99 gone)
    assert 99 not in by_pid
    assert sorted(by_pid[_pid_of(metas, "trainer r1")]) == ["step", "sync"]
    assert by_pid[_pid_of(metas, "inference_server r0")] == ["decode"]


def _pid_of(metas, name):
    return next(pid for pid, n in metas.items() if n == name)


def test_converter_requires_trace_files(tmp_path):
    with pytest.raises(FileNotFoundError):
        perf_trace_converter.convert(tmp_path)


# ---------------------------------------------------------------------------
# postmortem: fleet merge correlated by trace ids
# ---------------------------------------------------------------------------


def _timeline_record(rid, task_id, anchor=1000.0):
    tl = RequestTimeline(rid=rid, task_id=task_id, session_id="s-1")
    t0 = tl.queued_ts
    tl.epoch_anchor = anchor
    for stage, dt in (
        ("admitted", 0.1), ("prefill_start", 0.1), ("prefill_end", 0.3),
        ("first_token", 0.4), ("terminal", 1.0),
    ):
        tl.events.append((stage, t0 + dt, None))
    tl.terminal_reason = "stop"
    return tl.to_dict()


def test_postmortem_merges_fleet_snapshots_by_trace_id(tmp_path):
    """Two processes' /debug/flight payloads -> ONE trace with both as
    separate pid rows, their events correlated by the x-areal-trace task
    id riding in args."""
    server_snap = {
        "role": "inference_server",
        "pid": 111,
        "events": [
            {"ts": 1000.2, "kind": "admission_reject", "severity": "warn",
             "seq": 1, "data": {"task_id": "t-abc"}},
        ],
        "timelines": [_timeline_record("r1", "t-abc")],
    }
    controller_snap = {
        "role": "rollout_controller",
        "pid": 222,
        "events": [
            {"ts": 1000.9, "kind": "quarantine", "severity": "error",
             "seq": 1, "data": {"task_id": "t-abc"}},
        ],
    }
    out = postmortem.build_incident_trace(
        [("s", server_snap), ("c", controller_snap)],
        tmp_path / "incident.json",
    )
    merged = json.loads(out.read_text())["traceEvents"]
    real = [e for e in merged if e["ph"] != "M"]
    assert len({e["pid"] for e in real}) == 2  # both processes present
    tagged = [e for e in real if e.get("args", {}).get("task_id") == "t-abc"]
    assert len({e["pid"] for e in tagged}) == 2  # correlated across both
    # timeline spans got rebased onto the wall clock (epoch anchor 1000s)
    spans = [e for e in real if e["ph"] == "X"]
    assert {s["name"] for s in spans} == {"queue_wait", "prefill", "decode"}
    for s in spans:
        assert 1000.0e6 <= s["ts"] <= 1001.0e6


def test_postmortem_dedups_shared_ring_of_colocated_replicas():
    """Snapshots of ONE process's ring (two ports of an in-process
    LocalFleet, or a live scrape + that process's wedge dump file) must
    merge the flight ring once — but each still contributes its own
    timelines. A distinct process that happens to share the pid number
    (another host) records different events and is kept."""
    ring = [{"ts": 1000.2, "kind": "evict_radix", "severity": "info", "seq": 1}]
    snap_a = {"role": "inference_server", "pid": 111, "events": list(ring),
              "timelines": [_timeline_record("rA", "t-a")]}
    snap_b = {"role": "inference_server", "pid": 111, "events": list(ring),
              "timelines": [_timeline_record("rB", "t-b")]}
    # same pid on another host: same shape, different recorded events
    other = {"role": "inference_server", "pid": 111, "timelines": [],
             "events": [{"ts": 2000.5, "kind": "evict_radix",
                         "severity": "info", "seq": 1}]}
    # the same process's earlier wedge dump: subset of the live ring
    dump = {"role": "inference_server", "pid": 111, "events": list(ring),
            "dump_reason": "wedge"}
    snaps = [("h1:7001", snap_a), ("h1:7002", snap_b), ("h2:7001", other),
             ("flight_inference_server_111_wedge", dump)]
    postmortem.dedup_shared_rings(snaps)
    assert not snap_a.get("_dup_flight_ring")
    assert snap_b.get("_dup_flight_ring")  # shared ring: suppressed
    assert not other.get("_dup_flight_ring")  # distinct ring content: kept
    assert dump.get("_dup_flight_ring")  # scrape+dump of one process
    ev_b = postmortem.snapshot_to_events(snap_b)
    assert [e for e in ev_b if e["cat"] == "flight"] == []
    assert [e for e in ev_b if e["cat"] == "timeline"]  # timelines survive


def test_postmortem_dedup_keeps_one_superset_across_three_snapshots():
    """Live scrape + wedge dump + SIGTERM dump of ONE process, in
    increasing size order: exactly one (the largest) stays unsuppressed."""
    def ev(seq):
        return {"ts": 1000.0 + seq, "kind": "evict_radix",
                "severity": "info", "seq": seq}

    live = {"pid": 7, "events": [ev(1), ev(2)]}
    wedge = {"pid": 7, "events": [ev(1), ev(2), ev(3)]}
    sigterm = {"pid": 7, "events": [ev(1), ev(2), ev(3), ev(4)]}
    snaps = [("h:7001", live), ("wedge_dump", wedge), ("sigterm_dump", sigterm)]
    postmortem.dedup_shared_rings(snaps)
    unsuppressed = [s for _, s in snaps if not s.get("_dup_flight_ring")]
    assert unsuppressed == [sigterm]

    # bridged groups: an old dump (seq 1-2) and a post-rotation live scrape
    # (seq 5-6) share nothing, but the final dump covers both — all three
    # must collapse to one group with the superset unsuppressed
    old = {"pid": 9, "events": [ev(1), ev(2)]}
    rotated = {"pid": 9, "events": [ev(5), ev(6)]}
    full = {"pid": 9, "events": [ev(s) for s in (1, 2, 3, 4, 5, 6)]}
    snaps = [("old_dump", old), ("h:7001", rotated), ("final_dump", full)]
    postmortem.dedup_shared_rings(snaps)
    unsuppressed = [s for _, s in snaps if not s.get("_dup_flight_ring")]
    assert unsuppressed == [full]


def test_timeline_trace_events_wall_clock_rebase():
    rec = _timeline_record("r9", None, anchor=500.0)
    events = timelines_to_trace_events([rec])
    term = next(e for e in events if e["name"] == "terminal")
    assert term["ts"] == pytest.approx(501.0e6)


def test_flight_trace_events_carry_severity_and_data():
    events = flight_to_trace_events(
        {"events": [{"ts": 2.0, "kind": "wedge", "severity": "error",
                     "data": {"slot": 3}}]}
    )
    assert events[0]["name"] == "wedge"
    assert events[0]["ts"] == pytest.approx(2.0e6)
    assert events[0]["args"] == {"severity": "error", "slot": 3}


@pytest.mark.slow
def test_two_process_incident_trace_correlated_by_trace_id(tmp_path):
    """Acceptance: two REAL server processes, one deliberately wedged —
    postmortem merges their /debug/flight payloads (+ the wedge dump)
    into one Perfetto trace with flight events from both processes and
    request timelines correlated by the x-areal-trace task id."""
    import os
    import subprocess
    import sys
    import urllib.error
    import urllib.request

    flight_dir = tmp_path / "flight"
    wedge_file = tmp_path / "wedge_now"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AREAL_FLIGHT_DIR=str(flight_dir),
        PYTHONPATH=repo_root,
    )
    child = os.path.join(os.path.dirname(__file__), "flight_child.py")
    procs = [
        subprocess.Popen(
            [sys.executable, child, str(wedge_file)],
            env=env,
            stdout=subprocess.PIPE,
            text=True,
            cwd=repo_root,
        )
        for _ in range(2)
    ]
    try:
        addrs = []
        for p in procs:
            line = p.stdout.readline()
            assert line.startswith("READY "), f"child failed: {line!r}"
            addrs.append(line.split()[1].strip())

        def post(addr, path, body, headers=None):
            req = urllib.request.Request(
                f"http://{addr}{path}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json", **(headers or {})},
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read().decode())

        # requests on BOTH replicas carrying one x-areal-trace task id
        trace_hdr = {"x-areal-trace": "task=t-incident;session=s-inc"}
        for addr in addrs:
            for i in range(2):
                out = post(
                    addr,
                    "/generate",
                    {
                        "input_ids": [3 + i, 7, 9],
                        "gconfig": {"max_new_tokens": 4, "greedy": True},
                    },
                    headers=trace_hdr,
                )
                assert out["timing"]["queue_wait_s"] >= 0
        # a flight event unique to process 0 (staged weight update)
        post(addrs[0], "/update_weights_begin", {"stage_target": "host"})
        # deliberately wedge process 1; the escalation evaluates on /health
        # polls (exactly how the fleet probe/supervisor would find it) and
        # dumps the flight ring to disk the first time it reports wedged
        wedge_file.write_text("")
        deadline = time.monotonic() + 60
        dumps = []
        while time.monotonic() < deadline and not dumps:
            try:
                urllib.request.urlopen(
                    f"http://{addrs[1]}/health", timeout=5
                ).read()
            except urllib.error.HTTPError as e:
                assert e.code == 503
            dumps = list(flight_dir.glob("flight_*_wedge.json"))
            time.sleep(0.2)
        assert dumps, "wedge escalation never dumped the flight ring"

        out_path = tmp_path / "incident.json"
        rc = postmortem.main(
            [
                "--targets",
                ",".join(addrs),
                "--files",
                str(dumps[0]),
                "-o",
                str(out_path),
            ]
        )
        assert rc == 0
        merged = json.loads(out_path.read_text())["traceEvents"]
        real = [e for e in merged if e["ph"] != "M"]
        assert len({e["pid"] for e in real}) >= 2
        # flight events from >= 2 processes (the wedge fired on one, the
        # weight stage on the other)
        flight_pids = {
            e["pid"] for e in real if e.get("cat") == "flight"
        }
        assert len(flight_pids) >= 2
        kinds = {e["name"] for e in real if e.get("cat") == "flight"}
        assert "wedge" in kinds and "weight_stage" in kinds
        # request timelines from both processes correlate on the trace id
        tagged_pids = {
            e["pid"]
            for e in real
            if e.get("args", {}).get("task_id") == "t-incident"
        }
        assert len(tagged_pids) >= 2
    finally:
        for p in procs:
            p.kill()
            p.wait()


# ---------------------------------------------------------------------------
# gateway goodput bench: tiny-client smoke (tools/bench_gateway.py)
# ---------------------------------------------------------------------------


def test_client_task_latency_aggregation_feeds_executor_log_line(monkeypatch):
    """The client folds each finished request's stage breakdown into its
    workflow task's aggregate; the executor pops it exactly once and logs
    the per-trajectory latency line."""
    from types import SimpleNamespace

    from areal_tpu.api.config import InferenceEngineConfig
    from areal_tpu.api.io_struct import ModelResponse
    from areal_tpu.infra import workflow_executor as wf_mod
    from areal_tpu.inference.client import RemoteJaxEngine

    eng = RemoteJaxEngine(InferenceEngineConfig(), addresses=["127.0.0.1:1"])
    resp = ModelResponse(
        input_tokens=[1], output_tokens=[2, 3], output_logprobs=[0.0, 0.0],
        latency=2.0, ttft=0.5, queue_wait_s=0.1, prefill_s=0.2,
        decode_s=1.5, fence_stall_s=0.1,
    )
    eng._note_task_latency("t1", resp)
    eng._note_task_latency("t1", resp)
    stub = SimpleNamespace(
        engine=eng, config=SimpleNamespace(enable_rollout_tracing=True)
    )
    lines = []
    monkeypatch.setattr(wf_mod.logger, "info", lambda msg: lines.append(msg))
    wf_mod.WorkflowExecutor._log_task_latency(stub, "t1", True)
    assert len(lines) == 1
    assert "reqs=2 tokens=4" in lines[0]
    assert "queue_wait=0.200s" in lines[0]
    assert "fence_stall=0.200s" in lines[0]
    assert "ttft_max=0.500s" in lines[0]
    # popped: a second trajectory completion can't re-log stale numbers
    assert eng.take_task_latency("t1") is None
    wf_mod.WorkflowExecutor._log_task_latency(stub, "t1", True)
    assert len(lines) == 1
    # tombstoned: a quarantined task's aborted generations resolve AFTER
    # the pop — their late notes must not re-create a never-popped entry
    eng._note_task_latency("t1", resp)
    assert not eng._task_latency


def test_tpot_excludes_only_in_window_fence_stall():
    """A hold fence that lands BETWEEN prefill and the first token lies
    outside TPOT's first_token->terminal window — subtracting it would
    drive the tail <= 0 and silently drop the observation exactly during
    the weight-sync windows the metric characterizes."""
    reg = Registry()
    rec = TimelineRecorder()
    rec._obs = obs_catalog.timeline_metrics(reg)
    tl = rec.start("r1")
    tl.queued_ts -= 2.0
    tl.events[0] = (tl_mod.QUEUED, tl.queued_ts, None)
    # 0.5s hold before the first token, first_token->terminal ~= 0.5s
    tl.fence_stall_s = 0.5
    tl.fence_stall_pre_first_s = 0.5
    tl.events.append((tl_mod.FIRST_TOKEN, tl.queued_ts + 1.5, None))
    rec.complete(tl, "stop", n_tokens=6)
    text = reg.render_prometheus()
    assert "areal_request_tpot_seconds_count 1" in text


def test_recorder_clamps_unknown_priority_label():
    # the priority header is client-controlled; arbitrary values must not
    # mint unbounded ttft histogram children
    rec = TimelineRecorder()
    assert rec.start("r1", priority="interactive").priority == "interactive"
    assert rec.start("r2", priority="rollout").priority == "rollout"
    assert rec.start("r3", priority="p-4afc81").priority == "interactive"


def test_bench_gateway_percentile():
    from areal_tpu.tools.bench_gateway import _percentile

    assert _percentile([], 0.5) is None
    assert _percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert _percentile([3.0, 1.0, 2.0], 0.99) == 3.0


def test_bench_gateway_smoke_tiny_fleet():
    """One-replica fleet, a handful of clients, no chaos: the bench must
    emit a complete scoreboard (non-null p50/p99 TTFT per class, goodput,
    zero errors) and the engines must terminate every timeline."""
    from areal_tpu.tools.bench_gateway import run_local_bench

    report = asyncio.run(
        run_local_bench(
            n_replicas=1,
            n_interactive=2,
            n_rollout=2,
            duration_s=0.5,
            chaos_stall_prob=0.0,
        )
    )
    for cls in ("interactive", "rollout"):
        c = report["classes"][cls]
        assert c["sent"] == 2 and c["completed"] == 2 and c["errors"] == 0
        assert c["ttft_p50_s"] is not None and c["ttft_p99_s"] is not None
        assert c["e2e_p50_s"] is not None
        assert c["tokens"] > 0
    assert report["totals"]["completed"] == 4
    assert report["totals"]["goodput_tok_s"] > 0
    for rep in report["fleet"]["replicas"]:
        assert rep["timelines"]["unterminated"] == 0
