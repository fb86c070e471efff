"""Preemption-tolerant training (docs/fault_tolerance.md): the trajectory
journal's crash-durability contract, the flag-only PreemptionHandler state
machine, the serving drain path (admission 429 / finish-or-park / leak
audit), async recover dumps, and the chaos-injected kill→relaunch→resume
acceptance run."""

import os
import signal
import threading
import time

import numpy as np
import pytest

from areal_tpu.api.config import (
    ChaosConfig,
    GenerationHyperparameters,
    MeshConfig,
    ServerConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.infra.trajectory_journal import TrajectoryJournal
from areal_tpu.robustness.preemption import (
    DRAINED,
    DRAINING,
    RUNNING,
    PreemptionHandler,
)


def _traj(version: int, n: int = 2, L: int = 8):
    return {
        "input_ids": np.arange(n * L, dtype=np.int32).reshape(n, L),
        "attention_mask": np.ones((n, L), bool),
        "versions": np.full((n, L), version, np.int32),
        "rewards": np.ones((n,), np.float32),
    }


# ---------------------------------------------------------------------------
# trajectory journal
# ---------------------------------------------------------------------------


def test_journal_roundtrip_and_seal(tmp_path):
    j = TrajectoryJournal(str(tmp_path), segment_max_records=2, fsync=False)
    j.append_trajectory(_traj(3), "t1", 3, 3, 16)
    j.append_trajectory(_traj(4), "t2", 4, 4, 16)  # seals segment 0
    j.append_trajectory(_traj(5), "t3", 5, 5, 16)
    j.close()  # seals the active segment
    stats = j.stats()
    assert stats["segments_sealed"] == 2 and stats["segments_open"] == 0

    j2 = TrajectoryJournal(str(tmp_path), fsync=False)
    entries = j2.scan()
    assert [e.task_id for e in entries] == ["t1", "t2", "t3"]
    assert entries[0].head_version == 3 and entries[2].tail_version == 5
    np.testing.assert_array_equal(
        entries[1].traj["input_ids"], _traj(4)["input_ids"]
    )
    assert all(e.consumed_version is None for e in entries)


def test_journal_torn_tail_truncated_on_open(tmp_path):
    j = TrajectoryJournal(str(tmp_path), fsync=False)
    j.append_trajectory(_traj(1), "a", 1, 1, 16)
    j.append_trajectory(_traj(1), "b", 1, 1, 16)
    # crash mid-append: garbage after the last intact frame in the .open
    # segment (no close/seal — the writer died)
    open_segs = [p for p in os.listdir(tmp_path) if p.endswith(".open")]
    assert len(open_segs) == 1
    with open(tmp_path / open_segs[0], "ab") as f:
        f.write(b"\x42\x00\x00\x00torn-frame-partial")
    j2 = TrajectoryJournal(str(tmp_path), fsync=False)
    entries = j2.scan()
    # the torn tail cost nothing that was fully appended
    assert [e.task_id for e in entries] == ["a", "b"]
    # and the recovered segment was sealed atomically
    assert j2.stats()["segments_open"] == 0


def test_journal_replay_policy(tmp_path):
    """consumed-below-restored skipped, consumed-at/above replayed (the
    step died with the crash), unconsumed replayed, over-stale dropped."""
    j = TrajectoryJournal(str(tmp_path), fsync=False)
    j.append_trajectory(_traj(1), "old_consumed", 1, 1, 16)
    j.append_trajectory(_traj(4), "lost_step", 4, 4, 16)
    j.append_trajectory(_traj(4), "pending", 4, 5, 16)
    j.append_trajectory(_traj(0), "too_stale", 0, 0, 16)
    j.mark_consumed(["old_consumed"], version=2)
    j.mark_consumed(["lost_step"], version=5)  # step 5 never checkpointed
    j.close()

    j2 = TrajectoryJournal(str(tmp_path), fsync=False)
    replayable, dropped_stale, n_consumed = j2.pending_for_replay(
        restored_version=5, max_staleness=2
    )
    assert {e.task_id for e in replayable} == {"lost_step", "pending"}
    # too_stale: 5 - 0 > 2 — returned as an entry for the flight audit
    assert [e.task_id for e in dropped_stale] == ["too_stale"]
    assert n_consumed == 1  # old_consumed: durable inside the checkpoint


def test_journal_gc_drops_fully_consumed_segments(tmp_path):
    j = TrajectoryJournal(str(tmp_path), segment_max_records=2, fsync=False)
    j.append_trajectory(_traj(1), "a", 1, 1, 16)
    j.append_trajectory(_traj(1), "b", 1, 1, 16)  # seals segment 0
    j.append_trajectory(_traj(2), "c", 2, 2, 16)
    j.mark_consumed(["a", "b"], version=2)  # one C frame per tid
    j.close()
    assert j.stats()["segments_sealed"] == 3  # [a,b] [c,Ca] [Cb]
    # segment 0 (a,b consumed below 3) drops; the marker-only segment [Cb]
    # drops WITH it (its marker's trajectory leaves in the same pass);
    # [c, Ca] stays: c is unconsumed (the dangling 'a' marker is harmless)
    assert j.gc(covered_version=3) == 2
    j2 = TrajectoryJournal(str(tmp_path), fsync=False)
    assert {e.task_id for e in j2.scan()} == {"c"}


def test_journal_gc_keeps_load_bearing_markers(tmp_path):
    """The double-train guard: a consumed-marker segment must survive as
    long as the segment homing its trajectory survives — deleting it would
    make the trajectory look unconsumed and replay into training twice."""
    j = TrajectoryJournal(str(tmp_path), segment_max_records=3, fsync=False)
    j.append_trajectory(_traj(1), "A", 1, 1, 16)
    j.append_trajectory(_traj(1), "Z", 1, 1, 16)
    j.append_trajectory(_traj(1), "B", 1, 1, 16)  # seals seg0 [A,Z,B]
    j.mark_consumed(["A", "B"], version=1)  # seg1 [CA,CB] (sealed on close)
    j.close()
    # seg0 is kept (Z unconsumed) -> seg1's markers are load-bearing: gc
    # must drop NOTHING even though seg1 itself holds no trajectories
    assert j.gc(covered_version=2) == 0
    j2 = TrajectoryJournal(str(tmp_path), fsync=False)
    pend, _, consumed = j2.pending_for_replay(restored_version=2, max_staleness=5)
    assert {e.task_id for e in pend} == {"Z"} and consumed == 2
    # once Z is consumed too, trajectory and marker segments drop together
    j2.mark_consumed(["Z"], version=1)
    j2.close()
    assert j2.gc(covered_version=2) == 3
    assert TrajectoryJournal(str(tmp_path), fsync=False).scan() == []


# ---------------------------------------------------------------------------
# preemption handler
# ---------------------------------------------------------------------------


def test_handler_state_machine():
    h = PreemptionHandler(role="test", grace_s=5.0)
    assert h.state == RUNNING and h.remaining() == float("inf")
    h.request(signal.SIGTERM)
    assert h.state == DRAINING
    assert 0.0 < h.remaining() <= 5.0
    h.note_draining()
    h.note_draining()  # idempotent: counted once
    h.note_drained(0.1)
    assert h.state == DRAINED


def test_handler_real_signal_sets_flag_only():
    h = PreemptionHandler(role="test", grace_s=5.0, handle_sigusr1=True)
    assert h.install()
    try:
        signal.raise_signal(signal.SIGUSR1)
        assert h.requested.wait(2.0)
        assert h.signum == signal.SIGUSR1
        assert h.state == DRAINING
    finally:
        h.uninstall()
    # uninstalled: a later programmatic request still works, but the
    # process-level handler is back to the default
    assert signal.getsignal(signal.SIGUSR1) in (
        signal.SIG_DFL,
        signal.default_int_handler,
        None,
    ) or callable(signal.getsignal(signal.SIGUSR1))


def test_handler_drainer_thread_runs_after_request():
    h = PreemptionHandler(role="test", grace_s=5.0)
    ran = threading.Event()
    h.spawn_drainer(lambda handler: ran.set(), exit_code=None)
    assert not ran.is_set()
    h.request()
    assert ran.wait(5.0)
    assert h.drained.wait(5.0)


def test_controller_preemption_drains_and_dumps(tmp_path, monkeypatch):
    """Standalone-controller preemption: the drainer pauses the fleet,
    stops supervision, and persists the flight ring — without exiting
    (exit_code=None) so the test can observe it."""
    from areal_tpu.infra.controller.rollout_controller import RolloutController

    calls = []

    class _Sched:
        def call_all(self, workers, method, *a, **k):
            calls.append(method)
            return []

    monkeypatch.setenv("AREAL_FLIGHT_DIR", str(tmp_path))
    ctl = RolloutController(scheduler=_Sched())
    h = ctl.install_preemption(exit_code=None)
    try:
        h.request(signal.SIGTERM)
        assert h.drained.wait(10.0)
        assert "pause" in calls
        assert list(tmp_path.glob("flight_*preempt*.json"))
    finally:
        h.uninstall()


# ---------------------------------------------------------------------------
# executor journal wiring + interrupt (no engine needed)
# ---------------------------------------------------------------------------


class _VersionedEngine:
    def __init__(self, version=0):
        self.version = version

    def get_version(self):
        return self.version


def _executor(tmp_path, version=0, journal=True):
    from areal_tpu.api.config import (
        InferenceEngineConfig,
        TrajectoryJournalConfig,
    )
    from areal_tpu.infra.workflow_executor import WorkflowExecutor

    cfg = InferenceEngineConfig(
        max_concurrent_rollouts=4,
        consumer_batch_size=2,
        max_head_offpolicyness=2,
    )
    ex = WorkflowExecutor(cfg, engine=_VersionedEngine(version))
    if journal:
        ex.attach_journal(
            TrajectoryJournal(str(tmp_path / "journal"), fsync=False)
        )
    return ex


def test_executor_journal_append_consume_replay(tmp_path):
    ex = _executor(tmp_path, version=3)
    ex._journal_append(_traj(3), "keep", 16, 3, 3)
    ex._journal_append(_traj(3), "eaten", 16, 3, 3)
    ex._mark_consumed(["eaten"])  # consumed at version 3
    ex.journal.close()

    # relaunch at restored version 3: "eaten" was consumed by the step
    # producing version 4 -> that step died -> NOT durable... consumed at 3
    # < restored 4 would skip; here restored == 3, so 3 >= 3 replays BOTH
    ex2 = _executor(tmp_path, version=3)
    replayed, dropped = ex2.replay_from_journal()
    assert (replayed, dropped) == (2, 0)
    st = ex2.staleness.export_stats()
    # accepted restored (capacity formula re-tightens), but this-life
    # submitted/running throughput counters are NOT inflated by old work
    assert st["accepted"] == 2 and st["submitted"] == 0 and st["running"] == 0
    assert len(ex2._results) == 2
    # the capacity formula sees the replayed work: bound = (η + v + 1)·bs
    # minus accepted/running = (2+3+1)*2 - 2 = 10, capped by concurrency 4
    assert ex2.staleness.get_capacity() == 4

    # restored one version later: the consumed entry is now durable
    ex3 = _executor(tmp_path, version=4)
    replayed, dropped = ex3.replay_from_journal()
    assert (replayed, dropped) == (1, 0)
    assert ex3._results[0][0] == "keep"

    # far future: everything over-stale (bound = max_head_offpolicyness 2)
    ex4 = _executor(tmp_path, version=10)
    replayed, dropped = ex4.replay_from_journal()
    assert (replayed, dropped) == (0, 1)


def test_executor_wait_raises_on_interrupt(tmp_path):
    from areal_tpu.infra.workflow_executor import RolloutInterrupted

    ex = _executor(tmp_path, journal=False)
    ev = threading.Event()
    ex.set_interrupt(ev)
    ev.set()
    with pytest.raises(RolloutInterrupted):
        ex.wait(1, timeout=5.0)


# ---------------------------------------------------------------------------
# serving drain path (real engine, tiny model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.tools.validate_installation import tiny_model_config
    from areal_tpu.models import qwen

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    cfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=256,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
    eng.initialize()
    eng.start()
    yield eng
    eng.stop()


def test_engine_drain_finish_or_park(tiny_engine):
    eng = tiny_engine
    done = []
    # a long rid'd request that cannot finish inside the drain budget:
    # it must PARK (partial tokens returned now, KV retained)
    eng.submit(
        ModelRequest(
            input_ids=[5, 6, 7],
            rid="drain-park",
            gconfig=GenerationHyperparameters(
                max_new_tokens=100_000, greedy=True, ignore_eos=True
            ),
        ),
        done.append,
    )
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if any(t is not None and t.out_tokens for t in eng._slot_task):
            break
        time.sleep(0.01)
    summary = eng.drain(budget_s=0.05)
    try:
        # terminal fired with the partial output (client resubmits elsewhere)
        assert len(done) == 1
        assert done[0].stop_reason == "abort"
        assert len(done[0].output_tokens) > 0
        assert "drain-park" in eng.slots.parked  # rid-affinity KV retained
        assert summary["parked"] >= 1
        # admission is closed with the draining reason (server turns it
        # into 429 + Retry-After)
        admit, reason, _ = eng.check_admission()
        assert not admit and reason == "draining"
        # the audit: nothing leaked, every timeline terminated
        assert summary["leaked_pages"] == 0
        assert summary["unterminated_timelines"] == 0
        assert eng.drain_status()["draining"] is True
    finally:
        # un-drain for the other tests sharing the module engine; the
        # parked KV is reaped through the normal cancellation path
        eng.end_drain()
        eng.continue_generation()
        eng.abort_request("drain-park")
        deadline = time.monotonic() + 10
        while "drain-park" in eng.slots.parked and time.monotonic() < deadline:
            time.sleep(0.01)
        assert "drain-park" not in eng.slots.parked


def test_engine_drain_aborts_queued(tiny_engine):
    eng = tiny_engine
    eng.pause_generation()  # hold the loop so submissions stay queued
    eng._pause_ack.wait(5.0)
    done = []
    for i in range(3):
        eng.submit(
            ModelRequest(
                input_ids=[9 + i, 2, 3],
                rid=f"queued-{i}",
                gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
            ),
            done.append,
        )
    try:
        summary = eng.drain(budget_s=0.05)
        assert len(done) == 3  # every queued request got a terminal
        assert all(r.stop_reason == "abort" for r in done)
        assert summary["unterminated_timelines"] == 0
    finally:
        eng.end_drain()
        eng.continue_generation()


def test_server_drain_endpoint_and_health():
    import json
    import urllib.request

    import jax

    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.tools.validate_installation import tiny_model_config

    tiny = tiny_model_config()
    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=64,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    eng = DecodeEngine(
        cfg, params=qwen.init_params(jax.random.PRNGKey(0), tiny), model_cfg=tiny
    )
    eng.initialize()
    srv = ServerThread(cfg, eng)  # astart() starts the decode loop
    srv.start()
    try:
        body = json.dumps({"budget_s": 0.2}).encode()
        req = urllib.request.Request(
            f"http://{srv.address}/drain",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["status"] == "ok" and out["leaked_pages"] == 0
        # /health flips 503 "draining" -> fleet probe stops routing here
        try:
            urllib.request.urlopen(f"http://{srv.address}/health", timeout=10)
            raise AssertionError("draining replica reported healthy")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
        # /statusz carries the drain section
        with urllib.request.urlopen(
            f"http://{srv.address}/statusz", timeout=10
        ) as r:
            drain = json.loads(r.read())["drain"]
        assert drain["draining"] is True and "drain_seconds" in drain
        # a new generation is rejected 429 with the draining reason
        greq = urllib.request.Request(
            f"http://{srv.address}/generate",
            data=json.dumps(
                {"input_ids": [4, 5], "sampling_params": {"max_new_tokens": 2}}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(greq, timeout=10)
            raise AssertionError("draining replica admitted a request")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert e.headers.get("Retry-After") is not None
            assert json.loads(e.read())["reason"] == "draining"
        # ops called the migration off: /undrain re-opens the replica
        ureq = urllib.request.Request(f"http://{srv.address}/undrain", data=b"")
        with urllib.request.urlopen(ureq, timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(f"http://{srv.address}/health", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(
            f"http://{srv.address}/statusz", timeout=10
        ) as r:
            assert json.loads(r.read())["drain"]["draining"] is False
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# async recover dumps (fake engine: ordering without orbax cost)
# ---------------------------------------------------------------------------


class _SnapshotEngine:
    """Engine exposing the snapshot/write split with an observable delay."""

    def __init__(self, write_delay_s=0.15):
        self.write_delay_s = write_delay_s
        self.version = 0
        self.written = []
        self.write_started = threading.Event()

    def get_version(self):
        return self.version

    def set_version(self, v):
        self.version = v

    def load(self, meta):
        self.loaded = meta.path

    def save(self, meta):  # sync fallback path
        os.makedirs(meta.path, exist_ok=True)
        self.written.append(meta.path)

    def snapshot_for_save(self, with_optim=True):
        return {"params": {"w": np.ones(4)}}

    def write_snapshot(self, snapshot, path):
        self.write_started.set()
        time.sleep(self.write_delay_s)
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "state"), "wb") as f:
            f.write(b"snapshot")
        self.written.append(path)


def _recover_handler(tmp_path, mode="auto"):
    from areal_tpu.api.config import RecoverConfig
    from areal_tpu.utils.recover import RecoverHandler

    return RecoverHandler(
        RecoverConfig(
            mode=mode,
            freq_steps=1,
            fileroot=str(tmp_path),
            experiment_name="pre",
            trial_name="t",
        )
    )


def _step(gs):
    from areal_tpu.api.io_struct import StepInfo

    return StepInfo(epoch=0, epoch_step=gs, global_step=gs, steps_per_epoch=10)


def test_async_dump_records_land_after_write(tmp_path):
    h = _recover_handler(tmp_path)
    eng = _SnapshotEngine(write_delay_s=0.25)
    t0 = time.monotonic()
    path = h.dump(eng, _step(0), async_=True)
    blocked = time.monotonic() - t0
    assert path is not None
    assert blocked < 0.2, f"async dump blocked {blocked:.2f}s"
    # the write is still in flight: no record generation is visible yet
    assert eng.write_started.wait(5.0)
    assert h.read_recover_info() is None
    h.saver.wait_async()
    info, ckpt = h.read_recover_info()
    assert ckpt == path and info.last_step_info.global_step == 0
    # a crash BEFORE the write completed would have fallen back to the
    # previous generation: dump another and verify rotation happened only
    # after the second write
    h.dump(eng, _step(1), async_=True)
    h.saver.wait_async()
    info2, _ = h.read_recover_info()
    assert info2.last_step_info.global_step == 1
    assert os.path.exists(h._info_path(".prev"))


def test_emergency_dump_forces_sync_and_skips_freq_gate(tmp_path):
    h = _recover_handler(tmp_path)
    eng = _SnapshotEngine()
    # consume the frequency trigger for step 0…
    assert h.dump(eng, _step(0)) is not None
    # …the gated dump now declines, but the emergency dump must not
    assert h.dump(eng, _step(0)) is None
    path = h.dump_emergency(eng, _step(0))
    assert path is not None
    info, ckpt = h.read_recover_info()
    assert os.path.isdir(ckpt)


def test_async_dump_write_failure_surfaces_and_preserves_prev(tmp_path):
    h = _recover_handler(tmp_path)
    good = _SnapshotEngine(write_delay_s=0.0)
    assert h.dump(good, _step(0), async_=True) is not None
    h.saver.wait_async()

    class _Broken(_SnapshotEngine):
        def write_snapshot(self, snapshot, path):
            raise OSError("disk gone")

    h.saver.freq_ctl.load_state_dict({"last_time_delta": 0, "last_epoch": 0, "last_step": 0})
    h.dump(_Broken(), _step(1), async_=True)
    with pytest.raises(RuntimeError):
        h.saver.wait_async()
    # the failed generation never rotated the records: step-0 still loads
    info, _ = h.read_recover_info()
    assert info.last_step_info.global_step == 0


# ---------------------------------------------------------------------------
# acceptance: chaos SIGTERM mid-run -> drain -> relaunch -> journal replay
# ---------------------------------------------------------------------------


@pytest.mark.slow  # full trainer+fleet stack; tier-1 budget rides the
# lighter tests above — the same flow also runs in
# `validate_installation --preemption-self-test`
def test_chaos_preemption_kill_relaunch_resume(tmp_path):
    """SIGTERM a live trainer (chaos preempt injection) + drain the live
    replica under load: the trainer emergency-dumps and exits cleanly, the
    replica drains with zero leaks, and a relaunch resumes within one
    recover interval replaying >= 1 journaled in-bound trajectory."""
    import jax

    from areal_tpu.api.config import (
        DatasetConfig,
        InferenceEngineConfig,
        MicroBatchSpec,
        OptimizerConfig,
        PPOActorConfig,
        PPOConfig,
        PreemptionConfig,
        RecoverConfig,
        SaverConfig,
        StatsLoggerConfig,
        TrajectoryJournalConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.robustness import FaultInjector
    from areal_tpu.tools.validate_installation import tiny_model_config
    from areal_tpu.trainer.rl_trainer import PPOTrainer
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    root = str(tmp_path)
    tiny = tiny_model_config()

    def actor_cfg():
        return PPOActorConfig(
            init_from_scratch=True,
            dtype="float32",
            param_dtype="float32",
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
            mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
            bucket_step=64,
            group_size=1,
            ppo_n_minibatches=1,
            adv_norm=None,
            use_decoupled_loss=False,
            recompute_logprob=False,
        )

    def make_cfg():
        cfg = PPOConfig(
            experiment_name="chaos-preempt",
            trial_name="t0",
            total_train_epochs=50,
            weight_update_mode="mem",
            gconfig=GenerationHyperparameters(
                n_samples=1, max_new_tokens=4, greedy=True
            ),
            train_dataset=DatasetConfig(batch_size=2, shuffle=True),
            actor=actor_cfg(),
            saver=SaverConfig(fileroot=root),
            checkpointer=SaverConfig(fileroot=root),
            recover=RecoverConfig(mode="auto", freq_steps=1, fileroot=root),
            stats_logger=StatsLoggerConfig(fileroot=root),
        )
        cfg.evaluator.fileroot = root
        cfg.cluster.fileroot = root
        cfg.rollout = InferenceEngineConfig(
            max_concurrent_rollouts=4,
            consumer_batch_size=2,
            max_head_offpolicyness=4,
            request_timeout=120,
            journal=TrajectoryJournalConfig(enabled=True),
        )
        cfg.preemption = PreemptionConfig(grace_s=60.0)
        return cfg

    engine = JaxTrainEngine(actor_cfg(), model_config=tiny)
    engine.initialize(FinetuneSpec(1, 16, 2))
    scfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=128,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    dec = DecodeEngine(
        scfg, params=jax.tree.map(np.asarray, engine.params), model_cfg=tiny
    )
    dec.initialize()
    server = ServerThread(scfg, dec)
    server.start()
    rng = np.random.default_rng(1)
    dataset = [
        {"prompt_ids": rng.integers(2, 100, 3).tolist()} for _ in range(16)
    ]
    wf = RLVRWorkflow(
        lambda *a, **k: 1.0,
        GenerationHyperparameters(max_new_tokens=4, greedy=True),
    )

    rollout = RemoteJaxEngine(make_cfg().rollout, addresses=[server.address])
    rollout.initialize()
    # chaos-injected preemption: every /generate boundary draws; targets
    # register only once a step completed, so the SIGTERM lands mid-run
    # with a dump to fall back on
    injector = FaultInjector(
        ChaosConfig(enabled=True, seed=7, preempt_prob=0.5, path_prefix="/generate")
    )
    rollout.install_fault_injector(injector)
    trainer = PPOTrainer(make_cfg(), dataset, rollout=rollout, actor_engine=engine)

    def arm():
        deadline = time.time() + 300
        while time.time() < deadline:
            if rollout.get_version() >= 1:
                break
            time.sleep(0.05)
        injector.set_preempt_targets([os.getpid()])

    armer = threading.Thread(target=arm, daemon=True)
    armer.start()
    t_killed = time.monotonic()
    trainer.train(workflow=wf)
    armer.join(timeout=10)
    assert trainer.preempted, "chaos SIGTERM did not preempt the trainer"
    assert injector.stats()["preempt"] >= 1, "chaos preempt never fired"
    pair = trainer.recover_handler.read_recover_info()
    assert pair is not None, "no durable recover generation after preemption"
    dumped_step = pair[0].last_step_info.global_step
    appended = trainer.journal.stats()["appended"]
    assert appended >= 1
    trainer.close()

    # the live replica drains under load: 0 leaks, all timelines terminal
    done = []
    dec.submit(
        ModelRequest(
            input_ids=[5, 6, 7],
            rid="load-1",
            gconfig=GenerationHyperparameters(
                max_new_tokens=100_000, greedy=True, ignore_eos=True
            ),
        ),
        done.append,
    )
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if any(t is not None and t.out_tokens for t in dec._slot_task):
            break
        time.sleep(0.01)
    summary = dec.drain(budget_s=2.0)
    assert summary["drain_seconds"] <= 10.0
    assert len(done) == 1
    assert summary["leaked_pages"] == 0
    assert summary["unterminated_timelines"] == 0
    dec.end_drain()
    dec.continue_generation()

    # relaunch: resume within ONE recover interval + journal replay
    t_relaunch = time.monotonic()
    engine2 = JaxTrainEngine(actor_cfg(), model_config=tiny)
    engine2.initialize(FinetuneSpec(1, 16, 2))
    rollout2 = RemoteJaxEngine(make_cfg().rollout, addresses=[server.address])
    rollout2.initialize()
    trainer2 = PPOTrainer(
        make_cfg(), dataset, rollout=rollout2, actor_engine=engine2
    )
    assert trainer2.recover_info is not None
    resume_step = trainer2.recover_info.last_step_info.next().global_step
    # "within one recover interval": the dump cadence is every step, so the
    # relaunch must resume exactly one step past the dumped one
    assert resume_step == dumped_step + 1
    replayed = len(rollout2.executor._results)
    assert replayed >= 1, "no journaled trajectory replayed on relaunch"
    # measured re-generation savings: each replayed trajectory is a rollout
    # the fleet does not have to decode again
    saved_tokens = sum(n for _, _, n in rollout2.executor._results)
    print(
        f"preemption acceptance: killed {time.monotonic() - t_killed:.1f}s in, "
        f"drain {summary['drain_seconds']:.2f}s, resume step {resume_step}, "
        f"{replayed} trajectories / {saved_tokens} tokens replayed "
        f"(re-generation saved), relaunch {time.monotonic() - t_relaunch:.1f}s"
    )
    trainer2.close()
    server.stop()
