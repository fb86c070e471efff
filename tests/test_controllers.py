"""Single-controller layer: serialization round-trips, controller dispatch
over a mock scheduler (reference tests/test_train_controller.py +
test_rollout_controller.py pattern), and a real LocalScheduler integration
test spawning RPC worker subprocesses."""

import dataclasses

import numpy as np
import pytest

from areal_tpu.api.scheduler_api import Job, Scheduler, Worker
from areal_tpu.infra.rpc.serialization import decode_value, encode_value


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Cfg:
    name: str = "x"
    n: int = 3
    sub: dict = dataclasses.field(default_factory=dict)


def test_serialization_roundtrip():
    v = {
        "a": np.arange(6, dtype=np.int32).reshape(2, 3),
        "b": [1, 2.5, "s", None, True],
        "c": (_Cfg(name="y", n=7, sub={"k": np.float32(1.5)}),),
        "d": b"bytes",
    }
    out = decode_value(encode_value(v))
    assert np.array_equal(out["a"], v["a"])
    assert out["a"].dtype == np.int32
    assert out["b"] == [1, 2.5, "s", None, True]
    assert isinstance(out["c"], tuple) and out["c"][0] == _Cfg("y", 7, {"k": 1.5})
    assert out["d"] == b"bytes"


def test_serialization_bf16():
    import ml_dtypes

    arr = np.asarray([1.5, -2.25], dtype=ml_dtypes.bfloat16)
    out = decode_value(encode_value(arr))
    assert out.dtype == ml_dtypes.bfloat16
    assert np.array_equal(out.astype(np.float32), arr.astype(np.float32))


# ---------------------------------------------------------------------------
# mock scheduler (in-process workers)
# ---------------------------------------------------------------------------


class MockScheduler(Scheduler):
    """In-process scheduler: 'workers' are plain objects, calls are direct
    (reference MockScheduler, tests/test_train_controller.py:26-50)."""

    def __init__(self):
        self.engines: dict[str, object] = {}
        self.roles: dict[str, list[Worker]] = {}
        self.envs: dict[str, dict] = {}

    def create_workers(self, job: Job) -> list[Worker]:
        ws = [
            Worker(id=f"{job.role}-{i}", role=job.role, ip="127.0.0.1", ports=[0])
            for i in range(job.replicas)
        ]
        self.roles[job.role] = ws
        return ws

    def get_workers(self, role):
        return self.roles.get(role, [])

    def delete_workers(self, role=None):
        for r in [role] if role else list(self.roles):
            for w in self.roles.pop(r, []):
                self.engines.pop(w.id, None)

    def set_worker_env(self, role, env):
        self.envs.setdefault(role, {}).update(env)

    def create_engine(self, worker, engine_path, *args, **kwargs):
        from areal_tpu.utils.dynamic_import import import_from_string

        self.engines[worker.id] = import_from_string(engine_path)(*args, **kwargs)

    def call_engine(self, worker, method, *args, **kwargs):
        return getattr(self.engines[worker.id], method)(*args, **kwargs)


def _mean_loss(outputs, batch):  # importable loss fn for serialized dispatch
    raise NotImplementedError


class RecordingEngine:
    """Fake train engine recording dispatched batches."""

    calls: list = []

    def __init__(self, **kw):
        self.version = 0

    def initialize(self, ft_spec=None, **kw):
        pass

    def destroy(self):
        pass

    def train_batch_serialized(self, batch, loss_fn, loss_weight_fn, **kw):
        RecordingEngine.calls.append(batch)
        return {"loss": float(np.asarray(batch["attention_mask"]).sum())}

    def forward_batch(self, batch, **kw):
        return np.asarray(batch["attention_mask"], np.float32)

    def set_version(self, v):
        self.version = v

    def export_stats(self):
        return {"x": 1.0}


def test_train_controller_dispatch():
    from areal_tpu.infra.controller import TrainController

    RecordingEngine.calls = []
    sched = MockScheduler()
    tc = TrainController(
        sched, "test_controllers.RecordingEngine", replicas=2
    )
    tc.initialize()
    assert len(tc.workers) == 2

    B, L = 6, 10
    attn = np.zeros((B, L), np.int64)
    for i in range(B):
        attn[i, : 2 + i] = 1
    batch = {"attention_mask": attn, "input_ids": np.ones((B, L), np.int64)}
    stats = tc.train_batch(batch, "test_controllers._mean_loss", "test_controllers._mean_loss")
    # every sequence dispatched exactly once across the two workers
    assert sum(len(b["attention_mask"]) for b in RecordingEngine.calls) == B
    tok_total = sum(
        np.asarray(b["attention_mask"]).sum() for b in RecordingEngine.calls
    )
    assert tok_total == attn.sum()
    # merged stats = mean of per-worker losses
    assert stats["loss"] == pytest.approx(
        sum(float(np.asarray(b["attention_mask"]).sum()) for b in RecordingEngine.calls) / 2
    )

    out = tc.forward_batch(batch)
    assert out.shape == (B, L)

    tc.set_version(3)
    assert all(e.version == 3 for e in sched.engines.values())
    assert tc.export_stats() == {"x": 1.0}
    tc.destroy()
    assert not sched.engines


class FakeRolloutEngine:
    def __init__(self, config=None, **kw):
        self.version = 0
        self.submitted = []

    def initialize(self, addresses=None, **kw):
        pass

    def destroy(self):
        pass

    def set_completion_callback(self, url, worker_id=""):
        self.cb = (url, worker_id)

    def _push(self, task_id):
        import json as _json
        import urllib.request

        url, wid = self.cb
        req = urllib.request.Request(
            url,
            data=_json.dumps(
                {"task_id": task_id, "accepted": True, "worker_id": wid}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req, timeout=10).read()

    def submit(self, data, workflow=None, **kw):
        self.submitted.append(data)
        tid = f"task-{len(self.submitted)}"
        if getattr(self, "cb", None):
            import threading as _t

            _t.Timer(0.05, self._push, args=(tid,)).start()
        return tid

    def wait_for_task(self, task_id, timeout=None):
        return {"input_ids": np.ones((1, 4), np.int64), "task": task_id}

    def rollout_batch(self, data, workflow=None, **kw):
        n = len(data)
        return {
            "input_ids": np.ones((n, 3 + n), np.int64),
            "attention_mask": np.ones((n, 3 + n), np.int64),
        }

    def set_version(self, v):
        self.version = v

    def get_capacity(self):
        return 4

    def export_stats(self):
        return {"accepted": 2.0}


def test_rollout_controller_dispatch():
    from areal_tpu.infra.controller import RolloutController

    sched = MockScheduler()
    rc = RolloutController(
        sched,
        engine_path="test_controllers.FakeRolloutEngine",
        replicas=2,
    )
    rc.initialize(config=None)

    tid = rc.submit({"q": 1})
    res = rc.wait_for_task(tid)
    assert res["task"] == tid

    # push mode: completions arrive via the controller's callback listener
    rc.enable_completion_callbacks()
    tid2 = rc.submit({"q": 2})
    res2 = rc.wait_for_task(tid2, timeout=30)
    assert res2["task"] == tid2

    out = rc.rollout_batch([{"q": i} for i in range(5)])
    assert len(out["input_ids"]) == 5
    # padded concat: both workers' L dims reconciled
    assert out["input_ids"].shape[1] == max(3 + 3, 3 + 2)

    assert rc.get_capacity() == 8
    rc.set_version(2)
    assert all(e.version == 2 for e in sched.engines.values())
    rc.destroy()


# ---------------------------------------------------------------------------
# real LocalScheduler integration (worker subprocesses)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_local_scheduler_end_to_end(tmp_path):
    from areal_tpu.infra.scheduler import LocalScheduler

    sched = LocalScheduler(log_dir=str(tmp_path), start_timeout=60)
    try:
        workers = sched.create_workers(Job(replicas=2, role="w"))
        assert len(workers) == 2
        for w in workers:
            sched.create_engine(
                w, "areal_tpu.infra.rpc.echo_engine.EchoEngine", tag=w.id
            )
        # distinct processes
        pids = sched.call_all(workers, "pid")
        assert len(set(pids)) == 2
        # args/kwargs + numpy round-trip
        r = sched.call_engine(workers[0], "echo", 1, k=np.arange(3))
        assert r["tag"] == "w-0" and np.array_equal(r["kwargs"]["k"], [0, 1, 2])
        doubled = sched.call_engine(workers[1], "double", np.arange(4, dtype=np.int32))
        assert np.array_equal(doubled, np.arange(4, dtype=np.int32) * 2)
        # worker errors surface as controller-side exceptions
        with pytest.raises(RuntimeError, match="boom"):
            sched.call_engine(workers[0], "boom")
        # CPU pinning: a worker that was not given the chip can never take it
        assert sched.call_engine(workers[0], "env", "JAX_PLATFORMS") == "cpu"
        sched.check_health("w")
    finally:
        sched.delete_workers()
