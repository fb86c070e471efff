"""LocalScheduler: worker subprocesses on this host.

Reference: areal/infra/scheduler/local.py:82-1533 (subprocess spawn, port
allocation, colocation, readiness polling, health checks, log-tail capture
on failure). TPU differences: device allocation is per-host, not per-GPU,
and a chip belongs to one process at a time. Every worker is spawned with
``JAX_PLATFORMS`` set: a worker that owns the host's TPU chips
(`Job.tpus > 0`) gets ``tpu`` and so FAILS at start-up when jax finds no
chip instead of quietly computing on the CPU; every other worker gets
``cpu``, so an auxiliary process can never take the chip from its owner
(a second process touching a held chip fails or hangs).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field

from areal_tpu.api.scheduler_api import Job, Scheduler, Worker
from areal_tpu.utils import logging as alog, network

logger = alog.getLogger("local_scheduler")


@dataclass
class _Proc:
    worker: Worker
    proc: subprocess.Popen
    log_path: str
    job: Job = field(default=None)  # type: ignore[assignment]


# control-plane JSON RPC (shared helper; rpc_server ships structured errors)
_http_json = network.http_json


class LocalScheduler(Scheduler):
    def __init__(
        self,
        log_dir: str = "/tmp/areal_tpu/scheduler",
        start_timeout: float = 120.0,
        tpu_exclusive: bool = True,
    ):
        self.log_dir = log_dir
        self.start_timeout = start_timeout
        self.tpu_exclusive = tpu_exclusive
        self._procs: dict[str, list[_Proc]] = {}  # role -> procs
        self._role_env: dict[str, dict[str, str]] = {}
        self._tpu_owner: str | None = None
        os.makedirs(log_dir, exist_ok=True)

    # -- worker lifecycle -------------------------------------------------
    def create_workers(self, job: Job) -> list[Worker]:
        assert job.role not in self._procs, f"role {job.role} exists"
        if job.tpus > 0 and job.replicas > 1:
            raise RuntimeError(
                f"role {job.role!r}: {job.replicas} replicas each asking for "
                "this host's TPU — one process per chip; use replicas=1 "
                "(one jax process drives all local chips) or tpus=0"
            )
        if job.tpus > 0:
            if self.tpu_exclusive and self._tpu_owner is not None:
                if job.colocate_with != self._tpu_owner:
                    raise RuntimeError(
                        f"TPU already owned by role {self._tpu_owner!r}; "
                        f"colocate_with it or use tpus=0"
                    )
            self._tpu_owner = self._tpu_owner or job.role
        procs: list[_Proc] = []
        for i in range(job.replicas):
            procs.append(
                self._spawn(
                    role=job.role,
                    index=i,
                    module="areal_tpu.infra.rpc.rpc_server",
                    argv=["--port", "{port}"],
                    extra_env=job.env,
                    on_tpu=job.tpus > 0,
                    job=job,
                )
            )
        self._procs[job.role] = procs
        try:
            self._wait_healthy(procs)
        except Exception:
            self.delete_workers(job.role)
            raise
        return [p.worker for p in procs]

    def _spawn(
        self,
        role: str,
        index: int,
        module: str,
        argv: list[str],
        *,
        on_tpu: bool,
        extra_env: dict[str, str] | None = None,
        job: Job | None = None,
        ip: str = "127.0.0.1",
    ) -> _Proc:
        """One worker subprocess: env assembly (role env + the backend pin:
        ``JAX_PLATFORMS=tpu`` for the chip owner, ``cpu`` for everyone
        else), ``python -m module`` with "{port}" substituted, log
        redirection. Shared by create_workers and fork_workers so the
        spawn mechanics live in exactly one place."""
        port = network.find_free_port()
        wid = f"{role}-{index}"
        env = dict(os.environ)
        env.update(self._role_env.get(role, {}))
        env.update(extra_env or {})
        network.ensure_pkg_on_pythonpath(env)
        env["JAX_PLATFORMS"] = "tpu" if on_tpu else "cpu"
        log_path = os.path.join(self.log_dir, f"{wid}.log")
        with open(log_path, "ab") as logf:
            proc = subprocess.Popen(
                [
                    sys.executable,
                    "-u",
                    "-m",
                    module,
                    *[a.replace("{port}", str(port)) for a in argv],
                ],
                env=env,
                stdout=logf,
                stderr=subprocess.STDOUT,
                start_new_session=True,
                cwd=os.getcwd(),
            )
        worker = Worker(id=wid, role=role, ip=ip, ports=[port])
        return _Proc(worker=worker, proc=proc, log_path=log_path, job=job)

    def _wait_healthy(self, procs: list[_Proc]) -> None:
        deadline = time.monotonic() + self.start_timeout
        for p in procs:
            last_err: BaseException | None = None
            while True:
                if p.proc.poll() is not None:
                    raise RuntimeError(
                        f"worker {p.worker.id} died rc={p.proc.returncode}:\n"
                        + self._log_tail(p)
                    )
                try:
                    d = _http_json(
                        f"http://{p.worker.address}/health", timeout=2
                    )
                    if d.get("status") == "ok":
                        break
                    last_err = RuntimeError(f"/health says {d!r}")
                except Exception as e:  # noqa: BLE001 — still booting
                    last_err = e
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"worker {p.worker.id} not healthy after "
                        f"{self.start_timeout}s (last error: {last_err!r}):\n"
                        + self._log_tail(p)
                    )
                time.sleep(0.2)

    def _log_tail(self, p: _Proc, n: int = 30) -> str:
        try:
            with open(p.log_path, "rb") as f:
                return b"\n".join(f.read().splitlines()[-n:]).decode(
                    errors="replace"
                )
        except OSError:
            return "<no log>"

    def get_workers(self, role: str) -> list[Worker]:
        return [p.worker for p in self._procs.get(role, [])]

    def check_health(self, role: str) -> None:
        """Raise if any worker of the role died (reference liveness poll,
        scheduler/local.py:903-919)."""
        for p in self._procs.get(role, []):
            if p.proc.poll() is not None:
                raise RuntimeError(
                    f"worker {p.worker.id} died rc={p.proc.returncode}:\n"
                    + self._log_tail(p)
                )

    def delete_workers(self, role: str | None = None) -> None:
        roles = [role] if role else list(self._procs)
        for r in roles:
            for p in self._procs.pop(r, []):
                if p.proc.poll() is None:
                    try:
                        _http_json(
                            f"http://{p.worker.address}/kill", {}, timeout=2
                        )
                    except Exception as e:  # noqa: BLE001 — SIGKILL follows
                        logger.debug(f"graceful kill of {p.worker.id} failed: {e!r}")
                    try:
                        p.proc.wait(timeout=5)
                    except subprocess.TimeoutExpired:
                        try:
                            os.killpg(os.getpgid(p.proc.pid), signal.SIGKILL)
                        except (ProcessLookupError, PermissionError):
                            pass
                        p.proc.wait(timeout=5)
            if r == self._tpu_owner:
                self._tpu_owner = None

    def set_worker_env(self, role: str, env: dict[str, str]) -> None:
        self._role_env.setdefault(role, {}).update(env)

    def respawn_worker(self, worker: Worker) -> Worker:
        """Replace one (presumed-dead) worker subprocess in place: same
        role, same slot index (so the worker id is stable and supervisor
        respawn budgets accumulate per slot), fresh port. Any process still
        attached to the slot is killed first."""
        procs = self._procs.get(worker.role)
        assert procs, f"no workers of role {worker.role!r}"
        slot = next(
            (i for i, p in enumerate(procs) if p.worker.id == worker.id), None
        )
        assert slot is not None, f"unknown worker {worker.id}"
        old = procs[slot]
        if old.proc.poll() is None:
            try:
                os.killpg(os.getpgid(old.proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                old.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        job = old.job
        index = int(worker.id.rsplit("-", 1)[-1])
        fresh = self._spawn(
            role=worker.role,
            index=index,
            module="areal_tpu.infra.rpc.rpc_server",
            argv=["--port", "{port}"],
            extra_env=(job.env if job is not None else None),
            on_tpu=job is not None and job.tpus > 0,
            job=job,
        )
        self._wait_healthy([fresh])
        procs[slot] = fresh
        logger.info(
            f"respawned worker {worker.id}: {worker.address} -> "
            f"{fresh.worker.address}"
        )
        return fresh.worker

    def fork_workers(
        self,
        role: str,
        target_role: str,
        command: str | None = None,
        args: list[str] | None = None,
    ) -> list[Worker]:
        """One colocated auxiliary process per ``target_role`` worker (on a
        single host: same machine, CPU-pinned, fresh port). The forked
        module owns its own protocol; health is polled on GET /health."""
        assert role not in self._procs, f"role {role} exists"
        targets = self._procs.get(target_role)
        assert targets, f"no workers of role {target_role!r} to fork from"
        module = command or "areal_tpu.infra.rpc.rpc_server"
        procs: list[_Proc] = []
        for i, tgt in enumerate(targets):
            procs.append(
                self._spawn(
                    role=role,
                    index=i,
                    module=module,
                    argv=list(args or ["--port", "{port}"]),
                    on_tpu=False,  # auxiliary: never touch the TPU
                    job=tgt.job,
                    ip=tgt.worker.ip,
                )
            )
        self._procs[role] = procs
        try:
            self._wait_healthy(procs)
        except Exception:
            self.delete_workers(role)
            raise
        return [p.worker for p in procs]

