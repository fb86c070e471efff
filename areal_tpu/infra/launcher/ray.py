"""RayLauncher: trial orchestration over a Ray cluster.

Reference: areal/infra/launcher/ray.py:77-635. The reference submits every
GPU process as a ``ray.remote`` task inside PACK placement groups, amends
torchrun-style env vars (RANK/MASTER_ADDR) so torch.distributed initializes,
and on any task failure cancels the trial and recursively relaunches it with
run_id+1 until the recover budget is spent.

TPU shape, re-derived rather than translated:
- one trainer task per HOST (jax owns every chip local to its process), so
  placement bundles are whole-host reservations, not per-GPU slots;
- the amended env is jax.distributed's coordinator tuple
  (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) instead of
  torchrun's rank vars;
- inference servers self-register in name_resolve exactly as under the
  Local/Slurm launchers (the name_resolve root must be cluster-visible:
  shared FS or etcd3), so controllers never learn which launcher placed them;
- supervision is the same run_id+1 loop as LocalLauncher.run_trainer — the
  launcher is the failure-recovery supervisor, checkpoint restore happens
  inside the relaunched trainer (utils/recover.py).

``ray`` is optional in the image; importing this module without ray only
raises when the launcher is constructed (same gating as RayScheduler).
"""

from __future__ import annotations

import importlib
import importlib.util
import os
import socket
import sys
import time

from areal_tpu.infra.launcher.local import RUN_ID_ENV, SERVER_ADDRS_ENV
from areal_tpu.utils import logging as alog, name_resolve

logger = alog.getLogger("ray_launcher")

POLL_INTERVAL_S = 0.2


def run_entry(entry: str, func_name: str, argv: list, env: dict) -> object:
    """Task body executed inside a ray worker: apply env, load the entry
    (a ``.py`` file path or a dotted module name), call ``func_name(argv)``.

    Top-level so both real ray and the in-process fake can serialize it by
    module path (reference run_func, launcher/ray.py:50-74)."""
    os.environ.update({k: str(v) for k, v in env.items()})
    if entry.endswith(".py") or os.path.sep in entry:
        module_name = "areal_ray_entry_" + os.path.basename(entry).replace(".", "_")
        spec = importlib.util.spec_from_file_location(module_name, entry)
        if spec is None:
            raise FileNotFoundError(f"cannot load entry file {entry!r}")
        module = importlib.util.module_from_spec(spec)
        sys.modules[module_name] = module
        spec.loader.exec_module(module)
    else:
        module = importlib.import_module(entry)
    try:
        fn = getattr(module, func_name)
    except AttributeError as e:
        raise ValueError(f"entry {entry!r} has no function {func_name!r}") from e
    return fn(list(argv))


def _node_addr() -> tuple[str, int]:
    """Runs pinned to placement bundle 0: reports (ip, free port) for the
    jax.distributed coordinator. Uses plain sockets, not
    ray.util.get_node_ip_address, so the body has no ray import (entry
    subprocesses under the fake harness have no ray module at all).

    IP via the UDP-connect trick: gethostbyname(gethostname()) returns
    127.0.1.1 on stock Debian /etc/hosts, which other hosts cannot dial."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as probe:
            probe.connect(("8.8.8.8", 80))  # no packet sent; routes only
            ip = probe.getsockname()[0]
    except OSError:
        try:
            ip = socket.gethostbyname(socket.gethostname())
        except OSError:
            ip = "127.0.0.1"
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    return ip, port


class RayLauncher:
    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        n_servers: int = 1,
        server_args: list[str] | None = None,
        server_entry: str = "areal_tpu.inference.server",
        server_func: str = "main",
        trainer_hosts: int = 1,
        server_on_tpu: bool = True,
        trainer_on_tpu: bool = True,
        log_dir: str = "/tmp/areal_tpu/ray_launcher",
        recover_mode: str = "off",  # off | on | auto
        recover_retries: int = 1,
        server_start_timeout: float = 300.0,
        cpus_per_task: int = 1,
        mem_mb_per_task: int = 1024,
        tpus_per_host: int = 0,
        ray_init_kwargs: dict | None = None,
    ):
        try:
            import ray  # noqa: F401
        except ImportError as e:  # pragma: no cover - ray not in TPU image
            raise RuntimeError(
                "RayLauncher requires the `ray` package (not in the base "
                "TPU image); use LocalLauncher or SlurmLauncher"
            ) from e
        import ray

        self._ray = ray
        if not ray.is_initialized():
            ray.init(**(ray_init_kwargs or {}))
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.n_servers = n_servers
        self.server_args = list(server_args or [])
        self.server_entry = server_entry
        self.server_func = server_func
        self.trainer_hosts = trainer_hosts
        self.server_on_tpu = server_on_tpu
        self.trainer_on_tpu = trainer_on_tpu
        self.log_dir = log_dir
        self.recover_mode = recover_mode
        self.recover_retries = recover_retries
        self.server_start_timeout = server_start_timeout
        self.cpus_per_task = cpus_per_task
        self.mem_mb_per_task = mem_mb_per_task
        self.tpus_per_host = tpus_per_host
        os.makedirs(log_dir, exist_ok=True)
        os.environ.setdefault("AREAL_NAME_RESOLVE", "file")
        os.environ.setdefault(
            "AREAL_NAME_RESOLVE_ROOT", os.path.join(log_dir, "name_resolve")
        )
        kind = os.environ["AREAL_NAME_RESOLVE"]
        kw = (
            {"root": os.environ["AREAL_NAME_RESOLVE_ROOT"]}
            if kind in ("file", "nfs")
            else {}
        )
        name_resolve.reconfigure(kind, **kw)
        self._remote_entry = ray.remote(run_entry)
        # job name -> object ref, mirroring the reference's self.jobs map
        self.jobs: dict[str, object] = {}
        self._trainer_pg = None

    @property
    def run_name(self) -> str:
        return f"{self.experiment_name}_{self.trial_name}"

    @property
    def _ns_key(self) -> str:
        return name_resolve.rollout_server_key(
            self.experiment_name, self.trial_name
        )

    # -- submission -------------------------------------------------------
    def _base_env(self, on_tpu: bool) -> dict[str, str]:
        env = {
            "AREAL_NAME_RESOLVE": os.environ["AREAL_NAME_RESOLVE"],
            "AREAL_NAME_RESOLVE_ROOT": os.environ["AREAL_NAME_RESOLVE_ROOT"],
        }
        # the etcd backend's connection tuple must reach remote workers too,
        # or their name_resolve dials 127.0.0.1:2379 on the worker node
        for var in ("AREAL_ETCD_ADDR", "AREAL_ETCD_USER", "AREAL_ETCD_PASSWORD"):
            if os.environ.get(var):
                env[var] = os.environ[var]
        # the backend pin, either way (infra/launcher/local.py): a task that
        # owns its node's chips must get a TPU or die, everyone else stays
        # on the CPU
        env["JAX_PLATFORMS"] = "tpu" if on_tpu else "cpu"
        return env

    def submit(
        self,
        job_name: str,
        entry: str,
        func_name: str,
        argv: list,
        env: dict[str, str],
        tpus: int = 0,
        placement_group=None,
        bundle_index: int = -1,
    ):
        """Submit one entry call as a ray task; tracked under ``job_name``."""
        opts: dict = {
            "num_cpus": self.cpus_per_task,
            "memory": self.mem_mb_per_task * 1024 * 1024,
            "runtime_env": {"env_vars": {k: str(v) for k, v in env.items()}},
        }
        if tpus > 0:
            # TPU is a custom ray resource (there is no num_gpus analogue);
            # clusters register it per node, e.g. {"TPU": 4}
            opts["resources"] = {"TPU": tpus}
        if placement_group is not None:
            from ray.util.scheduling_strategies import (
                PlacementGroupSchedulingStrategy,
            )

            opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                placement_group=placement_group,
                placement_group_bundle_index=bundle_index,
                placement_group_capture_child_tasks=True,
            )
        future = self._remote_entry.options(**opts).remote(
            entry, func_name, argv, env
        )
        self.jobs[job_name] = future
        return future

    def _poll(self, future) -> str:
        """'running' | 'done' | 'failed' (non-destructive)."""
        ray = self._ray
        try:
            ray.get(future, timeout=0.05)
            return "done"
        except ray.exceptions.GetTimeoutError:
            return "running"
        except Exception:  # noqa: BLE001 — RayTaskError and kin
            return "failed"

    # -- inference fleet --------------------------------------------------
    def start_servers(self) -> list[str]:
        """Submit the server array; wait for name_resolve registration."""
        env = self._base_env(self.server_on_tpu)
        for i in range(self.n_servers):
            self.submit(
                f"llm_server:{i}",
                self.server_entry,
                self.server_func,
                ["--name", f"{self._ns_key}/{i}", *self.server_args],
                env,
                tpus=self.tpus_per_host if self.server_on_tpu else 0,
            )
        deadline = time.monotonic() + self.server_start_timeout
        while True:
            addrs = name_resolve.get_subtree(self._ns_key)
            if len(addrs) >= self.n_servers:
                logger.info(f"servers up: {addrs}")
                return addrs
            for i in range(self.n_servers):
                if self._poll(self.jobs[f"llm_server:{i}"]) == "failed":
                    self.stop_all()
                    raise RuntimeError(f"server {i} task failed during startup")
            if time.monotonic() > deadline:
                self.stop_all()
                raise TimeoutError(
                    f"servers not registered after {self.server_start_timeout}s"
                )
            time.sleep(POLL_INTERVAL_S)

    # -- trainer + supervision -------------------------------------------
    def _ensure_trainer_pg(self):
        """Whole-host PACK bundles for the trainer gang; reused across
        recover relaunches (reference ray.py:183-218)."""
        if self._trainer_pg is not None or self.trainer_hosts <= 1:
            return self._trainer_pg
        ray = self._ray
        bundle: dict[str, float] = {"CPU": self.cpus_per_task}
        if self.tpus_per_host > 0 and self.trainer_on_tpu:
            bundle["TPU"] = self.tpus_per_host
        pg = ray.util.placement_group(
            bundles=[dict(bundle) for _ in range(self.trainer_hosts)],
            strategy="PACK",
        )
        ray.get(pg.ready(), timeout=60)
        self._trainer_pg = pg
        return pg

    def _coordinator_env(self, pg) -> dict[str, str]:
        """jax.distributed coordinator tuple from the bundle-0 node —
        the TPU analogue of the reference's torch_env_hook MASTER_ADDR."""
        if self.trainer_hosts <= 1:
            return {}
        ray = self._ray
        probe = self._ray.remote(_node_addr)
        opts: dict = {"num_cpus": 0}
        if pg is not None:
            from ray.util.scheduling_strategies import (
                PlacementGroupSchedulingStrategy,
            )

            opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                placement_group=pg, placement_group_bundle_index=0
            )
        ip, port = ray.get(probe.options(**opts).remote(), timeout=60)
        return {
            "JAX_COORDINATOR_ADDRESS": f"{ip}:{port}",
            "JAX_NUM_PROCESSES": str(self.trainer_hosts),
        }

    def _heal_servers(self) -> None:
        """Restart any dead server task before (re)launching the trainer —
        a crashed server would otherwise poison every relaunch with a stale
        address (the reference restarts the whole trial, ray.py:603-629;
        healing in place keeps live servers' KV and avoids a full redeploy)."""
        env = self._base_env(self.server_on_tpu)
        healed = False
        for i in range(self.n_servers):
            job = f"llm_server:{i}"
            if job in self.jobs and self._poll(self.jobs[job]) == "running":
                continue
            healed = True
            logger.warning(f"server task {job} is gone; resubmitting")
            try:
                name_resolve.delete(f"{self._ns_key}/{i}")
            except Exception:  # noqa: BLE001 — may have never registered
                pass
            self.jobs.pop(job, None)
            self.submit(
                job,
                self.server_entry,
                self.server_func,
                ["--name", f"{self._ns_key}/{i}", *self.server_args],
                env,
                tpus=self.tpus_per_host if self.server_on_tpu else 0,
            )
        if healed:
            deadline = time.monotonic() + self.server_start_timeout
            while len(name_resolve.get_subtree(self._ns_key)) < self.n_servers:
                if time.monotonic() > deadline:
                    raise TimeoutError("healed servers did not re-register")
                time.sleep(POLL_INTERVAL_S)

    def run_trainer(
        self,
        entry: str,
        argv: list | None = None,
        func_name: str = "main",
        extra_env: dict | None = None,
    ) -> int:
        """Run the trainer gang under restart supervision. Returns final rc
        (0 = every host task completed)."""
        argv = list(argv or [])
        attempt = 0
        while True:
            if attempt > 0:
                self._heal_servers()
            pg = self._ensure_trainer_pg()
            env = self._base_env(self.trainer_on_tpu)
            # re-read per attempt: healing may have re-registered servers
            addrs = name_resolve.get_subtree(self._ns_key)
            env[SERVER_ADDRS_ENV] = ",".join(addrs)
            env[RUN_ID_ENV] = str(attempt)
            env.update(self._coordinator_env(pg))
            env.update(extra_env or {})
            logger.info(
                f"launching trainer gang (run_id={attempt}, "
                f"hosts={self.trainer_hosts})"
            )
            names = []
            for i in range(self.trainer_hosts):
                host_env = dict(env)
                if self.trainer_hosts > 1:
                    host_env["JAX_PROCESS_ID"] = str(i)
                name = f"trainer:{attempt}:{i}"
                self.submit(
                    name,
                    entry,
                    func_name,
                    argv,
                    host_env,
                    tpus=self.tpus_per_host if self.trainer_on_tpu else 0,
                    placement_group=pg,
                    bundle_index=i if pg is not None else -1,
                )
                names.append(name)
            rc = self._wait_gang(names)
            if rc == 0:
                return 0
            if self.recover_mode in ("on", "auto") and attempt < self.recover_retries:
                attempt += 1
                logger.warning(
                    f"trainer gang failed; relaunching run_id={attempt} "
                    "(reference ray.py:603-629 recover loop)"
                )
                continue
            return rc

    def _wait_gang(self, names: list[str]) -> int:
        """Wait for a gang: 0 when all complete; on any failure cancel the
        rest (a dead jax process wedges the coordinator barrier) and
        return 1."""
        pending = set(names)
        while pending:
            for name in list(pending):
                st = self._poll(self.jobs[name])
                if st == "done":
                    pending.discard(name)
                    self.jobs.pop(name, None)
                elif st == "failed":
                    logger.error(f"trainer task {name} failed")
                    self.jobs.pop(name, None)
                    for other in pending - {name}:
                        self._cancel(other)
                    return 1
            time.sleep(POLL_INTERVAL_S)
        return 0

    def _cancel(self, job_name: str) -> None:
        future = self.jobs.pop(job_name, None)
        if future is None:
            return
        try:
            self._ray.cancel(future, force=True)
        except Exception as e:  # noqa: BLE001
            logger.warning(f"cancel {job_name}: {e}")

    def stop_all(self) -> None:
        for name in list(self.jobs):
            self._cancel(name)
        try:
            name_resolve.clear_subtree(self._ns_key)
        except Exception:  # noqa: BLE001
            pass

    def launch(
        self, entry: str, argv: list | None = None, extra_env: dict | None = None
    ) -> int:
        """Full trial: server array + supervised trainer gang, teardown on
        exit (reference ray_main, launcher/ray.py:345-629)."""
        try:
            self.start_servers()
            return self.run_trainer(entry, argv, extra_env=extra_env)
        finally:
            self.stop_all()
