"""LocalLauncher: SPMD-mode process orchestration on one host.

Reference: areal/infra/launcher/local.py:84-431. The launcher (1) spawns the
inference-server array, (2) waits for their addresses to appear in
name_resolve, (3) runs the trainer entrypoint with AREAL_LLM_SERVER_ADDRS
set, and (4) supervises: on trainer failure it relaunches the whole trial
with run_id+1 up to ``recover_retries`` when recover mode is on/auto
(reference :399-425 — the launcher IS the failure-recovery supervisor;
checkpoint restore happens inside the relaunched trainer via RecoverHandler).

TPU process topology: a chip belongs to one process at a time, and one jax
process owns ALL of a host's chips; `torchrun --nproc-per-node N` has no
equivalent here. So on one host exactly one child can own the TPU — the
(single) server or the trainer — and the other runs on the CPU backend,
by an explicit flag and with a log line saying so. Every child is spawned
with ``JAX_PLATFORMS`` set: ``tpu`` for the owner, so that it FAILS at
start-up when jax finds no chip rather than quietly computing on the CPU,
and ``cpu`` for the rest, so that they can never take the chip from it.
To put server and trainer on the same chip, run them in one process
(examples/math/common.py ``start_single_host_stack``).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

from areal_tpu.utils import logging as alog, name_resolve

logger = alog.getLogger("local_launcher")

SERVER_ADDRS_ENV = "AREAL_LLM_SERVER_ADDRS"
RUN_ID_ENV = "AREAL_RUN_ID"



def _child_env(on_tpu: bool) -> dict:
    """This process's environment with the child's backend pinned: the chip
    owner must get a TPU or die, everyone else stays off it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu" if on_tpu else "cpu"
    return env


class LocalLauncher:
    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        n_servers: int = 1,
        server_args: list[str] | None = None,
        server_on_tpu: bool = True,
        trainer_on_tpu: bool = False,
        log_dir: str = "/tmp/areal_tpu/launcher",
        recover_mode: str = "off",  # off | on | auto (reference recover modes)
        recover_retries: int = 1,
        server_start_timeout: float = 300.0,
    ):
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.n_servers = n_servers
        self.server_args = list(server_args or [])
        self.server_on_tpu = server_on_tpu
        self.trainer_on_tpu = trainer_on_tpu
        self.log_dir = log_dir
        self.recover_mode = recover_mode
        self.recover_retries = recover_retries
        self.server_start_timeout = server_start_timeout
        if server_on_tpu and (trainer_on_tpu or n_servers > 1):
            raise ValueError(
                "one process per chip: a host's TPU belongs to ONE child. "
                f"Got server_on_tpu with n_servers={n_servers}, "
                f"trainer_on_tpu={trainer_on_tpu}; pin the others to the CPU, "
                "or colocate server and trainer in one process "
                "(examples/math/common.py start_single_host_stack)"
            )
        logger.info(
            f"placement: {n_servers} server(s) on "
            f"{'tpu' if server_on_tpu else 'cpu'}, trainer on "
            f"{'tpu' if trainer_on_tpu else 'cpu'}"
        )
        self._server_procs: list[subprocess.Popen] = []
        os.makedirs(log_dir, exist_ok=True)
        # cross-process discovery: pin the file-backed name_resolve tree and
        # export it so every child resolves against the same root
        os.environ.setdefault("AREAL_NAME_RESOLVE", "file")
        os.environ.setdefault(
            "AREAL_NAME_RESOLVE_ROOT", os.path.join(log_dir, "name_resolve")
        )
        name_resolve.reconfigure(
            "file", root=os.environ["AREAL_NAME_RESOLVE_ROOT"]
        )

    @classmethod
    def from_config(cls, config, **overrides) -> "LocalLauncher":
        """Build from an experiment config: ``config.allocation_mode`` (when
        set) sizes the server array (one server per gen DP replica) and the
        engine meshes; recover policy comes from ``config.recover``."""
        from areal_tpu.api.alloc_mode import apply_allocation_mode

        apply_allocation_mode(config)
        kw = dict(
            experiment_name=config.experiment_name,
            trial_name=config.trial_name,
            n_servers=config.launcher.n_servers,
            recover_mode=getattr(config.recover, "mode", "off"),
            recover_retries=getattr(config.recover, "retries", 1),
            server_start_timeout=config.scheduler.startup_timeout,
        )
        kw.update(overrides)
        return cls(**kw)

    # -- inference fleet --------------------------------------------------
    @property
    def _ns_key(self) -> str:
        return name_resolve.rollout_server_key(
            self.experiment_name, self.trial_name
        )

    def start_servers(self) -> list[str]:
        """Spawn the server array; wait for name_resolve registration."""
        for i in range(self.n_servers):
            env = _child_env(self.server_on_tpu)
            from areal_tpu.utils.network import ensure_pkg_on_pythonpath

            ensure_pkg_on_pythonpath(env)
            log_path = os.path.join(self.log_dir, f"server-{i}.log")
            logf = open(log_path, "ab")
            proc = subprocess.Popen(
                [
                    sys.executable,
                    "-u",
                    "-m",
                    "areal_tpu.inference.server",
                    "--name",
                    f"{self._ns_key}/{i}",
                    *self.server_args,
                ],
                env=env,
                stdout=logf,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
            logf.close()
            self._server_procs.append(proc)
        deadline = time.monotonic() + self.server_start_timeout
        while True:
            addrs = name_resolve.get_subtree(self._ns_key)
            if len(addrs) >= self.n_servers:
                logger.info(f"servers up: {addrs}")
                return addrs
            for i, p in enumerate(self._server_procs):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"server {i} died rc={p.returncode}; see "
                        f"{self.log_dir}/server-{i}.log"
                    )
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"servers not registered after {self.server_start_timeout}s"
                )
            time.sleep(0.5)

    def stop_servers(self) -> None:
        for p in self._server_procs:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        for p in self._server_procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        self._server_procs = []
        try:
            name_resolve.clear_subtree(self._ns_key)
        except Exception:  # noqa: BLE001
            pass

    # -- trainer + supervision -------------------------------------------
    def run_trainer(self, trainer_cmd: list[str], extra_env: dict | None = None) -> int:
        """Run the trainer under restart supervision. Returns the final rc."""
        addrs = name_resolve.get_subtree(self._ns_key)
        attempt = 0
        while True:
            env = _child_env(self.trainer_on_tpu)
            if not self.trainer_on_tpu:
                logger.warning(
                    "trainer child runs on the CPU backend "
                    "(JAX_PLATFORMS=cpu): trainer_on_tpu is off"
                    + (
                        " and this host's TPU belongs to the server"
                        if self.server_on_tpu
                        else ""
                    )
                )
            env[SERVER_ADDRS_ENV] = ",".join(addrs)
            env[RUN_ID_ENV] = str(attempt)
            env.update(extra_env or {})
            log_path = os.path.join(self.log_dir, f"trainer-run{attempt}.log")
            logger.info(f"launching trainer (run_id={attempt}) -> {log_path}")
            with open(log_path, "ab") as logf:
                proc = subprocess.Popen(
                    trainer_cmd,
                    env=env,
                    stdout=logf,
                    stderr=subprocess.STDOUT,
                    start_new_session=True,
                )
                rc = proc.wait()
            if rc == 0:
                return 0
            if (
                self.recover_mode in ("on", "auto")
                and attempt < self.recover_retries
            ):
                attempt += 1
                logger.warning(
                    f"trainer failed rc={rc}; relaunching run_id={attempt} "
                    f"(reference launcher/local.py:399-425 semantics)"
                )
                continue
            return rc

    def launch(self, trainer_cmd: list[str], extra_env: dict | None = None) -> int:
        """Full trial: servers + supervised trainer, teardown on exit."""
        try:
            self.start_servers()
            return self.run_trainer(trainer_cmd, extra_env)
        finally:
            self.stop_servers()
