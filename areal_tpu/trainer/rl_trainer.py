"""PPOTrainer: the user-facing RL training facade + step loop.

Behavioral parity with reference areal/trainer/rl_trainer.py (86-498): build
actor/critic/ref engines and the rollout client, then per global step run
    prepare_batch -> [values] -> [recompute logp] -> [ref logp]
    -> compute_advantages -> ppo_update (+critic)
    -> pause rollout -> update_weights -> set_version -> save -> recover-ckpt
    -> eval -> log -> resume
Async-vs-sync is one knob: ``config.rollout.max_head_offpolicyness`` (0 =
synchronous; the staleness manager then admits exactly one batch per
version — reference blog AReaL_v0_3 η semantics).
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Any, Callable

import numpy as np

from areal_tpu.api.config import PPOConfig
from areal_tpu.api.io_struct import StepInfo, WeightUpdateMeta
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.infra.trajectory_journal import journal_from_config
from areal_tpu.infra.workflow_executor import RolloutInterrupted
from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability import step_timeline
from areal_tpu.robustness.preemption import PreemptionHandler
from areal_tpu.trainer.ppo import PPOActor, PPOCritic
from areal_tpu.utils import logging as alog, perf_tracer, stats_tracker
from areal_tpu.utils.perf_tracer import Category
from areal_tpu.utils.data import StatefulDataLoader
from areal_tpu.utils.recover import RecoverHandler
from areal_tpu.utils.saver import Evaluator, Saver
from areal_tpu.utils.stats_logger import StatsLogger

logger = alog.getLogger("rl_trainer")


def resolve_weight_update_wire(config) -> str:
    """``weight_update_wire`` policy: "auto" -> "q8" when the serving fleet
    is int8-quantized (half the wire bytes, bit-identical to server-side
    quantization), else "bf16". Validates eagerly so a typo fails at
    trainer init, not at the first mid-training update."""
    wire = getattr(config, "weight_update_wire", "auto") or "auto"
    if wire == "auto":
        server_cfg = getattr(config, "server", None)
        wire = (
            "q8"
            if server_cfg is not None
            and getattr(server_cfg, "quantization", "none") == "int8"
            else "bf16"
        )
    if wire not in ("bf16", "q8"):
        raise ValueError(
            f"weight_update_wire={wire!r}; valid: auto|bf16|q8 "
            "(int8 is a ServerConfig.quantization value, not a wire format)"
        )
    if wire == "q8":
        server_cfg = getattr(config, "server", None)
        if getattr(server_cfg, "quantization", "none") != "int8":
            raise ValueError(
                "weight_update_wire='q8' requires an int8-serving fleet "
                "(set server.quantization='int8') — servers reject q8-wire "
                "leaves otherwise, at the first mid-training update"
            )
    return wire


class PPOTrainer:
    def __init__(
        self,
        config: PPOConfig,
        train_dataset,
        valid_dataset=None,
        rollout=None,
        eval_rollout=None,
        tokenizer=None,
        actor_engine=None,
        critic_engine=None,
        ref_engine=None,
    ):
        self.config = config
        self.tokenizer = tokenizer

        # allocation-mode DSL is the single topology knob (reference
        # rl_trainer.py:91): resolve it into engine/server MeshConfigs first
        from areal_tpu.api.alloc_mode import apply_allocation_mode

        self.allocation_mode = apply_allocation_mode(config)
        if config.cluster.name_resolve.type != "memory":
            # the discovery backend must be live BEFORE any rollout client
            # resolves server addresses (reference NameResolveConfig wiring)
            from areal_tpu.utils import name_resolve

            name_resolve.reconfigure_from_config(config.cluster.name_resolve)

        self.train_dataloader = StatefulDataLoader(
            train_dataset,
            batch_size=config.train_dataset.batch_size,
            shuffle=config.train_dataset.shuffle,
            seed=config.seed,
            drop_last=config.train_dataset.drop_last,
        )
        self.valid_dataset = valid_dataset
        from areal_tpu.api.io_struct import FinetuneSpec

        self.ft_spec = FinetuneSpec(
            total_train_epochs=config.total_train_epochs,
            dataset_size=len(train_dataset),
            train_batch_size=config.train_dataset.batch_size,
        )

        # engines (injectable for tests / custom backends)
        config.actor.temperature = config.gconfig.temperature
        self.actor_engine = actor_engine or JaxTrainEngine(config.actor)
        if getattr(self.actor_engine, "params", 1) is None or actor_engine is None:
            self.actor_engine.initialize(self.ft_spec)
        self.actor = PPOActor(config.actor, self.actor_engine)

        self.critic = None
        if config.critic is not None:
            eng = critic_engine or JaxTrainEngine(config.critic, value_head=True)
            if critic_engine is None:
                eng.initialize(self.ft_spec)
            self.critic = PPOCritic(config.critic, eng)

        self.ref = None
        if config.ref is not None:
            eng = ref_engine or JaxTrainEngine(config.ref, need_optimizer=False)
            if ref_engine is None:
                eng.initialize(self.ft_spec)
            self.ref = PPOActor(config.actor, eng)

        # rollout client
        if rollout is None:
            from areal_tpu.inference.client import RemoteJaxEngine

            addrs = os.environ.get("AREAL_TPU_SERVER_ADDRS", "")
            rollout = RemoteJaxEngine(
                config.rollout, addresses=[a for a in addrs.split(",") if a]
            )
            rollout.initialize()
        self.rollout = rollout
        # eval must NOT share the training executor: its results buffer
        # interleaves with async training trajectories (the reference builds
        # a separate eval_rollout client for the same reason)
        if eval_rollout is None and valid_dataset is not None:
            import dataclasses as _dc

            from areal_tpu.inference.client import RemoteJaxEngine

            eval_cfg = _dc.replace(
                config.rollout,
                max_head_offpolicyness=10_000_000,  # eval is version-agnostic
                max_concurrent_rollouts=config.rollout.max_concurrent_rollouts,
            )
            eval_rollout = RemoteJaxEngine(
                eval_cfg, addresses=list(self.rollout.addresses)
            )
            eval_rollout.initialize()
        self.eval_rollout = eval_rollout

        # weight update channel
        mode = config.weight_update_mode or config.actor.weight_update_mode
        update_dir = os.path.join(
            config.cluster.fileroot,
            config.experiment_name,
            config.trial_name,
            "update_weights",
        )
        wire = resolve_weight_update_wire(config)
        self.weight_update_meta = WeightUpdateMeta(
            type=mode, path=update_dir, with_version=True, wire_format=wire
        )
        self.actor_engine.connect_engine(self.rollout, self.weight_update_meta)

        # aux subsystems
        for c in (
            config.saver,
            config.checkpointer,
            config.evaluator,
            config.recover,
            config.stats_logger,
        ):
            c.experiment_name = c.experiment_name or config.experiment_name
            c.trial_name = c.trial_name or config.trial_name
            if hasattr(c, "fileroot"):
                c.fileroot = c.fileroot or config.cluster.fileroot
        perf_tracer.configure(config.perf_tracer, rank=0, role="trainer")
        self._obs = obs_catalog.trainer_metrics()
        # trainer goodput observatory (docs/observability.md "Trainer
        # observatory"): per-step phase timeline + utilization gauges, and
        # the XLA compile counters that make recompile storms visible
        self.step_recorder = step_timeline.StepTimelineRecorder()
        from areal_tpu.utils import compile_cache

        compile_cache.install_compile_counters()
        # on-demand device profiling: SIGUSR2 sets this flag (handler is
        # flag-only per the arealint SIG contract) and the NEXT step runs
        # under a jax.profiler trace, exactly like perf_tracer.profile_steps
        self._profile_requested = threading.Event()
        self.last_hbm_ledger: dict | None = None
        self.saver = Saver(config.saver, self.ft_spec)
        self.evaluator = Evaluator(config.evaluator, self.ft_spec)
        self.recover_handler = RecoverHandler(config.recover, self.ft_spec)
        self.stats_logger = StatsLogger(config.stats_logger, self.ft_spec)
        self.recover_info = self.recover_handler.load(
            self.actor_engine,
            saver=self.saver,
            evaluator=self.evaluator,
            dataloader=self.train_dataloader,
            inference_engine=self.rollout,
            weight_update_meta=self.weight_update_meta,
        )

        # durable trajectory journal (infra/trajectory_journal.py):
        # accepted-but-unconsumed rollouts survive trainer death; on a
        # recovered start the in-bound entries replay into the batch queue
        # instead of being re-generated
        self.journal = journal_from_config(
            config.rollout.journal,
            fileroot=config.cluster.fileroot,
            experiment=config.experiment_name,
            trial=config.trial_name,
        )
        if self.journal is not None and hasattr(self.rollout, "attach_journal"):
            self.rollout.attach_journal(self.journal)
            if self.recover_info is not None and hasattr(
                self.rollout, "replay_from_journal"
            ):
                t_replay = time.monotonic()
                replayed, dropped = self.rollout.replay_from_journal(
                    config.rollout.max_head_offpolicyness
                )
                if replayed or dropped:
                    logger.info(
                        f"recovered {replayed} journaled trajectories "
                        f"({dropped} over-stale dropped) in "
                        f"{time.monotonic() - t_replay:.2f}s — rollout "
                        "regeneration saved"
                    )

        # journal GC lags one recover generation: segments consumed below
        # this version are durable inside a checkpoint load() can reach
        self._journal_gc_version = (
            self.recover_info.last_step_info.global_step + 1
            if self.recover_info is not None
            else 0
        )

        # goodput autopilot (areal_tpu/autopilot/, docs/autopilot.md):
        # trainer-side placement — the staleness controller actuates the
        # in-process StalenessManager directly while the replica knobs
        # ride POST /autopilot/knobs. Off by default; the static config
        # then behaves exactly as before.
        self.autopilot = None
        ap_cfg = getattr(config.rollout, "autopilot", None)
        if ap_cfg is not None and ap_cfg.enabled:
            from areal_tpu.autopilot import autopilot_from_config

            self.autopilot = autopilot_from_config(
                ap_cfg,
                lambda: list(getattr(self.rollout, "addresses", []) or []),
                staleness_manager=getattr(
                    getattr(self.rollout, "executor", None), "staleness", None
                ),
            )
            if self.autopilot is not None:
                self.autopilot.seed_setpoints(
                    max_queue_depth=config.server.lifecycle.max_queue_depth,
                    min_free_pages=config.server.lifecycle.min_free_pages,
                    radix_max_fraction=config.server.prefix_cache.max_fraction,
                )
                self.autopilot.start()
                logger.info(
                    "goodput autopilot started: "
                    f"{[c.name for c in self.autopilot.controllers]} "
                    f"(signals: {ap_cfg.metrics_addr or 'local registry'})"
                )
                if not ap_cfg.metrics_addr:
                    # the trainer registry carries bubble/span but NOT the
                    # remote fleet's serving tails — without metrics_addr
                    # the admission/cache controllers hold on absent
                    # signals (areal_autopilot_signal_hold_total counts it)
                    logger.warning(
                        "autopilot.metrics_addr is unset: serving-side "
                        "signals (queue-wait, shed, prefix-hit, HBM) are "
                        "only visible for in-process fleets — point it at "
                        "the controller telemetry /metrics for a remote "
                        "fleet (docs/autopilot.md)"
                    )

        # preemption tolerance (robustness/preemption.py): the SIGTERM
        # handler only sets an event; the step loop polls it at phase
        # boundaries and the executor's blocking waits abort on it
        self.preempted = False
        self.preemption: PreemptionHandler | None = None
        if config.preemption.enabled:
            self.preemption = PreemptionHandler(
                role="trainer",
                grace_s=config.preemption.grace_s,
                handle_sigusr1=config.preemption.handle_sigusr1,
            )
            if hasattr(self.rollout, "set_interrupt"):
                self.rollout.set_interrupt(self.preemption.requested)

    # -- preemption (robustness/preemption.py) -----------------------------
    def _preempt_requested(self) -> bool:
        return self.preemption is not None and self.preemption.requested.is_set()

    def _handle_preemption(self, last_completed: StepInfo | None) -> None:
        """Grace-window drain: stop rollout submissions, force an
        emergency (sync, durable) recover dump of the last COMPLETED step,
        seal the trajectory journal, and mark the trial preempted — the
        caller exits cleanly and the relauncher resumes from here."""
        assert self.preemption is not None
        self.preemption.note_draining()
        t0 = time.monotonic()
        self.rollout.pause()
        if last_completed is not None:
            try:
                self.recover_handler.dump_emergency(
                    self.actor_engine,
                    last_completed,
                    saver=self.saver,
                    evaluator=self.evaluator,
                    dataloader=self.train_dataloader,
                    tokenizer=self.tokenizer,
                )
            except Exception:  # noqa: BLE001 — an older durable generation
                # (plus the journal) still recovers the trial; dying inside
                # the grace window with no exit is the one unacceptable path
                logger.exception("emergency recover dump failed")
        if self.journal is not None:
            self.journal.seal_active()
        self._dump_lineage("preempt")
        self.preemption.note_drained(time.monotonic() - t0)
        self.preempted = True
        logger.warning(
            "trainer preempted: emergency state durable, rollout drained — "
            "exiting the step loop cleanly"
        )

    def _on_profile_signal(self, signum, frame) -> None:
        # flag-only (arealint SIG family): the step loop does the work
        self._profile_requested.set()

    def _dump_lineage(self, reason: str) -> None:
        """Persist the trajectory-lineage ring next to the flight-recorder
        dumps (docs/observability.md "Learning-health observatory"):
        tools/postmortem.py merges both into one incident trace, joining
        generate -> journal -> consume -> update by trace id."""
        from areal_tpu.observability import lineage as lineage_mod

        ring = lineage_mod.get_lineage()
        if not ring.recent(1):
            return  # nothing recorded (e.g. SFT-style runs): no dump file
        try:
            ring.dump(lineage_mod.default_dump_path(reason), reason)
        except OSError:
            logger.exception("trajectory lineage dump failed")

    # -- step loop --------------------------------------------------------
    # arealint: hot-path — the RL step loop: every statement here runs once
    # per global step, so PRF flags any blocking device read added to it
    def train(
        self,
        workflow: Any = None,
        eval_workflow: Any = None,
        dynamic_filter_fn: Callable | None = None,
    ) -> None:
        config = self.config
        start_step = (
            self.recover_info.last_step_info.next().global_step
            if self.recover_info is not None
            else 0
        )
        steps_per_epoch = len(self.train_dataloader)
        max_steps = config.total_train_epochs * steps_per_epoch
        if config.total_train_steps is not None:
            max_steps = min(max_steps, config.total_train_steps)
        if self.preemption is not None:
            self.preemption.install()
        try:
            # docs/observability.md "On-demand device profiling": SIGUSR2
            # profiles the next step without restarting the trial
            signal.signal(signal.SIGUSR2, self._on_profile_signal)
        except ValueError:
            logger.debug("SIGUSR2 profile trigger unavailable off the main thread")
        last_completed: StepInfo | None = (
            self.recover_info.last_step_info
            if self.recover_info is not None
            else None
        )

        for global_step in range(start_step, max_steps):
            if self._preempt_requested():
                self._handle_preemption(last_completed)
                return
            epoch = global_step // steps_per_epoch
            step = global_step % steps_per_epoch
            t_step = time.monotonic()
            # detailed device profile at requested steps (perf_tracer
            # .profile_steps — reference knob; XLA profiler instead of
            # torch.profiler, traces viewable in TensorBoard/XProf), or on
            # demand via SIGUSR2 (flag consumed here, one step per signal)
            profiling = bool(
                config.perf_tracer.profile_steps
                and global_step in config.perf_tracer.profile_steps
            )
            if self._profile_requested.is_set():
                self._profile_requested.clear()
                profiling = True
                logger.info(f"SIGUSR2: device-profiling step {global_step}")
            if profiling:
                perf_tracer.start_device_profile()

            tl = self.step_recorder.start(global_step)
            try:
                with tl.phase("rollout_wait", args={"global_step": global_step}):
                    batch = self.rollout.prepare_batch(
                        self.train_dataloader,
                        workflow=workflow,
                        should_accept_fn=dynamic_filter_fn,
                    )
            except RolloutInterrupted:
                # SIGTERM landed while waiting on rollout: abort this step
                # (the executor raised out of its blocking wait; accepted
                # work is journaled and replays after relaunch)
                self.step_recorder.abandon(tl)
                self._handle_preemption(last_completed)
                return
            if self._preempt_requested():
                # signal landed after the batch was ready — the remaining
                # phases (fwd/bwd, weight push) can outlast the grace
                # window, so abort the step; the popped batch replays from
                # the journal (its consumption marker post-dates the dump)
                self.step_recorder.abandon(tl)
                self._handle_preemption(last_completed)
                return

            # device fwd passes + the update: the engine attributes its own
            # host_prep / forward_backward / optimizer spans into ``tl``
            # through the step_timeline.engine_phase hook — the superseded
            # per-block stats_tracker timing keys are gone (docs note)
            n_extra_fwd = 0
            if self.critic is not None:
                with perf_tracer.trace_scope(
                    "areal.train.compute_values", Category.COMPUTE
                ):
                    batch["values"] = self.critic.compute_values(batch)
                n_extra_fwd += 1

            if self.actor.should_compute_prox_logp():
                with perf_tracer.trace_scope(
                    "areal.train.recompute_logp", Category.COMPUTE
                ):
                    batch["prox_logp"] = self.actor.compute_logp(batch)
                n_extra_fwd += 1

            if self.ref is not None:
                with perf_tracer.trace_scope(
                    "areal.train.ref_logp", Category.COMPUTE
                ):
                    batch["ref_logp"] = self.ref.compute_logp(batch)
                n_extra_fwd += 1

            with tl.phase("host_prep"):
                adv_batch = self.actor.compute_advantages(batch)

            t_train = time.monotonic()
            self.actor.ppo_update(adv_batch)
            if self.critic is not None:
                self.critic.ppo_update(adv_batch)
            train_step_secs = time.monotonic() - t_train

            # §3.4 protocol: stop submissions, push weights, advance version
            with tl.phase("weight_publish", Category.COMM):
                self.rollout.pause()
                t_update = time.monotonic()
                new_version = global_step + 1
                self.actor_engine.update_weights(self.weight_update_meta)
                self.actor_engine.set_version(new_version)
                if self.critic is not None:
                    self.critic.engine.set_version(new_version)
                self.rollout.set_version(new_version)
                if self.eval_rollout is not None:
                    self.eval_rollout.set_version(new_version)
            self._obs.update_seconds.observe(time.monotonic() - t_update)
            self._obs.version.set(new_version)

            t_save = time.monotonic()
            with tl.phase("ckpt_eval", Category.IO):
                self.saver.maybe_save(
                    self.actor_engine, epoch, step, global_step, self.tokenizer
                )
                # async recover dump: the step loop pauses only for the
                # host snapshot; Orbax writes (and the recover records
                # land) on a background thread. Emergency dumps on the
                # preemption path stay synchronous.
                dumped = self.recover_handler.dump(
                    self.actor_engine,
                    StepInfo(
                        epoch=epoch,
                        epoch_step=step,
                        global_step=global_step,
                        steps_per_epoch=steps_per_epoch,
                    ),
                    saver=self.saver,
                    evaluator=self.evaluator,
                    dataloader=self.train_dataloader,
                    tokenizer=self.tokenizer,
                    async_=True,
                )
                if dumped is not None and self.journal is not None:
                    # GC journal segments fully consumed by steps the
                    # PREVIOUS dump already covers (this dump's write may
                    # still be in flight; the lag keeps gc safe even if it
                    # fails and recovery falls back a generation)
                    self.journal.gc(self._journal_gc_version)
                    self._journal_gc_version = new_version

            save_secs = time.monotonic() - t_save
            # resume BEFORE eval: the default eval client is the training
            # rollout client, whose dispatcher skips submissions while paused
            # (a dedicated eval_rollout keeps the reference's order anyway)
            self.rollout.resume()
            t_eval = time.monotonic()
            with tl.phase("ckpt_eval"):
                self._maybe_evaluate(eval_workflow or workflow, epoch, global_step)
            eval_secs = time.monotonic() - t_eval

            bd = self._complete_step_timeline(tl, batch, n_extra_fwd)
            stats = stats_tracker.export_all()
            stats.update(self.rollout.export_stats())
            stats.update(step_timeline.breakdown_stat_keys(bd))
            # backward-compatible timing keys (the per-block ad-hoc
            # record_timing scopes these replace; the dropped keys —
            # critic_values/recompute_logp/ref_logp/compute_advantages/
            # critic_train_step — are folded into the phase vocabulary)
            stats["timing/rollout"] = bd["rollout_wait_s"]
            stats["timing/train_step"] = train_step_secs
            stats["timing/update_weights"] = bd["weight_publish_s"]
            stats["timing/save"] = save_secs
            stats["timing/eval"] = eval_secs
            if self.last_hbm_ledger is not None:
                stats["hbm/in_use_bytes"] = float(
                    self.last_hbm_ledger["bytes_in_use"]
                )
                if self.last_hbm_ledger["headroom_fraction"] is not None:
                    stats["hbm/headroom_fraction"] = float(
                        self.last_hbm_ledger["headroom_fraction"]
                    )
            stats["step_secs"] = time.monotonic() - t_step
            self._obs.step_seconds.observe(stats["step_secs"])
            stats["version"] = float(new_version)
            logger.info(
                f"step {global_step}: {step_timeline.format_phase_line(bd)}"
            )
            self.stats_logger.commit(epoch, step, global_step, stats)
            last_completed = StepInfo(
                epoch=epoch,
                epoch_step=step,
                global_step=global_step,
                steps_per_epoch=steps_per_epoch,
            )
            if profiling:
                trace_dir = perf_tracer.stop_device_profile()
                if trace_dir:
                    logger.info(f"device profile captured: {trace_dir}")
            perf_tracer.save(step=global_step)

    def _complete_step_timeline(self, tl, batch, n_extra_fwd: int) -> dict:
        """Close the step's phase timeline (shared helper: utilization
        inputs + HBM ledger refresh — step_timeline.complete_trainer_step)."""
        bd, ledger = step_timeline.complete_trainer_step(
            self.step_recorder,
            tl,
            self.actor_engine,
            self.config.telemetry,
            batch,
            n_extra_forwards=n_extra_fwd,
            remat=bool(
                getattr(self.config.actor, "gradient_checkpointing", False)
            ),
        )
        if ledger is not None:
            self.last_hbm_ledger = ledger
        return bd

    def _maybe_evaluate(self, eval_workflow, epoch: int, global_step: int) -> None:
        if self.valid_dataset is None or eval_workflow is None:
            return

        def run_eval():
            client = self.eval_rollout
            if client is None:
                return
            batch = client.rollout_batch(
                list(self.valid_dataset), workflow=eval_workflow
            )
            rewards = np.asarray(batch["rewards"], np.float32)
            with stats_tracker.scope("eval"):
                stats_tracker.get().scalar(
                    reward=float(rewards.mean()),
                    n_seqs=float(rewards.shape[0]),
                )

        self.evaluator.maybe_evaluate(epoch, global_step, run_eval)

    def close(self) -> None:
        try:
            # a periodic async recover dump may still be writing: join it
            # so close() means "everything durable" (preemption's emergency
            # dump already forces this)
            self.saver.wait_async()
            self.recover_handler.saver.wait_async()
        except RuntimeError:
            logger.exception("async checkpoint write failed during close")
        if self.journal is not None:
            self.journal.close()
        self._dump_lineage("close")
        if self.autopilot is not None:
            self.autopilot.stop()
        if self.preemption is not None:
            self.preemption.uninstall()
        self.stats_logger.close()
        self.rollout.destroy()
