"""Trainer goodput observatory (docs/observability.md "Trainer
observatory"): step-phase breakdown identity on both trainers, bubble
attribution under a slow rollout, the HBM ledger with its analytic CPU
fallback, XLA compile counters, and the on-demand device-profile endpoint
+ postmortem linking."""

import json
import os
import time

import numpy as np
import pytest

from areal_tpu.api.config import (
    DatasetConfig,
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PPOActorConfig,
    PPOConfig,
    RecoverConfig,
    SaverConfig,
    SFTConfig,
    StatsLoggerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.observability import hw_accounting, step_timeline
from areal_tpu.observability.step_timeline import PHASES

from tpu_testing import TINY_QWEN2


def _identity_ok(bd: dict) -> bool:
    named = sum(bd[f"{p}_s"] for p in PHASES)
    return abs(named + bd["other_s"] - bd["total_s"]) < 1e-9


# ---------------------------------------------------------------------------
# unit: the breakdown contract
# ---------------------------------------------------------------------------


def test_breakdown_identity_and_bubble_unit():
    rec = step_timeline.StepTimelineRecorder()
    tl = rec.start(3)
    with tl.phase("rollout_wait"):
        time.sleep(0.05)
    with step_timeline.engine_phase("forward_backward"):
        time.sleep(0.01)
    bd = rec.complete(tl, tokens=500, flops=1e9, peak_flops_per_chip=1e12)
    assert _identity_ok(bd)
    assert bd["rollout_wait_s"] >= 0.05
    assert bd["forward_backward_s"] >= 0.01
    assert 0.0 < bd["bubble_fraction"] < 1.0
    assert bd["tok_s_per_chip"] > 0 and 0 < bd["mfu"] <= 1.0
    # mfu_step <= mfu: the compute window is a subset of the step
    assert bd["mfu_step"] <= bd["mfu"] + 1e-12
    assert rec.recent()[-1]["step"] == 3


def test_engine_phase_is_noop_without_open_step():
    # no current timeline (standalone engine use): must not raise or record
    with step_timeline.engine_phase("forward_backward"):
        pass
    assert step_timeline.current_step_timeline() is None


def test_engine_phase_suppressed_inside_explicit_phase():
    """Eval forwards inside ckpt_eval must not ALSO land in
    forward_backward: double-attribution would push the named sum past the
    wall clock and silently break the identity."""
    rec = step_timeline.StepTimelineRecorder()
    tl = rec.start(0)
    with tl.phase("ckpt_eval"):
        with step_timeline.engine_phase("forward_backward"):
            time.sleep(0.02)
    bd = rec.complete(tl)
    assert _identity_ok(bd)
    assert bd["ckpt_eval_s"] >= 0.02
    assert bd["forward_backward_s"] == 0.0


def test_abandon_clears_current_without_observing():
    rec = step_timeline.StepTimelineRecorder()
    tl = rec.start(0)
    assert step_timeline.current_step_timeline() is tl
    rec.abandon(tl)
    assert step_timeline.current_step_timeline() is None
    assert rec.recent() == []


def test_format_phase_line_and_stat_keys():
    rec = step_timeline.StepTimelineRecorder()
    tl = rec.start(0)
    tl.add("rollout_wait", 1.0)
    tl.add("forward_backward", 0.5)
    bd = rec.complete(tl)
    line = step_timeline.format_phase_line(bd)
    assert "rollout_wait" in line and "bubble" in line
    keys = step_timeline.breakdown_stat_keys(bd)
    assert keys["phase/rollout_wait_s"] == bd["rollout_wait_s"]
    assert keys["bubble_fraction"] == bd["bubble_fraction"]


# ---------------------------------------------------------------------------
# RL trainer: identity + bubble attribution under a slow rollout
# ---------------------------------------------------------------------------


def _rl_batch(n=4, seed=0, L=24, reward=1.0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 250, (n, L)).astype(np.int32)
    lm = np.zeros((n, L), np.float32)
    lm[:, 4:] = 1.0
    return {
        "input_ids": ids,
        "attention_mask": np.ones((n, L), bool),
        "loss_mask": lm,
        "logprobs": rng.normal(-1.5, 0.2, (n, L)).astype(np.float32),
        "versions": np.zeros((n, L), np.int32),
        "rewards": np.full((n,), reward, np.float32),
        "seq_no_eos_mask": np.zeros((n,), bool),
    }


class _SlowFakeRollout:
    """Minimal InferenceEngine surface for PPOTrainer with a deliberately
    slow prepare_batch — the throttled rollout whose wait must land in the
    rollout_wait phase (the async bubble), not in other_s."""

    def __init__(self, wait_s: float):
        self.wait_s = wait_s
        self.version = 0

    def prepare_batch(self, dataloader, workflow=None, should_accept_fn=None):
        time.sleep(self.wait_s)
        return _rl_batch(seed=self.version)

    def update_weights(self, meta, params=None):
        pass

    def pause(self):
        pass

    def resume(self):
        pass

    def set_version(self, v):
        self.version = v

    def get_version(self):
        return self.version

    def export_stats(self):
        return {}

    def destroy(self):
        pass


@pytest.fixture()
def rl_trainer(tmp_path):
    from areal_tpu.trainer.rl_trainer import PPOTrainer

    actor_cfg = PPOActorConfig(
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
        kl_ctl=0.0,
        use_decoupled_loss=False,
        recompute_logprob=False,
    )
    engine = JaxTrainEngine(actor_cfg, model_config=TINY_QWEN2)
    engine.initialize(FinetuneSpec(1, 8, 4))
    cfg = PPOConfig(
        experiment_name="obs",
        trial_name="t0",
        total_train_epochs=1,
        total_train_steps=2,
        weight_update_mode="mem",
        train_dataset=DatasetConfig(batch_size=4),
        actor=actor_cfg,
        saver=SaverConfig(fileroot=str(tmp_path)),
        checkpointer=SaverConfig(fileroot=str(tmp_path)),
        recover=RecoverConfig(mode="disabled", fileroot=str(tmp_path)),
        stats_logger=StatsLoggerConfig(fileroot=str(tmp_path)),
    )
    cfg.cluster.fileroot = str(tmp_path)
    # unknown-chip override: CPU has no peak spec, the config knob is the
    # documented way to still get an MFU number
    cfg.telemetry.chip_peak_tflops = 0.05
    cfg.telemetry.chip_hbm_gb = 4.0
    trainer = PPOTrainer(
        cfg,
        [{"prompt_ids": [3, 5, 7]} for _ in range(8)],
        rollout=_SlowFakeRollout(wait_s=0.08),
        actor_engine=engine,
    )
    yield trainer
    trainer.close()


def test_rl_trainer_phase_breakdown(rl_trainer):
    rl_trainer.train()
    recent = rl_trainer.step_recorder.recent()
    assert len(recent) == 2
    for rec in recent:
        bd = rec["breakdown"]
        assert _identity_ok(bd), bd
        # the slow rollout is attributed, not hidden in other_s
        assert bd["rollout_wait_s"] >= 0.07, bd
        assert bd["bubble_fraction"] > 0.0
        # engine spans landed through the thread-local hook
        assert bd["forward_backward_s"] > 0.0, bd
        assert bd["host_prep_s"] > 0.0, bd
        # utilization riders (peak comes from the config override on CPU)
        assert "mfu" in bd and "tok_s_per_chip" in bd
    # HBM ledger refreshed with the analytic CPU fallback + override limit
    ledger = rl_trainer.last_hbm_ledger
    assert ledger is not None and ledger["source"] == "analytic"
    assert ledger["components"]["params"] > 0
    assert ledger["components"]["opt_state"] > 0
    assert ledger["bytes_limit"] == int(4.0 * 1e9)
    assert 0.0 < ledger["headroom_fraction"] < 1.0


def test_rl_trainer_stats_carry_compat_and_phase_keys(rl_trainer, tmp_path):
    committed = []
    rl_trainer.stats_logger.commit = (
        lambda epoch, step, gstep, stats: committed.append(stats)
    )
    rl_trainer.train()
    stats = committed[-1]
    # backward-compatible timing keys survive the record_timing removal
    for k in (
        "timing/rollout",
        "timing/train_step",
        "timing/update_weights",
        "timing/save",
        "timing/eval",
    ):
        assert k in stats, sorted(stats)
    # the new phase vocabulary rides the same per-step stats surface
    for p in PHASES:
        assert f"phase/{p}_s" in stats
    assert stats["timing/rollout"] == stats["phase/rollout_wait_s"]
    assert "bubble_fraction" in stats and "phase/other_s" in stats


# ---------------------------------------------------------------------------
# SFT trainer: same contract, no bubble
# ---------------------------------------------------------------------------


def test_sft_trainer_phase_breakdown(tmp_path):
    from areal_tpu.trainer.sft_trainer import SFTTrainer

    rng = np.random.default_rng(0)
    rows = []
    for _ in range(8):
        ids = rng.integers(1, 250, 10).astype(np.int32)
        rows.append(
            {
                "input_ids": ids.tolist(),
                "loss_mask": np.ones(10, np.float32).tolist(),
            }
        )
    cfg = SFTConfig(
        experiment_name="sft-obs",
        trial_name="t0",
        total_train_epochs=1,
        model=TrainEngineConfig(
            init_from_scratch=True,
            dtype="float32",
            param_dtype="float32",
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            optimizer=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant"),
            mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
            bucket_step=64,
        ),
        train_dataset=DatasetConfig(batch_size=4),
        saver=SaverConfig(fileroot=str(tmp_path)),
        checkpointer=SaverConfig(fileroot=str(tmp_path)),
        recover=RecoverConfig(mode="disabled", fileroot=str(tmp_path)),
        stats_logger=StatsLoggerConfig(fileroot=str(tmp_path)),
    )
    cfg.cluster.fileroot = str(tmp_path)
    engine = JaxTrainEngine(cfg.model, model_config=TINY_QWEN2)
    engine.initialize(FinetuneSpec(1, 8, 4))
    tr = SFTTrainer(cfg, rows, engine=engine)
    tr.train()
    recent = tr.step_recorder.recent()
    assert len(recent) == 2
    for rec in recent:
        bd = rec["breakdown"]
        assert _identity_ok(bd), bd
        assert bd["rollout_wait_s"] == 0.0  # SFT has no async bubble
        assert bd["bubble_fraction"] == 0.0
        assert bd["forward_backward_s"] > 0.0
    assert tr.last_hbm_ledger is not None
    assert tr.last_hbm_ledger["components"]["params"] > 0
    tr.close()


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def test_hbm_ledger_analytic_cpu_fallback():
    ledger = hw_accounting.build_hbm_ledger(
        {"params": 1000, "opt_state": 2000, "radix_cache": 500},
        exclude_from_total=("radix_cache",),
    )
    # radix pages live INSIDE the kv pool: a view, never double counted
    assert ledger["itemized_bytes"] == 3000
    assert ledger["source"] == "analytic"
    assert ledger["bytes_in_use"] == 3000
    assert ledger["bytes_limit"] is None  # CPU, no override: no fabrication
    led2 = hw_accounting.build_hbm_ledger(
        {"params": int(2e8)}, override_hbm_gb=1.0
    )
    assert led2["bytes_limit"] == int(1e9)
    assert led2["headroom_fraction"] == pytest.approx(0.8)


def test_hbm_ledger_decode_engine():
    import jax

    from areal_tpu.api.config import ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.models import qwen

    tiny = qwen.ModelConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        dtype="float32",
        tie_word_embeddings=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    eng = DecodeEngine(
        ServerConfig(
            max_batch_size=2,
            max_seq_len=64,
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        ),
        params=params,
        model_cfg=tiny,
    )
    eng.initialize()
    ledger = eng.hbm_ledger()
    comp = ledger["components"]
    assert comp["params"] > 0 and comp["kv_page_pool"] > 0
    assert comp["staged_update"] == 0
    # the radix view is reported but excluded from the itemized total
    assert ledger["itemized_bytes"] == (
        comp["params"] + comp["kv_page_pool"] + comp["staged_update"]
    )


def test_train_step_flops_formula():
    counts = hw_accounting.transformer_param_counts(TINY_QWEN2)
    assert counts["matmul"] > 0 and counts["total"] > counts["embedding"]
    base = hw_accounting.train_step_flops(TINY_QWEN2, 100)
    assert base == 6 * counts["matmul"] * 100
    # remat adds one recomputed forward, each extra fwd pass adds 2M
    assert hw_accounting.train_step_flops(TINY_QWEN2, 100, remat=True) == (
        8 * counts["matmul"] * 100
    )
    assert hw_accounting.train_step_flops(
        TINY_QWEN2, 100, n_extra_forwards=2
    ) == (10 * counts["matmul"] * 100)


def test_chip_peak_override_wins():
    assert hw_accounting.chip_peak_flops(override_tflops=123.0) == 123e12
    # CPU device_kind is unknown to the TPU table: no fabricated peak
    assert hw_accounting.chip_peak_flops() is None


# ---------------------------------------------------------------------------
# compile counters
# ---------------------------------------------------------------------------


def test_compile_counters_increment_on_forced_recompile():
    import jax
    import jax.numpy as jnp

    from areal_tpu.utils import compile_cache

    assert compile_cache.install_compile_counters()
    before = compile_cache.compile_stats()

    @jax.jit
    def f(x):
        return (x * 2 + 1).sum()

    f(jnp.ones(11))
    mid = compile_cache.compile_stats()
    assert mid["compiles"] >= before["compiles"] + 1
    # forced recompile: a NEW operand shape retraces + recompiles the same
    # jitted function — exactly the storm the counter exists to expose
    f(jnp.ones(13))
    after = compile_cache.compile_stats()
    assert after["compiles"] >= mid["compiles"] + 1
    assert after["compile_seconds"] > before["compile_seconds"]


# ---------------------------------------------------------------------------
# on-demand device profiling + postmortem linking
# ---------------------------------------------------------------------------


def test_debug_profile_endpoint_and_postmortem_links(tmp_path, monkeypatch):
    import urllib.request

    import jax

    from areal_tpu.api.config import ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.tools import postmortem
    from areal_tpu.utils import perf_tracer

    tiny = qwen.ModelConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        dtype="float32",
        tie_word_embeddings=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=64,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
    eng.initialize()
    srv = ServerThread(cfg, eng)
    srv.start()
    # route captures into the test's tmp dir
    monkeypatch.setattr(
        perf_tracer,
        "default_profile_root",
        lambda output_dir=None: str(tmp_path / "xprof"),
    )
    try:
        req = urllib.request.Request(
            f"http://{srv.address}/debug/profile?duration_s=0.3",
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = json.loads(r.read().decode())
        assert body["status"] == "profiling"
        trace_dir = body["trace_dir"]
        # a second start while active must 409 with the active dir
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("concurrent profile start did not 409")
        except urllib.error.HTTPError as e:
            assert e.code == 409
        # wait for the background stop to land the xplane files
        deadline = time.monotonic() + 20
        files = []
        while time.monotonic() < deadline:
            if perf_tracer.device_profile_active() is None:
                files = [
                    os.path.join(root, f)
                    for root, _d, fs in os.walk(trace_dir)
                    for f in fs
                ]
                if files:
                    break
            time.sleep(0.05)
        assert files, f"no profile files under {trace_dir}"
        assert any(f.endswith(".xplane.pb") for f in files)

        # postmortem links the capture next to the merged Perfetto trace
        from areal_tpu.observability.timeline import FlightRecorder

        fr = FlightRecorder(role="inference_server")
        fr.record("wedge", severity="warn")
        dump = tmp_path / "flight_dump.json"
        fr.dump(str(dump), "test")
        out = tmp_path / "incident.json"
        rc = postmortem.main(
            [
                "--files",
                str(dump),
                "--profile-dirs",
                str(tmp_path / "xprof"),
                "-o",
                str(out),
            ]
        )
        assert rc == 0
        merged = json.loads(out.read_text())
        profiles = merged["metadata"]["device_profiles"]
        assert profiles, "postmortem linked no device profiles"
        assert any(
            os.path.abspath(p) == os.path.abspath(trace_dir) for p in profiles
        ), (profiles, trace_dir)
    finally:
        srv.stop()


def test_profile_for_stops_itself(tmp_path, monkeypatch):
    from areal_tpu.utils import perf_tracer

    monkeypatch.setattr(
        perf_tracer,
        "default_profile_root",
        lambda output_dir=None: str(tmp_path / "xprof2"),
    )
    d = perf_tracer.profile_for(0.1)
    assert perf_tracer.device_profile_active() == d
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if perf_tracer.device_profile_active() is None:
            break
        time.sleep(0.02)
    assert perf_tracer.device_profile_active() is None
    # idempotent stop: nothing active returns None
    assert perf_tracer.stop_device_profile() is None


def test_stale_profile_timer_cannot_stop_newer_capture(tmp_path, monkeypatch):
    """An early-stopped capture's background timer must not truncate a
    NEWER capture that reused the active slot (stop is dir-guarded)."""
    from areal_tpu.utils import perf_tracer

    monkeypatch.setattr(
        perf_tracer,
        "default_profile_root",
        lambda output_dir=None: str(tmp_path / "xprof3"),
    )
    d1 = perf_tracer.profile_for(0.15)
    assert perf_tracer.stop_device_profile() == d1  # operator stops early
    d2 = perf_tracer.start_device_profile()
    assert d2 != d1
    # d1's timer fires at ~0.15s: it must leave d2 running
    time.sleep(0.4)
    assert perf_tracer.device_profile_active() == d2
    assert perf_tracer.stop_device_profile() == d2
