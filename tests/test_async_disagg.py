"""Disaggregated async-vs-sync across a REAL process boundary (VERDICT r04
item #2 / weak #4): the ≥2x async mechanism cannot show on one chip where
decode and train serialize, so this is the CI-demonstrable form — an
inference-server SUBPROCESS whose generation cost is wall-clock latency
(tests/delay_server.py models a fleet with its own capacity), a real jax
trainer in this process, the real HTTP client + staleness-gated executor +
PPO actor + mem weight updates between them.

eta=0 serializes every step (generate -> train -> update); eta=2 lets
generation for future steps overlap training. A CPU timing of the overlap
MECHANISM, not a speed: the ratio on a chip is not measured (PERF.md section
7, `rl-async-1.5b-4chip`). Reference bar: 2.77x at fleet scale
(blog/AReaL_v0_3.md)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

GROUP = 2
PROMPTS_PER_STEP = 4
NEW_TOKENS = 64
TOKEN_DELAY = 0.006  # -> ~0.4s generation latency per request wave
N_STEPS = 4


@pytest.fixture()
def server_proc(tmp_path):
    addr_file = str(tmp_path / "addr")
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    env["PYTHONPATH"] = os.pathsep.join(
        [repo, tests, env.get("PYTHONPATH", "")]
    )
    proc = subprocess.Popen(
        [sys.executable, os.path.join(tests, "delay_server.py"), addr_file, str(TOKEN_DELAY)],
        env=env,
    )
    deadline = time.monotonic() + 60
    while not os.path.exists(addr_file):
        assert proc.poll() is None, "delay server died"
        assert time.monotonic() < deadline, "delay server never came up"
        time.sleep(0.1)
    with open(addr_file) as f:
        addr = f.read().strip()
    yield addr
    proc.terminate()
    proc.wait(timeout=10)


@pytest.mark.slow
def test_async_overlap_beats_sync_across_processes(server_proc):
    import jax

    from areal_tpu.api.config import (
        InferenceEngineConfig,
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.api.io_struct import (
        FinetuneSpec,
        GenerationHyperparameters,
        WeightUpdateMeta,
    )
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.trainer.ppo import PPOActor
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    from tpu_testing import TINY_QWEN2

    actor_cfg = PPOActorConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=1e-4, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=128,
        group_size=GROUP,
        ppo_n_minibatches=1,
        adv_norm=NormConfig(mean_level="group", std_level="group", group_size=GROUP),
        kl_ctl=0.0,
        use_decoupled_loss=True,
        prox_logp_mode="loglinear",
    )
    engine = JaxTrainEngine(actor_cfg, model_config=TINY_QWEN2)
    engine.initialize(FinetuneSpec(1, 10_000, PROMPTS_PER_STEP))
    actor = PPOActor(actor_cfg, engine)

    rng = np.random.default_rng(0)
    dataset = [
        {"prompt_ids": rng.integers(20, 200, 16).tolist()} for _ in range(128)
    ]
    gconfig = GenerationHyperparameters(
        n_samples=GROUP, max_new_tokens=NEW_TOKENS, temperature=1.0
    )
    wf = RLVRWorkflow(lambda *a, **kw: 1.0, gconfig)
    meta = WeightUpdateMeta(type="mem")

    def run_mode(eta: int, n_steps: int) -> float:
        rollout = RemoteJaxEngine(
            InferenceEngineConfig(
                max_concurrent_rollouts=4 * PROMPTS_PER_STEP,
                consumer_batch_size=PROMPTS_PER_STEP,
                max_head_offpolicyness=eta,
                request_timeout=120,
            ),
            addresses=[server_proc],
        )
        rollout.initialize()
        rollout.set_version(engine.get_version())
        engine.connect_engine(rollout, meta)
        t0 = time.monotonic()
        for _ in range(n_steps):
            batch = rollout.prepare_batch(dataset, workflow=wf)
            adv = actor.compute_advantages(batch)
            actor.ppo_update(adv)
            rollout.pause()
            engine.update_weights(meta)
            v = engine.get_version() + 1
            engine.set_version(v)
            rollout.set_version(v)
            rollout.resume()
        dt = time.monotonic() - t0
        rollout.destroy()
        return dt

    run_mode(0, 1)  # warmup: compile train fwd/bwd + logp programs
    t_sync = run_mode(0, N_STEPS)
    t_async = run_mode(2, N_STEPS)
    speedup = t_sync / t_async
    print(f"disagg async-vs-sync: sync={t_sync:.2f}s async={t_async:.2f}s "
          f"speedup={speedup:.2f}x")
    # generation latency (~0.4s/wave) overlaps training; the win is bounded
    # by max vs sum of the two phases. 1.25 is a conservative floor that
    # still proves genuine cross-process overlap (no-overlap == ~1.0)
    assert speedup > 1.25, (t_sync, t_async)
