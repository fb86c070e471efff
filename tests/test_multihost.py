"""Real 2-process multi-host coverage (SURVEY §5.8 / VERDICT r04 §2.2
dist-rollout row): the engine's jax.distributed bring-up, a GSPMD train
step whose collectives cross the process boundary (Gloo on CPU — the DCN
stand-in), and DistRolloutCoordinator's broadcast + seqlen-balanced
sharding. The coordinator previously had only its single-process fast
path exercised."""

import json
import os
import subprocess
import sys

import pytest

from areal_tpu.utils.network import find_free_port


@pytest.mark.slow
def test_two_process_train_step_and_dist_rollout(tmp_path):
    port = find_free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = os.path.join(repo, "tests", "multihost_child.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    outs = [str(tmp_path / f"rank{r}.json") for r in range(2)]
    procs = [
        subprocess.Popen(
            [sys.executable, child, str(r), "2", str(port), outs[r]], env=env
        )
        for r in range(2)
    ]
    try:
        # fail fast: a rank that dies early leaves its peer blocked at a
        # distributed barrier — surface the REAL failure, don't wait it out
        import time

        deadline = time.monotonic() + 600
        while any(p.poll() is None for p in procs):
            assert time.monotonic() < deadline, "multihost children timed out"
            for r, p in enumerate(procs):
                rc = p.poll()
                assert rc is None or rc == 0, f"rank {r} exited rc={rc}"
            time.sleep(0.5)
        for r, p in enumerate(procs):
            assert p.returncode == 0, f"rank {r} exited rc={p.returncode}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = [json.load(open(o)) for o in outs]

    # identical replicated loss/grad-norm on both processes: the grads were
    # psum'd across the process boundary
    assert results[0]["nll"] == pytest.approx(results[1]["nll"], rel=1e-6)
    assert results[0]["grad_norm"] == pytest.approx(
        results[1]["grad_norm"], rel=1e-5
    )

    # the coordinator handed DISJOINT shards covering all 6 sequences,
    # seqlen-balanced (total 62 tokens -> 31/31 split for these lengths)
    uids = sorted(results[0]["shard_uids"] + results[1]["shard_uids"])
    assert uids == list(range(6))
    assert set(results[0]["shard_uids"]).isdisjoint(results[1]["shard_uids"])
    assert abs(results[0]["shard_tokens"] - results[1]["shard_tokens"]) <= 4
