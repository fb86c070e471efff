"""One rank of the 2-process multi-host test (tests/test_multihost.py).

Covers the REAL multi-host path on CPU: JaxTrainEngine.initialize with
``distributed`` kwargs (jax.distributed.initialize + a mesh spanning both
processes' devices), one GSPMD train step whose collectives cross the
process boundary, and DistRolloutCoordinator's host-0 pull + broadcast +
seqlen-balanced shard (infra/dist_rollout.py — previously only covered by
its single-process fast path).

Usage: python multihost_child.py RANK NPROC COORD_PORT OUT_JSON
(the parent pins JAX_PLATFORMS=cpu and the virtual device count in the
child's environment, before jax is imported)
"""

import json
import sys


def main():
    rank, nproc, port, out_path = (
        int(sys.argv[1]),
        int(sys.argv[2]),
        sys.argv[3],
        sys.argv[4],
    )
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

    import numpy as np

    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.models import qwen

    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        attn_impl="xla",
        gradient_checkpointing=False,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=32,
    )
    mcfg = qwen.ModelConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        tie_word_embeddings=True,
    )
    eng = JaxTrainEngine(cfg, model_config=mcfg)
    # the engine performs jax.distributed.initialize itself — the path
    # TrainController uses for multi-host worker meshes
    eng.initialize(
        FinetuneSpec(1, 32, 4),
        distributed={
            "coordinator_address": f"localhost:{port}",
            "num_processes": nproc,
            "process_id": rank,
        },
    )
    import jax
    import jax.numpy as jnp

    assert jax.process_count() == nproc
    assert jax.device_count() == nproc * jax.local_device_count()
    assert eng.mesh.shape["data"] == jax.device_count()

    rng = np.random.default_rng(0)  # SAME batch on every process
    B, L = 8, 24
    ids = rng.integers(1, 120, (B, L)).astype(np.int32)
    batch = {
        "input_ids": ids,
        "attention_mask": np.ones((B, L), bool),
        "loss_mask": np.ones((B, L), np.float32),
    }

    def sft_loss(outputs, b):
        lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
        loss = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
        return loss, {"nll": jax.lax.stop_gradient(loss)}

    stats = eng.train_batch(
        batch, sft_loss, lambda d: float(np.asarray(d["loss_mask"]).sum())
    )

    # DistRolloutCoordinator: host 0 pulls, everyone gets a balanced shard
    from areal_tpu.infra.dist_rollout import DistRolloutCoordinator

    class Host0Engine:
        def rollout_batch(self, data, workflow=None, **kw):
            assert jax.process_index() == 0, "only host 0 may consume"
            r = np.random.default_rng(7)
            lens = [5, 9, 13, 17, 11, 7]
            n, T = len(lens), max(lens)
            am = np.zeros((n, T), bool)
            for i, l in enumerate(lens):
                am[i, :l] = True
            return {
                "seq_uid": np.arange(n, dtype=np.int32),
                "input_ids": r.integers(1, 120, (n, T)).astype(np.int32),
                "attention_mask": am,
                "rewards": r.normal(0, 1, n).astype(np.float32),
            }

    coord = DistRolloutCoordinator(Host0Engine())
    shard = coord.rollout_batch([])
    with open(out_path, "w") as f:
        json.dump(
            {
                "rank": rank,
                "nll": float(stats["nll"]),
                "grad_norm": float(stats["grad_norm"]),
                "shard_uids": np.asarray(shard["seq_uid"]).tolist(),
                "shard_tokens": int(np.asarray(shard["attention_mask"]).sum()),
            },
            f,
        )
    print(f"rank {rank} done", flush=True)


if __name__ == "__main__":
    main()
