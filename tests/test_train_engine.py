"""JaxTrainEngine integration tests on the 8-device CPU mesh (replaces the
reference's test_train_engine.py / test_fsdp_engine_nccl.py GPU tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta
from areal_tpu.engine.train_engine import JaxTrainEngine
from jax import set_mesh

from tpu_testing import TINY_QWEN2, random_batch


def _engine(mesh=None, lr=1e-2, **kw):
    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=mesh or MeshConfig(data=2, fsdp=2, seq=1, model=2),
        optimizer=OptimizerConfig(lr=lr, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=1024),
        bucket_step=64,
        **kw,
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 128, 16))
    return eng


def sft_loss(outputs, b):
    lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
    loss = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
    return loss, {"ppl_loss": jax.lax.stop_gradient(loss)}


def weight_fn(d):
    return float((np.asarray(d["loss_mask"]) > 0).sum())


@pytest.fixture(scope="module")
def engine():
    return _engine()


def test_train_batch_learns(engine):
    batch = random_batch(seed=1)
    losses = [
        engine.train_batch(batch, sft_loss, weight_fn)["ppl_loss"] for _ in range(10)
    ]
    assert losses[-1] < losses[0] - 1.5, losses
    assert all(np.isfinite(losses))


def test_train_stats_keys(engine):
    batch = random_batch(seed=2)
    stats = engine.train_batch(batch, sft_loss, weight_fn)
    for k in ("loss", "ppl_loss", "grad_norm", "lr", "n_microbatches"):
        assert k in stats, stats.keys()
    assert stats["grad_norm"] > 0


def test_forward_batch_alignment(engine):
    """forward_batch[b, t] = logp(token t | prefix) with position 0 zeroed."""
    batch = random_batch(n_seqs=4, seed=3)
    lp = engine.forward_batch(batch)
    mask = np.asarray(batch["attention_mask"])
    assert lp.shape == mask.shape
    assert np.all(lp[:, 0] == 0.0)
    assert np.all(lp[mask][1:] <= 0.0)  # logprobs are negative
    assert np.all(lp[~mask] == 0.0)


def test_forward_batch_deterministic(engine):
    batch = random_batch(n_seqs=4, seed=4)
    a = engine.forward_batch(batch)
    b = engine.forward_batch(batch)
    np.testing.assert_array_equal(a, b)


def test_eval_batch(engine):
    batch = random_batch(seed=5)
    stats = engine.eval_batch(batch, sft_loss, weight_fn)
    assert np.isfinite(stats["loss"])


def test_microbatching_invariance():
    """Accumulated gradients and total loss over small microbatches must match
    a single big batch (the packed-loss weight protocol — reference
    engine/core/train_engine.py loss-weight all-reduce). Post-optimizer params
    are NOT compared: AdamW's first step is sign-like and amplifies fp32
    noise chaotically."""
    eng = _engine(lr=1e-2)
    batch = random_batch(n_seqs=8, seed=6)

    def grads_for(max_tok):
        eng.config.mb_spec = MicroBatchSpec(max_tokens_per_mb=max_tok)
        grids = eng._make_grids(batch)
        ws = [weight_fn(g.data) for g in grids]
        tot = sum(ws)
        acc, loss_sum = None, 0.0
        with set_mesh(eng.mesh):
            for g, w in zip(grids, ws):
                b = eng._grid_to_device(g)
                gfn = eng._get_grad_fn(sft_loss, b["segment_ids"].shape)
                gr, loss, _ = gfn(eng.params, b, jnp.float32(w / tot))
                loss_sum += float(loss)
                gr = jax.tree.map(jnp.copy, gr)
                acc = gr if acc is None else jax.tree.map(jnp.add, acc, gr)
        return len(grids), loss_sum, acc

    n_a, loss_a, ga = grads_for(100_000)
    n_b, loss_b, gb = grads_for(256)
    assert n_b > n_a
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        ),
        ga,
        gb,
    )


def test_offload_onload_roundtrip(engine):
    """offload frees device params; onload restores and training continues
    with identical numerics (colocated gen+train handoff)."""
    batch = random_batch(seed=7)
    before = engine.forward_batch(batch)
    engine.offload()
    assert engine._offload_mode is not None
    engine.onload()
    assert engine._offload_mode is None
    after = engine.forward_batch(batch)
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-5)
    stats = engine.train_batch(batch, sft_loss, weight_fn)
    assert np.isfinite(stats["loss"])


def test_version_bookkeeping(engine):
    engine.set_version(7)
    assert engine.get_version() == 7
    engine.set_version(0)


def test_save_load_hf_roundtrip(tmp_path, engine):
    batch = random_batch(n_seqs=4, seed=7)
    before = engine.forward_batch(batch)
    meta = SaveLoadMeta(path=str(tmp_path / "hf"), weight_format="hf")
    engine.save(meta)
    # perturb then restore
    engine.params = jax.tree.map(lambda x: x + 0.01 if x.ndim > 0 else x, engine.params)
    perturbed = engine.forward_batch(batch)
    assert not np.allclose(before, perturbed)
    engine.load(meta)
    after = engine.forward_batch(batch)
    np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-5)


def test_value_head_engine():
    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=1, fsdp=4, seq=1, model=2),
        optimizer=OptimizerConfig(lr=1e-2),
        mb_spec=MicroBatchSpec(),
        bucket_step=64,
    )
    eng = JaxTrainEngine(cfg, value_head=True, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 64, 8))
    batch = random_batch(n_seqs=4, seed=8)

    def v_loss(outputs, b):
        lm = (b["loss_mask"] > 0).astype(jnp.float32)
        tgt = jnp.ones_like(outputs["values"])
        loss = (jnp.square(outputs["values"] - tgt) * lm).sum() / jnp.maximum(lm.sum(), 1)
        return loss, {}

    losses = [eng.train_batch(batch, v_loss, weight_fn)["loss"] for _ in range(10)]
    assert losses[-1] < losses[0], losses
    vals = eng.forward_batch(batch, output_key="values")
    assert vals.shape == np.asarray(batch["attention_mask"]).shape
