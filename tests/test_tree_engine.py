"""Tree training wired into the train engine (VERDICT r04 missing #3;
reference areal/models/tree_attn/module_fsdp.py:1-185 + tree.py chunked
packing): TrainEngineConfig.tree_training routes train_batch through the
block-sparse trie forward; the loss zoo sees identical [B, T] outputs, so
parity with padded training is exact up to kernel numerics."""

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
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.models import qwen, tree
from areal_tpu.ops import functional as F
from areal_tpu.utils.data import pad_sequences_to_tensors
from jax import set_mesh

from tpu_testing import TINY_QWEN2

GROUP = 3


def grpo_batch(seed=0, n_groups=2, prompt_len=24, resp_max=16):
    """GRPO-shaped batch: groups share their prompt (the dedup win)."""
    rng = np.random.default_rng(seed)
    trajs = []
    for _ in range(n_groups):
        prompt = rng.integers(1, 250, prompt_len)
        for _ in range(GROUP):
            resp = rng.integers(1, 250, int(rng.integers(6, resp_max)))
            ids = np.concatenate([prompt, resp]).astype(np.int32)
            n = len(ids)
            trajs.append(
                {
                    "input_ids": ids,
                    "loss_mask": np.concatenate(
                        [np.zeros(prompt_len, np.float32), np.ones(n - prompt_len, np.float32)]
                    ),
                    "old_logprobs": rng.normal(-1.5, 0.2, n).astype(np.float32),
                    "advantages": rng.normal(0, 1, n).astype(np.float32),
                }
            )
    return pad_sequences_to_tensors(trajs)


def grpo_loss(outputs, b):
    lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
    loss, _ = F.ppo_actor_loss_fn(
        logprobs=outputs["logprobs"],
        proximal_logprobs=b["old_logprobs"],
        old_logprobs=b["old_logprobs"],
        advantages=b["advantages"],
        loss_mask=lm,
    )
    # entropy in the loss: proves the tree path's entropy gather is live
    ent = (outputs["entropy"] * lm).sum() / jnp.maximum(lm.sum(), 1.0)
    return loss - 0.0 * ent, {
        "actor_loss": jax.lax.stop_gradient(loss),
        "mean_entropy": jax.lax.stop_gradient(ent),
    }


def weight_fn(d):
    return float((np.asarray(d["loss_mask"]) > 0).sum())


def _engine(tree_training, lr=1e-3, **kw):
    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=lr, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=32,
        tree_training=tree_training,
        **kw,
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 128, 16))
    return eng


def test_pack_forest_budget_and_coverage():
    rng = np.random.default_rng(0)
    seqs = []
    for _ in range(4):  # 4 groups x 3 seqs sharing a 30-token prompt
        prompt = list(rng.integers(1, 250, 30))
        seqs += [prompt + list(rng.integers(1, 250, 10)) for _ in range(3)]
    packs = tree.pack_forest(seqs, node_budget=120, group_size=3)
    covered = [i for _, rows in packs for i in rows]
    assert covered == list(range(len(seqs)))  # order-preserving, exact
    for pack, rows in packs:
        assert len(rows) % 3 == 0, "groups must stay whole"
        assert pack.n_nodes <= 120 or len(rows) == 3  # oversized lone group
        # every sequence's path spells its tokens
        for local, r in enumerate(rows):
            assert list(pack.tokens[pack.seq_nodes[local]]) == list(seqs[r])
    # dedup actually happened: a group of 3 sharing 30 of ~40 tokens
    total = sum(len(s) for s in seqs)
    nodes = sum(p.n_nodes for p, _ in packs)
    assert nodes < total * 0.75


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_tree_outputs_match_per_sequence_forward():
    """The engine's tree outputs (logprobs+entropy, label-aligned [B, T])
    must equal a flat per-sequence forward — the loss zoo then guarantees
    end-to-end parity with padded training."""
    eng = _engine(tree_training=True)
    batch = grpo_batch()
    batches, stats = eng._make_tree_batches(batch)
    assert stats["tree_dedup_ratio"] > 1.3
    params = eng.params
    with set_mesh(eng.mesh):
        for host in batches:
            dev = eng._tree_batch_to_device(host)
            out = jax.jit(eng._tree_outputs_fn)(params, dev)
            logp = np.asarray(out["logprobs"])
            ent = np.asarray(out["entropy"])
            valid = np.asarray(host["label_valid"])
            ids_rows = np.asarray(host["input_ids"])
            for i in range(ids_rows.shape[0]):
                n = int(valid[i].sum()) + 1
                ids = ids_rows[i, :n][None]
                hidden = qwen.forward(
                    params,
                    TINY_QWEN2,
                    jnp.asarray(ids),
                    jnp.ones_like(jnp.asarray(ids)),
                    jnp.arange(n, dtype=jnp.int32)[None],
                )
                labels = np.concatenate([ids[0, 1:], [0]]).astype(np.int32)
                ref_logp, ref_ent = qwen.chunked_logprobs_entropy(
                    params, TINY_QWEN2, hidden, jnp.asarray(labels)[None]
                )
                np.testing.assert_allclose(
                    logp[i, : n - 1], np.asarray(ref_logp)[0, : n - 1],
                    rtol=2e-3, atol=2e-4,
                )
                np.testing.assert_allclose(
                    ent[i, : n - 1], np.asarray(ref_ent)[0, : n - 1],
                    rtol=2e-3, atol=2e-3,
                )


def test_train_batch_tree_matches_packed_loss():
    """One PPO step through the tree path vs the packed-grid path from the
    same init: identical loss (the training-equivalence bar the reference
    sets for its engine patches, models/tree_attn/module_fsdp.py)."""
    batch = grpo_batch(seed=3)
    eng_packed = _engine(tree_training=False)
    eng_tree = _engine(tree_training=True)
    stat_p = eng_packed.train_batch(batch, grpo_loss, weight_fn)
    stat_t = eng_tree.train_batch(batch, grpo_loss, weight_fn)
    assert stat_t["tree_dedup_ratio"] > 1.3
    assert np.isfinite(stat_t["loss"])
    np.testing.assert_allclose(stat_t["loss"], stat_p["loss"], rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        stat_t["actor_loss"], stat_p["actor_loss"], rtol=2e-3, atol=2e-4
    )
    np.testing.assert_allclose(
        stat_t["mean_entropy"], stat_p["mean_entropy"], rtol=2e-3, atol=2e-3
    )
    # gradients flowed: the two engines' params moved to ~the same place
    for k in ("embed",):
        a = np.asarray(eng_tree.params[k], np.float32)
        b = np.asarray(eng_packed.params[k], np.float32)
        np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-4)


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_train_batch_tree_multi_pack_accumulates():
    """A node budget smaller than the batch forces >1 forest microbatch —
    the grad-accumulation path — and training still learns."""
    batch = grpo_batch(seed=4, n_groups=4)
    eng = _engine(tree_training=True, tree_node_budget=192, tree_node_bucket=128)
    stats = eng.train_batch(batch, grpo_loss, weight_fn)
    assert stats["n_microbatches"] >= 2
    assert np.isfinite(stats["loss"])
    assert eng._opt_step_count() == 1


def test_ppo_actor_trains_through_tree_path():
    """Config-reachable end-to-end: a PPOActor whose engine config sets
    tree_training drives advantages + ppo_update THROUGH the tree kernel
    and reports the node-dedup ratio (the preset gsm8k_grpo_tree.yaml
    contract; reference docs/en/reference/tree_training.md)."""
    from areal_tpu.api.config import NormConfig, PPOActorConfig
    from areal_tpu.trainer.ppo import PPOActor

    cfg = PPOActorConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=32,
        tree_training=True,
        group_size=GROUP,
        ppo_n_minibatches=1,
        adv_norm=NormConfig(mean_level="group", std_level="group", group_size=GROUP),
        use_decoupled_loss=True,
        prox_logp_mode="loglinear",
        kl_ctl=0.0,
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 64, 4))
    actor = PPOActor(cfg, eng)

    rng = np.random.default_rng(7)
    n, L, P = 2 * GROUP, 28, 12
    ids = np.zeros((n, L), np.int32)
    for g in range(2):  # GRPO groups share their prompt
        prompt = rng.integers(1, 250, P)
        for j in range(GROUP):
            ids[g * GROUP + j, :P] = prompt
            ids[g * GROUP + j, P:] = rng.integers(1, 250, L - P)
    lm = np.zeros((n, L), np.float32)
    lm[:, P:] = 1.0
    batch = {
        "input_ids": ids,
        "attention_mask": np.ones((n, L), bool),
        "loss_mask": lm,
        "logprobs": rng.normal(-1.5, 0.2, (n, L)).astype(np.float32),
        "versions": np.zeros((n, L), np.int32),
        "rewards": rng.normal(0.5, 1.0, (n,)).astype(np.float32),
        "seq_no_eos_mask": np.zeros((n,), bool),
    }
    adv = actor.compute_advantages(batch)
    stats = actor.ppo_update(adv)
    assert np.isfinite(stats[0]["loss"])
    assert stats[0]["tree_dedup_ratio"] > 1.2


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_tree_training_moe():
    """MoE models train through the tree path: the router aux rides the
    forest forward (load balance over unique nodes) and the policy loss
    matches the packed path (aux statistics differ by design — unique
    nodes vs duplicated tokens — so only the pg loss is compared)."""
    moe_cfg = qwen.ModelConfig(
        vocab_size=256,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        tie_word_embeddings=True,
        attention_bias=False,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=48,
        capacity_factor=2.0,
    )

    def moe_loss(outputs, b):
        lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
        pg = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
        loss = pg + 0.01 * outputs["moe_aux"]  # aux must EXIST on both paths
        return loss, {
            "pg": jax.lax.stop_gradient(pg),
            "aux": jax.lax.stop_gradient(outputs["moe_aux"]),
        }

    batch = grpo_batch(seed=6)

    def make(tree):
        from areal_tpu.api.config import TrainEngineConfig
        from areal_tpu.parallel import mesh as mesh_lib

        cfg = TrainEngineConfig(
            init_from_scratch=True,
            dtype="float32",
            param_dtype="float32",
            mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
            optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
            mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
            bucket_step=32,
            tree_training=tree,
        )
        eng = JaxTrainEngine(cfg, model_config=moe_cfg)
        # ONE device deliberately: back-to-back 8-virtual-device fused MoE
        # programs (gmm interpret callbacks inside shard_map) can wedge
        # XLA:CPU's collective rendezvous on this 1-core box — an artifact
        # of the CPU test harness, not the product (real TPU collectives
        # don't rendezvous through host threads). 8-device MoE coverage
        # lives in tests/test_moe.py; the forest's unshardable-[1, N, D]
        # fallback is covered by test_forest_moe_fallback_under_mesh.
        mesh1 = mesh_lib.make_mesh(cfg.mesh, devices=jax.devices()[:1])
        eng.initialize(FinetuneSpec(1, 128, 16), mesh=mesh1)
        return eng

    s_packed = make(False).train_batch(batch, moe_loss, weight_fn)
    s_tree = make(True).train_batch(batch, moe_loss, weight_fn)
    np.testing.assert_allclose(s_tree["pg"], s_packed["pg"], rtol=2e-3, atol=2e-4)
    assert np.isfinite(s_tree["aux"]) and s_tree["aux"] > 0
    assert s_tree["tree_dedup_ratio"] > 1.3


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_forest_moe_fallback_under_mesh():
    """The forest's [1, Npad, D] token layout can't shard over data axes as
    given; moe_ffn must reshape it to a shardable layout (or run replicated
    with a loud log) instead of a shard_map divisibility error — grad
    through remat on the full 8-device mesh."""
    from areal_tpu.api.config import MeshConfig
    from areal_tpu.ops.tree_attention import BLOCK, forest_hidden, pack_ancestor_bits
    from areal_tpu.parallel import mesh as mesh_lib

    cfg = qwen.ModelConfig(
        vocab_size=256,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        tie_word_embeddings=True,
        attention_bias=False,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=48,
        capacity_factor=2.0,
        remat=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    pack = tree.build_tree([list(rng.integers(1, 250, 20)) for _ in range(3)])
    n_pad = -(-pack.n_nodes // BLOCK) * BLOCK
    words, block_any = pack_ancestor_bits(pack.parent, n_pad)
    ids = np.zeros(n_pad, np.int32)
    ids[: pack.n_nodes] = pack.tokens
    pos = np.zeros(n_pad, np.int32)
    pos[: pack.n_nodes] = pack.depth

    def loss(p):
        h, aux = forest_hidden(
            p, cfg, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(words), jnp.asarray(block_any), with_aux=True,
        )
        return (h.astype(jnp.float32) ** 2).mean() + 0.01 * aux

    mesh = mesh_lib.make_mesh(MeshConfig(data=-1, fsdp=1, seq=1, model=1))
    with set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(params)
    assert np.isfinite(float(jax.tree.leaves(g)[0].sum()))


def test_tree_sft_learns():
    """Optimization sanity: repeated tree-path steps reduce NLL."""
    batch = grpo_batch(seed=5)

    def sft_loss(outputs, b):
        lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
        loss = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
        return loss, {"nll": jax.lax.stop_gradient(loss)}

    eng = _engine(tree_training=True, lr=1e-2)
    losses = [eng.train_batch(batch, sft_loss, weight_fn)["nll"] for _ in range(8)]
    assert losses[-1] < losses[0] - 1.0, losses
