"""Tree training phase 1 (reference models/tree_attn + test_tree_training.py):
trie packing, ancestor masks, and exact logprob parity between the packed
tree forward and per-sequence forwards on shared-prefix batches."""

import jax
import numpy as np
import pytest

from areal_tpu.models import qwen, tree

from tpu_testing import TINY_QWEN2


def test_build_tree_dedups_prefixes():
    seqs = [[1, 2, 3, 4], [1, 2, 3, 5], [1, 2, 6]]
    pack = tree.build_tree(seqs)
    # shared prefix [1,2] stored once; [3] shared by two; total unique nodes:
    # 1,2,3,4,5,6 -> 6 vs 11 raw tokens
    assert pack.n_nodes == 6
    assert sum(len(s) for s in seqs) == 11
    # parent-before-child topological order
    assert all(pack.parent[i] < i for i in range(pack.n_nodes))
    # every sequence's path spells its tokens
    for seq, nodes in zip(seqs, pack.seq_nodes):
        assert list(pack.tokens[nodes]) == seq
    # depth = rope position along the path
    for nodes in pack.seq_nodes:
        assert list(pack.depth[nodes]) == list(range(len(nodes)))


def test_ancestor_mask():
    pack = tree.build_tree([[7, 8, 9], [7, 8, 10]])
    m = pack.ancestor_mask()
    n9, n10 = pack.seq_nodes[0][-1], pack.seq_nodes[1][-1]
    # leaves see their own path, not each other
    assert m[n9, n10] == False and m[n10, n9] == False  # noqa: E712
    assert m[n9].sum() == 3 and m[n10].sum() == 3
    # root sees only itself
    root = pack.seq_nodes[0][0]
    assert m[root].sum() == 1


def test_aggregate_sum_and_scatter():
    seqs = [[1, 2, 3], [1, 2, 4]]
    pack = tree.build_tree(seqs)
    adv = [np.asarray([0.5, 1.0, 2.0]), np.asarray([0.25, 0.75, 3.0])]
    agg = pack.aggregate(adv, reduce="sum")
    # shared nodes accumulate both sequences' values
    n1 = pack.seq_nodes[0][0]
    n2 = pack.seq_nodes[0][1]
    assert agg[n1] == pytest.approx(0.75)
    assert agg[n2] == pytest.approx(1.75)
    assert pack.traversal_count()[n1] == 2
    back = pack.scatter_to_sequences(agg)
    assert back[0][2] == pytest.approx(2.0)
    assert back[1][2] == pytest.approx(3.0)


def test_tree_forward_matches_per_sequence():
    """The core phase-1 guarantee: packed-tree logprobs == per-sequence
    forward logprobs on shared-prefix batches (reference
    test_tree_training.py role)."""
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 256, 6).tolist()
    seqs = [
        prefix + rng.integers(0, 256, 4).tolist(),
        prefix + rng.integers(0, 256, 3).tolist(),
        prefix[:3] + rng.integers(0, 256, 5).tolist(),
    ]
    pack = tree.build_tree(seqs)
    assert pack.n_nodes < sum(len(s) for s in seqs)

    node_logp = np.asarray(tree.tree_forward_logprobs(params, TINY_QWEN2, pack))
    per_seq_logp = pack.scatter_to_sequences(node_logp)

    for seq, got in zip(seqs, per_seq_logp):
        a = np.asarray(seq, np.int32)[None]
        segs = np.ones_like(a)
        pos = np.arange(len(seq), dtype=np.int32)[None]
        hidden = qwen.forward(params, TINY_QWEN2, a, segs, pos)
        logits = np.asarray(qwen.compute_logits(params, TINY_QWEN2, hidden))[0]
        ref_logp = jax.nn.log_softmax(logits, axis=-1)
        # token t>0: log p(seq[t] | seq[:t]) from the flat causal forward
        want = np.asarray(
            [ref_logp[t - 1, seq[t]] for t in range(1, len(seq))]
        )
        np.testing.assert_allclose(got[1:], want, rtol=2e-4, atol=2e-4)


# -- phase 2: Pallas block-sparse ancestor-bitmask kernel -------------------


def test_pack_ancestor_bits():
    import numpy as np

    from areal_tpu.models.tree import build_tree
    from areal_tpu.ops.tree_attention import BLOCK, BLOCK_K, pack_ancestor_bits

    pack = build_tree([[1, 2, 3], [1, 2, 4], [5, 6]])
    words, block_any = pack_ancestor_bits(pack.parent)
    # packed along the QUERY axis: bit i % 32 of words[i // 32, j]
    assert words.shape == (BLOCK // 32, BLOCK)
    mask = pack.ancestor_mask()
    for i in range(pack.n_nodes):
        for j in range(pack.n_nodes):
            bit = (int(words[i // 32, j]) >> (i % 32)) & 1
            assert bool(bit) == bool(mask[i, j]), (i, j)
    # padded rows and columns carry no bits
    dense = (words[:, None, :] >> np.arange(32, dtype=np.uint32)[None, :, None]) & 1
    dense = dense.reshape(BLOCK, BLOCK)
    assert dense[pack.n_nodes :].sum() == 0 and dense[:, pack.n_nodes :].sum() == 0
    assert block_any.shape == (1, BLOCK // BLOCK_K)
    assert block_any[0, 0] == 1 and block_any[0, 1:].sum() == 0


def test_tree_attention_kernel_matches_dense():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from areal_tpu.models.tree import build_tree
    from areal_tpu.ops.tree_attention import (
        BLOCK,
        pack_ancestor_bits,
        tree_attention,
    )

    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(1, 50, rng.integers(20, 60))) for _ in range(8)]
    # force shared prefixes
    for i in range(4, 8):
        seqs[i] = seqs[i - 4][:15] + seqs[i]
    pack = build_tree(seqs)
    N = pack.n_nodes
    n_pad = -(-N // BLOCK) * BLOCK
    H, d = 4, 128
    q = rng.normal(0, 1, (n_pad, H, d)).astype(np.float32)
    k = rng.normal(0, 1, (n_pad, H, d)).astype(np.float32)
    v = rng.normal(0, 1, (n_pad, H, d)).astype(np.float32)
    words, block_any = pack_ancestor_bits(pack.parent, n_pad)
    out = np.asarray(
        tree_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(words), jnp.asarray(block_any),
        )
    )
    # dense reference
    mask = np.zeros((n_pad, n_pad), bool)
    mask[:N, :N] = pack.ancestor_mask()
    logits = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    logits = np.where(mask[None], logits, -1e30)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = np.where(mask[None], probs, 0.0)
    probs = probs / np.maximum(probs.sum(-1, keepdims=True), 1e-30)
    ref = np.einsum("hqk,khd->qhd", probs, v)
    np.testing.assert_allclose(out[:N], ref[:N], atol=2e-3, rtol=2e-3)


def test_tree_forward_pallas_matches_dense():
    import numpy as np
    import jax

    from areal_tpu.models import qwen
    from areal_tpu.models.tree import build_tree, tree_forward_logprobs
    from areal_tpu.ops.tree_attention import tree_forward_logprobs_pallas

    cfg = qwen.ModelConfig(
        vocab_size=96,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=1,
        num_kv_heads=1,
        head_dim=128,
        dtype="float32",
        attention_bias=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    base = list(rng.integers(1, 96, 24))
    seqs = [base + list(rng.integers(1, 96, 10)) for _ in range(3)]
    pack = build_tree(seqs)
    dense = np.asarray(tree_forward_logprobs(params, cfg, pack))
    sparse = np.asarray(tree_forward_logprobs_pallas(params, cfg, pack))
    np.testing.assert_allclose(sparse, dense, atol=3e-4, rtol=3e-3)


def test_tree_training_grad_parity():
    """Sparse-kernel tree training == dense-mask tree training, in gradients
    (VERDICT r03 item: the reference's Triton kernel trains through the
    sparse path, models/tree_attn/triton_kernel.py fwd+bwd)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import qwen
    from areal_tpu.models.tree import build_tree, tree_train_logprobs
    from areal_tpu.ops.tree_attention import pack_ancestor_bits

    rng = np.random.default_rng(1)
    # >128 nodes with deep shared prefixes -> multiple tiles, some skippable
    base = list(rng.integers(1, 200, 90))
    seqs = [base[:60] + list(rng.integers(1, 200, 80)) for _ in range(3)]
    seqs += [base + list(rng.integers(1, 200, 40)) for _ in range(2)]
    pack = build_tree(seqs)
    assert pack.n_nodes > 128
    _, block_any = pack_ancestor_bits(pack.parent)
    assert block_any.mean() < 1.0, "expected at least one skippable tile"

    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    # per-node weights make the loss sensitive to every edge logprob
    wts = jnp.asarray(rng.normal(0, 1, pack.n_nodes), jnp.float32)

    def loss(params, impl):
        return (tree_train_logprobs(params, TINY_QWEN2, pack, impl) * wts).sum()

    ls, gs = jax.value_and_grad(lambda p: loss(p, "sparse"))(params)
    ld, gd = jax.value_and_grad(lambda p: loss(p, "dense"))(params)
    np.testing.assert_allclose(float(ls), float(ld), rtol=1e-4)
    flat_s = jax.tree.leaves(gs)
    flat_d = jax.tree.leaves(gd)
    for a, b in zip(flat_s, flat_d):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )
