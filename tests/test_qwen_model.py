"""Model correctness: HF-transformers parity, packed-grid equivalence,
sharded-vs-single-device equivalence (replaces the reference's
test_packed_vs_padded_consistency.py + torchrun ulysses equivalence tests)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import qwen
from areal_tpu.models.hf import load_params_from_hf, save_params_to_hf
from areal_tpu.parallel import make_mesh
from areal_tpu.api.config import MeshConfig
from jax import set_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_testing import TINY_QWEN2, TINY_QWEN3


def _simple_inputs(cfg, L=33, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (1, L)).astype(np.int32)
    seg = np.ones((1, L), np.int32)
    pos = np.arange(L, dtype=np.int32)[None]
    return ids, seg, pos


@pytest.mark.parametrize("cfg", [TINY_QWEN2, TINY_QWEN3], ids=["qwen2", "qwen3"])
def test_forward_runs(cfg):
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    ids, seg, pos = _simple_inputs(cfg)
    hidden = qwen.forward(params, cfg, ids, seg, pos)
    assert hidden.shape == (1, 33, cfg.hidden_size)
    logits = qwen.compute_logits(params, cfg, hidden)
    assert logits.shape == (1, 33, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("model_type", ["qwen2", "qwen3", "llama"])
def test_hf_transformers_parity(tmp_path, model_type):
    """Round-trip a tiny random HF model through our loader and compare logits
    against the torch implementation. Llama rides the same decoder family
    (RMSNorm + SwiGLU + GQA + rope, bias-free attention, untied head) — the
    config parser and name map are architecture-generic, so Llama-3-style
    checkpoints load without a separate model implementation."""
    torch = pytest.importorskip("torch")
    import transformers

    if model_type == "llama":
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128,
            hidden_size=32,
            intermediate_size=64,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            tie_word_embeddings=False,
            rope_theta=500000.0,
        )
        model = transformers.LlamaForCausalLM(hf_cfg)
    elif model_type == "qwen2":
        hf_cfg = transformers.Qwen2Config(
            vocab_size=128,
            hidden_size=32,
            intermediate_size=64,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            tie_word_embeddings=False,
            rope_theta=10000.0,
        )
        model = transformers.Qwen2ForCausalLM(hf_cfg)
    else:
        hf_cfg = transformers.Qwen3Config(
            vocab_size=128,
            hidden_size=32,
            intermediate_size=64,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            head_dim=8,
            tie_word_embeddings=False,
            rope_theta=10000.0,
        )
        model = transformers.Qwen3ForCausalLM(hf_cfg)
    model = model.eval().to(torch.float32)
    path = str(tmp_path / "hf")
    model.save_pretrained(path, safe_serialization=True)

    cfg = qwen.ModelConfig.from_hf_dict(json.loads(open(os.path.join(path, "config.json")).read()))
    cfg = qwen.ModelConfig(**{**cfg.__dict__, "dtype": "float32"})
    params, _ = load_params_from_hf(path, cfg, dtype=jnp.float32)

    ids, seg, pos = _simple_inputs(cfg, L=17)
    hidden = qwen.forward(params, cfg, ids, seg, pos)
    ours = np.asarray(qwen.compute_logits(params, cfg, hidden))[0]

    with torch.no_grad():
        theirs = model(torch.tensor(ids.astype(np.int64))).logits[0].numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_packed_grid_matches_separate_sequences():
    """Two sequences packed into one row must produce the same logits as each
    sequence alone (segment masking + per-segment positions)."""
    cfg = TINY_QWEN2
    params = qwen.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    a = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)

    L = 24
    ids = np.zeros((1, L), np.int32)
    seg = np.zeros((1, L), np.int32)
    pos = np.zeros((1, L), np.int32)
    ids[0, :11], ids[0, 11:18] = a, b
    seg[0, :11], seg[0, 11:18] = 1, 2
    pos[0, :11], pos[0, 11:18] = np.arange(11), np.arange(7)
    packed = np.asarray(
        qwen.compute_logits(params, cfg, qwen.forward(params, cfg, ids, seg, pos))
    )

    for seq, sl in ((a, slice(0, 11)), (b, slice(11, 18))):
        n = len(seq)
        ids1 = seq[None]
        seg1 = np.ones((1, n), np.int32)
        pos1 = np.arange(n, dtype=np.int32)[None]
        solo = np.asarray(
            qwen.compute_logits(params, cfg, qwen.forward(params, cfg, ids1, seg1, pos1))
        )
        np.testing.assert_allclose(packed[0, sl], solo[0], rtol=1e-4, atol=1e-4)


def test_chunked_logprobs_match_full_logits():
    cfg = TINY_QWEN2
    params = qwen.init_params(jax.random.PRNGKey(3), cfg)
    ids, seg, pos = _simple_inputs(cfg, L=21, seed=4)
    hidden = qwen.forward(params, cfg, ids, seg, pos)
    labels = np.roll(ids, -1, axis=-1)
    logp, ent = qwen.chunked_logprobs_entropy(params, cfg, hidden, jnp.asarray(labels), chunk_size=8)
    logits = np.asarray(qwen.compute_logits(params, cfg, hidden))
    full = jax.nn.log_softmax(logits, axis=-1)
    want_logp = np.take_along_axis(np.asarray(full), labels[..., None], axis=-1)[..., 0]
    p = np.exp(np.asarray(full))
    want_ent = -(p * np.asarray(full)).sum(-1)
    np.testing.assert_allclose(np.asarray(logp), want_logp, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ent), want_ent, rtol=1e-3, atol=1e-3)


def test_sharded_matches_single_device():
    """Full 8-way sharded forward (dp×fsdp×tp = 2×2×2) == unsharded forward."""
    cfg = TINY_QWEN2
    params = qwen.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    G, L = 4, 32
    ids = rng.integers(0, cfg.vocab_size, (G, L)).astype(np.int32)
    seg = (rng.random((G, L)) < 0.9).astype(np.int32)
    pos = np.maximum(0, np.cumsum(seg, axis=1) - 1).astype(np.int32)
    base = np.asarray(qwen.forward(params, cfg, ids, seg, pos))

    mesh = make_mesh(MeshConfig(data=2, fsdp=2, seq=1, model=2))
    specs = qwen.param_partition_specs(cfg)
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    with set_mesh(mesh):
        fn = jax.jit(lambda p, i, s, po: qwen.forward(p, cfg, i, s, po))
        batch_shard = NamedSharding(mesh, P(("data", "fsdp"), None))
        out = fn(
            sharded,
            jax.device_put(ids, batch_shard),
            jax.device_put(seg, batch_shard),
            jax.device_put(pos, batch_shard),
        )
    np.testing.assert_allclose(np.asarray(out), base, rtol=2e-4, atol=2e-4)


def test_hf_save_load_roundtrip(tmp_path):
    cfg = TINY_QWEN3
    params = qwen.init_params(jax.random.PRNGKey(7), cfg)
    path = str(tmp_path / "export")
    save_params_to_hf(params, cfg, path)
    re_params, _ = load_params_from_hf(path, cfg, dtype=jnp.float32)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6),
        params,
        re_params,
    )
