"""Shared test helpers (reference tests/utils.py + areal/utils/testing_utils.py)."""

import numpy as np

from areal_tpu.models import qwen
from areal_tpu.utils.data import pad_sequences_to_tensors

TINY_QWEN2 = qwen.ModelConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    dtype="float32",
    tie_word_embeddings=True,
    attention_bias=True,
    rope_theta=10000.0,
)

TINY_QWEN3 = qwen.ModelConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    dtype="float32",
    tie_word_embeddings=False,
    attention_bias=False,
    qk_norm=True,
    rope_theta=10000.0,
)


def random_batch(
    n_seqs=8, min_len=5, max_len=60, vocab=256, seed=0, with_rl_keys=False
):
    rng = np.random.default_rng(seed)
    trajs = []
    for _ in range(n_seqs):
        n = int(rng.integers(min_len, max_len))
        t = {
            "input_ids": rng.integers(0, vocab, n).astype(np.int32),
            "loss_mask": np.concatenate(
                [np.zeros(n // 2, np.float32), np.ones(n - n // 2, np.float32)]
            ),
        }
        if with_rl_keys:
            t["logprobs"] = rng.normal(-1.5, 0.3, n).astype(np.float32)
            t["versions"] = np.zeros(n, np.int32)
            t["rewards"] = np.float32(rng.uniform(0, 1))
        trajs.append(t)
    return pad_sequences_to_tensors(trajs)


def tiny_decode_engine(model_cfg=TINY_QWEN2, params=None, **server_kw):
    """An initialized (not started) ``DecodeEngine`` on ONE CPU device: 4
    slots x 512 context, pages of 16, 4 steps a call, one decode-chunk window
    (``attn_window_step`` = the context), radix cache off, seed 0, seeded
    float32 weights; ``server_kw`` overrides any ``ServerConfig`` field."""
    import jax

    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.parallel import mesh as mesh_lib

    kw = dict(
        max_batch_size=4, max_seq_len=512, page_size=16, decode_steps_per_call=4, seed=0,
        mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1), prefix_cache=PrefixCacheConfig(enabled=False),
    )
    kw.update(server_kw)
    kw.setdefault("attn_window_step", kw["max_seq_len"])
    cfg = ServerConfig(**kw)
    if params is None:
        params = qwen.init_params(jax.random.PRNGKey(0), model_cfg)
    eng = DecodeEngine(
        cfg, params=params, model_cfg=model_cfg, mesh=mesh_lib.make_mesh(cfg.mesh, devices=jax.devices()[:1])
    )
    eng.initialize()
    return eng
