"""int8 KV-cache quantization (ServerConfig.kv_quantization).

KV reads dominate decode HBM traffic at long context; int8 pages (per-
token-vector scales, the TPU paged-attention kernel's QuantizedTensor
convention) halve them and double what a kv_hbm_gb budget buys. CPU tests
run the gather+dequant XLA path; the kernel path shares the same pages.
"""

import pytest

import numpy as np
import jax
import jax.numpy as jnp

from areal_tpu.api.config import MeshConfig, ServerConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference import paged_kv
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.models import qwen

MODEL_KW = dict(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    dtype="float32",
    tie_word_embeddings=True,
)


def test_quantize_dequantize_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 2.0, (3, 5, 16)).astype(np.float32))
    q, s = paged_kv.quantize_kv(x)
    assert q.dtype == jnp.int8
    back = np.asarray(paged_kv.dequantize_kv(q, s, jnp.float32))
    # per-vector scale: |err| <= scale/127.5 (half-step + clip slack)
    bound = np.asarray(s) / 127.5
    assert np.all(np.abs(back - np.asarray(x)) <= bound + 1e-7)


def test_paged_attention_xla_int8_close():
    """Gathered int8 attention matches attention over the dequantized
    pages exactly (the dequant happens before the einsum)."""
    rng = np.random.default_rng(1)
    S, H, KH, hd, N, psz, wp = 3, 4, 2, 16, 9, 4, 2
    q = jnp.asarray(rng.normal(0, 1, (S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (KH, N, psz, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (KH, N, psz, hd)).astype(np.float32))
    kq, ks = paged_kv.quantize_pages(k)  # lane-major scales [KH, N, 1, psz]
    vq, vs = paged_kv.quantize_pages(v)
    assert ks.shape == (KH, N, 1, psz)
    lengths = jnp.asarray([5, 8, 3], jnp.int32)
    table = jnp.asarray(rng.integers(0, N, (S, wp)), jnp.int32)
    got = paged_kv.paged_attention_xla(q, kq, vq, lengths, table, ks, vs)
    kd = paged_kv.dequantize_kv(kq, jnp.swapaxes(ks, -1, -2), jnp.float32)
    vd = paged_kv.dequantize_kv(vq, jnp.swapaxes(vs, -1, -2), jnp.float32)
    want = paged_kv.paged_attention_xla(q, kd, vd, lengths, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_engine_serves_with_int8_kv():
    cfg = qwen.ModelConfig(**MODEL_KW)
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    outs = {}
    for kvq in ("none", "int8"):
        eng = DecodeEngine(
            ServerConfig(
                max_batch_size=4,
                max_seq_len=64,
                decode_steps_per_call=4,
                seed=0,
                kv_quantization=kvq,
                mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            ),
            params=params,
            model_cfg=cfg,
        )
        eng.initialize()
        if kvq == "int8":
            assert eng.cache["k"].dtype == jnp.int8
            # lane-major: one f32 per token vector, tokens along the lanes
            assert eng.cache["k_scale"].shape[-2:] == (1, eng.config.page_size)
        eng.start()
        try:
            r = eng.generate_sync(
                ModelRequest(
                    input_ids=list(range(1, 9)),
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=10, greedy=True
                    ),
                ),
                timeout=120,
            )
            outs[kvq] = (tuple(r.output_tokens), list(r.output_logprobs))
            assert len(r.output_tokens) == 10
        finally:
            eng.stop()
    # int8 KV drifts logprobs slightly but greedy argmax at random-init
    # margins should track for a short horizon
    assert outs["none"][0] == outs["int8"][0]
    np.testing.assert_allclose(outs["none"][1], outs["int8"][1], atol=0.15)


def test_budget_doubles_pages_with_int8():
    budget = 1 << 20
    n_bf16 = paged_kv.n_pages_for_budget(budget, 2, 2, 16, 16, 4, quant=False)
    n_int8 = paged_kv.n_pages_for_budget(budget, 2, 2, 16, 16, 4, quant=True)
    assert n_int8 > 1.5 * n_bf16


def test_prefix_sharing_with_int8_kv():
    """GRPO n_samples page aliasing + partial-page copy must carry the
    scale planes along with the int8 pages."""
    cfg = qwen.ModelConfig(**MODEL_KW)
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(
        ServerConfig(
            max_batch_size=4,
            max_seq_len=64,
            decode_steps_per_call=4,
            seed=0,
            kv_quantization="int8",
            enable_prefix_caching=True,
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        ),
        params=params,
        model_cfg=cfg,
    )
    eng.initialize()
    eng.start()
    try:
        r = eng.generate_sync(
            ModelRequest(
                input_ids=list(range(1, 9)),
                gconfig=GenerationHyperparameters(
                    max_new_tokens=6, n_samples=3, temperature=1.0
                ),
            ),
            timeout=120,
        )
        group = r if isinstance(r, list) else [r]
        for item in group:
            assert len(item.output_tokens) == 6
    finally:
        eng.stop()
