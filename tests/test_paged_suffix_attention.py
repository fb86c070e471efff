"""Paged suffix-attention kernel family (docs/perf.md "Paged
suffix-attention kernel family"): model-level kernel-vs-XLA parity for
both launch variants (suffix prefill with a chain mask, spec verify with
a tree mask) across the full kv-quantization ladder (bf16-free tiny f32
model x {none, int8, fp8} pages), the fp8 quantize/dequantize roundtrip,
kernel-level padded-row semantics, and an engine-level fp8 serve.

The kernel's own case grid (GQA ratios x ragged lengths x dtypes x
masks) lives in tools/kernelcheck.py; these tests pin the INTEGRATION —
`use_kernel=True` through `forward_prefill_paged`/`forward_verify_paged`
reads the same pages, scales, and masks the XLA path reads."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from tpu_testing import TINY_QWEN2

from areal_tpu.inference import paged_kv
from areal_tpu.models import qwen

PSZ, WP, A, B = 8, 4, 3, 12
PRE_LEN = 2 * PSZ


@pytest.fixture(scope="module")
def tiny_params():
    return qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)


def _prefixed_cache(tiny_params, quant):
    """A paged cache holding a PRE_LEN-token prefix per slot (pages 1..,
    page 0 is the trash page), plus the page table and prefix lengths."""
    rng = np.random.default_rng(3)
    cache = paged_kv.init_paged_cache(TINY_QWEN2, A * WP + 1, PSZ, quant=quant)
    pre_ids = jnp.asarray(rng.integers(1, 255, (A, PRE_LEN)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(PRE_LEN)[None], (A, PRE_LEN))
    seg = jnp.ones((A, PRE_LEN), jnp.int32)
    _, ks, vs = qwen.forward_prefill(tiny_params, TINY_QWEN2, pre_ids, pos, seg)
    n_pre = PRE_LEN // PSZ
    flat_pages = jnp.asarray(1 + np.arange(A * n_pre), jnp.int32)
    cache = paged_kv.scatter_prefill(cache, ks, vs, flat_pages, PSZ)
    ppt = np.zeros((A, WP), np.int32)
    ppt[:, :n_pre] = 1 + np.arange(A * n_pre).reshape(A, n_pre)
    return cache, jnp.asarray(ppt), jnp.full((A,), PRE_LEN, jnp.int32), rng


@pytest.mark.parametrize("quant", [False, "int8", "fp8"])
def test_prefill_kernel_matches_xla(tiny_params, quant):
    """Suffix prefill, ragged suffix lengths (one row fully padded-free,
    two partially padded): valid-row hidden states and the returned
    suffix KV match the XLA gather path. Padded rows are allowed to
    differ — their output is discarded and their KV lands in the trash
    page either way."""
    cache, ppt, offs, rng = _prefixed_cache(tiny_params, quant)
    suf_ids = jnp.asarray(rng.integers(1, 255, (A, B)), jnp.int32)
    plens = jnp.asarray([B, B - 3, 5], jnp.int32)
    positions = offs[:, None] + jnp.arange(B)[None]
    seg_s = (jnp.arange(B)[None] < plens[:, None]).astype(jnp.int32)
    h0, k0, v0 = qwen.forward_prefill_paged(
        tiny_params, TINY_QWEN2, suf_ids, positions, seg_s, cache, ppt,
        offs, use_kernel=False,
    )
    h1, k1, v1 = qwen.forward_prefill_paged(
        tiny_params, TINY_QWEN2, suf_ids, positions, seg_s, cache, ppt,
        offs, use_kernel=True,
    )
    m = np.asarray(seg_s, bool)
    assert float(jnp.max(jnp.abs(h0 - h1)[m])) < 1e-4, quant
    # the suffix KV the caller scatters is layer-stacked [L, A, B, KH, hd]
    assert float(jnp.max(jnp.abs(k0 - k1)[:, m])) < 1e-4
    assert float(jnp.max(jnp.abs(v0 - v1)[:, m])) < 1e-4


@pytest.mark.parametrize("quant", [False, "int8", "fp8"])
def test_verify_kernel_matches_xla(tiny_params, quant):
    """Tree verify: the drafter's ancestor mask (self-bit + root column +
    chain links) drives the SAME kernel body through the tree-mask
    operand — every row matches the XLA path, no padded-row carve-out,
    because the drafter sets each row's self-bit unconditionally."""
    cache, ppt, offs, rng = _prefixed_cache(tiny_params, quant)
    tm = np.zeros((A, B, B), bool)
    tm[:, np.arange(B), np.arange(B)] = True
    tm[:, :, 0] = True
    for r in range(2, B):
        tm[:, r, r - 1] = True
    tm = jnp.asarray(tm)
    ids = jnp.asarray(rng.integers(1, 255, (A, B)), jnp.int32)
    pos = offs[:, None] + jnp.asarray(rng.integers(0, 3, (A, B)), jnp.int32)
    hv0, _, _ = qwen.forward_verify_paged(
        tiny_params, TINY_QWEN2, ids, pos, tm, cache, ppt, offs,
        use_kernel=False,
    )
    hv1, _, _ = qwen.forward_verify_paged(
        tiny_params, TINY_QWEN2, ids, pos, tm, cache, ppt, offs,
        use_kernel=True,
    )
    assert float(jnp.max(jnp.abs(hv0 - hv1))) < 1e-4, quant


def test_kernel_padded_rows_output_exact_zeros():
    """Direct kernel semantics: a row whose mask diagonal bit is clear is
    invalid and outputs EXACT zeros (not garbage from an all-masked
    softmax) — both in the kernel and its XLA reference."""
    from areal_tpu.ops import paged_suffix_attention as psa

    rng = np.random.default_rng(0)
    S, Bq, KH, G, hd, L = 2, 4, 2, 2, 8, 1
    H = KH * G
    n_pages = S * WP + 1
    q = jnp.asarray(rng.standard_normal((S, Bq, H, hd)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((S, Bq, KH, hd)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((S, Bq, KH, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((L, KH, n_pages, PSZ, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, KH, n_pages, PSZ, hd)), jnp.float32)
    plens = jnp.asarray([PSZ, 0], jnp.int32)
    pidx = jnp.asarray(1 + np.arange(S * WP).reshape(S, WP), jnp.int32)
    mask = np.tril(np.ones((Bq, Bq), bool))[None].repeat(S, 0)
    mask[:, Bq - 1, :] = False  # last row fully padded
    mask = jnp.asarray(mask)
    for fn in (psa.paged_suffix_attention, psa.paged_suffix_attention_xla):
        out = fn(q, ks, vs, kp, vp, 0, plens, pidx, mask)
        assert out.shape == (S, Bq, H, hd)
        assert bool(jnp.all(out[:, Bq - 1] == 0.0)), fn.__name__


def test_fp8_quantize_roundtrip_and_dtype_ladder():
    """float8_e4m3fn pages share int8's scale semantics: one dequant
    formula recovers both within dtype-appropriate error, and
    quant_dtype() maps the config strings onto page dtypes."""
    assert paged_kv.quant_dtype(False) is None
    assert paged_kv.quant_dtype(True) == jnp.int8
    assert paged_kv.quant_dtype("int8") == jnp.int8
    assert paged_kv.quant_dtype("fp8") == jnp.float8_e4m3fn
    with pytest.raises(ValueError):
        paged_kv.quant_dtype("fp4")

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 16, 32)) * 3.0, jnp.float32)
    for dtype, rel_tol in ((jnp.int8, 0.01), (jnp.float8_e4m3fn, 0.08)):
        q, scale = paged_kv.quantize_kv(x, dtype=dtype)
        assert q.dtype == dtype
        assert scale.shape == (4, 16, 1)  # narrow trailing-1 per-vector
        back = paged_kv.dequantize_kv(q, scale, jnp.float32)
        rel = float(jnp.max(jnp.abs(back - x)) / jnp.max(jnp.abs(x)))
        assert rel < rel_tol, (dtype, rel)
    # scaled values sit inside e4m3's finite range (±448): no inf/nan
    q8, _ = paged_kv.quantize_kv(x, dtype=jnp.float8_e4m3fn)
    assert bool(jnp.all(jnp.isfinite(q8.astype(jnp.float32))))


def test_engine_kernel_on_greedy_parity_twin():
    """Engine-level twin with the suffix kernel FORCED on (interpret mode
    on CPU — `set_suffix_kernel(True)`, the bench A/B hook) vs the default
    XLA path: greedy byte-identity across cold prefill, radix-hit
    admission (shared-prefix follow-up), and spec-decode verify."""
    from areal_tpu.api.config import (
        MeshConfig,
        ServerConfig,
        SpeculativeConfig,
    )
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine

    def _serve(use_kernel):
        cfg = ServerConfig(
            max_batch_size=2,
            max_seq_len=256,
            decode_steps_per_call=4,
            page_size=16,
            seed=0,
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        )
        cfg.speculative = SpeculativeConfig(enabled=True, drafter="tree")
        params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
        eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
        eng.initialize()
        eng.programs.set_suffix_kernel(use_kernel)
        # the decode step's kernel and the writer of its KV rows go together
        impl = eng.attention_impl()
        assert impl["kv_write"] == impl["decode"] == "xla", impl
        eng.start()
        out = {}
        try:
            shared = ([9, 2, 9, 2, 7] * 8)[:32]
            # cold prefill + spec verify (periodic prompt: drafts accept)
            out["cold"] = eng.generate_sync(
                ModelRequest(
                    input_ids=[7, 3, 9] * 8,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=24, greedy=True
                    ),
                ),
                timeout=180,
            ).output_tokens
            # publish the shared prefix, then a follow-up admits via the
            # radix tree -> suffix prefill path
            eng.generate_sync(
                ModelRequest(
                    input_ids=list(shared),
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=8, greedy=True
                    ),
                ),
                timeout=180,
            )
            out["radix"] = eng.generate_sync(
                ModelRequest(
                    input_ids=list(shared) + [4, 4, 1, 3],
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=24, greedy=True
                    ),
                ),
                timeout=180,
            ).output_tokens
            assert eng.stats["spec_rounds"] > 0, "speculation never ran"
            held = (
                eng.prefix_cache_stats()["pages_held"]
                if eng.slots.radix is not None
                else 0
            )
            assert eng.slots.pool.used - held == 0
        finally:
            eng.stop()
        return out

    assert _serve(True) == _serve(False)


def test_engine_fp8_cache_serves_greedy():
    """Engine-level fp8: kv_quantization="fp8" builds float8_e4m3fn pages
    and a short greedy serve completes with zero leaked pages."""
    from areal_tpu.api.config import MeshConfig, ServerConfig
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine

    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=128,
        decode_steps_per_call=4,
        page_size=16,
        kv_quantization="fp8",
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    assert eng.cache["k"].dtype == jnp.float8_e4m3fn
    assert eng.cache["k_scale"].dtype == jnp.float32
    eng.start()
    try:
        resp = eng.generate_sync(
            ModelRequest(
                input_ids=[7, 3, 9] * 8,
                gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
            ),
            timeout=120,
        )
        assert len(resp.output_tokens) == 8
        held = (
            eng.prefix_cache_stats()["pages_held"]
            if eng.slots.radix is not None
            else 0
        )
        assert eng.slots.pool.used - held == 0
    finally:
        eng.stop()
