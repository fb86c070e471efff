"""Pallas paged-attention kernel parity in interpret mode (CPU).

The repo's decode kernels against the XLA gather path, which scales the
logits internally. chip_smoke.py's kernels phase re-checks on real hardware;
this file keeps the parity under CI without a chip.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from areal_tpu.inference import paged_kv


def _setup(S=4, KH=2, G=6, hd=128, psz=16, wp=4, seed=0):
    rng = np.random.default_rng(seed)
    H = KH * G
    N = S * wp + 1
    q = jnp.asarray(rng.normal(0, 1, (S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (KH, N, psz, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (KH, N, psz, hd)), jnp.float32)
    pt = jnp.asarray(1 + np.arange(S * wp).reshape(S, wp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, wp * psz + 1, S), jnp.int32)
    return q, k, v, lengths, pt


def test_xla_path_matches_dense_reference():
    """Ground truth: the XLA path IS scaled dot-product attention."""
    q, k, v, lengths, pt = _setup(S=1, KH=1, G=8, wp=2)
    W = 2 * 16
    lengths = jnp.asarray([W], jnp.int32)
    kk = np.concatenate([np.asarray(k)[0, p] for p in np.asarray(pt)[0]], axis=0)
    vv = np.concatenate([np.asarray(v)[0, p] for p in np.asarray(pt)[0]], axis=0)
    qq = np.asarray(q)[0]
    probs = np.asarray(
        jax.nn.softmax(jnp.asarray(qq @ kk.T / np.sqrt(q.shape[-1])), axis=-1)
    )
    want = probs @ vv
    got = np.asarray(paged_kv.paged_attention_xla(q, k, v, lengths, pt))[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_q8_kernel_interpret_matches_xla():
    """The quantized decode kernel (ops/paged_attention_q8.py, lane-major
    scales) against the gather+dequant XLA path."""
    import areal_tpu.ops.paged_attention_q8 as q8mod

    q, k, v, lengths, pt = _setup()
    kq, ks = paged_kv.quantize_pages(k)
    vq, vs = paged_kv.quantize_pages(v)
    ref = paged_kv.paged_attention_xla(q, kq, vq, lengths, pt, ks, vs)
    out = q8mod.paged_attention_q8(
        q,  # RAW: the wrapper applies 1/sqrt(hd) internally
        kq,
        ks,
        vq,
        vs,
        lengths,
        pt,
        pages_per_compute_block=2,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_stacked_kernel_interpret_matches_xla():
    """paged_attention_stacked (the serving hot path: full stacked cache +
    in-kernel layer slicing — no per-step layer copies) against the
    per-layer XLA path, bf16 and int8, multiple layer indices."""
    from tests.test_paged_decode_kernel import LAUNCH as paged_attention_stacked  # one jit of the entry point: a compile a shape, not a call

    rng = np.random.default_rng(7)
    L, S, KH, G, hd, psz, wp = 3, 4, 2, 6, 128, 16, 4
    H = KH * G
    N = S * wp + 1
    q = jnp.asarray(rng.normal(0, 1, (S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (L, KH, N, psz, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (L, KH, N, psz, hd)), jnp.float32)
    pt = jnp.asarray(1 + np.arange(S * wp).reshape(S, wp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, wp * psz + 1, S), jnp.int32)
    for li in (0, L - 1):
        ref = paged_kv.paged_attention_xla(q, k[li], v[li], lengths, pt)
        out = paged_attention_stacked(
            q, k, v, jnp.int32(li), lengths, pt,
            pages_per_compute_block=2, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
        )
    kq, ks = paged_kv.quantize_pages(k)
    vq, vs = paged_kv.quantize_pages(v)
    ref = paged_kv.paged_attention_xla(q, kq[1], vq[1], lengths, pt, ks[1], vs[1])
    out = paged_attention_stacked(
        q, kq, vq, jnp.int32(1), lengths, pt,
        pages_per_compute_block=2, k_scales=ks, v_scales=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


@pytest.mark.parametrize("quant", [False, True])
def test_full_decode_step_composition_interpret(quant, monkeypatch):
    """forward_decode_paged with use_kernel=True — the scatter-write +
    in-kernel layer slice composition inside the layers scan — against the
    XLA path, full model forward, greedy argmax parity. This is the exact
    program the serving chunk runs on chip."""
    import functools

    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw
    from areal_tpu.models import qwen

    monkeypatch.setattr(
        q8mod,
        "paged_attention_stacked",
        functools.partial(q8mod.paged_attention_stacked, interpret=True),
    )
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))
    cfg = qwen.ModelConfig(
        vocab_size=256,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        dtype="float32",
        tie_word_embeddings=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    S, psz, wp = 4, 16, 2
    cache = paged_kv.init_paged_cache(cfg, S * wp + 1, psz, quant=quant)
    pt = jnp.asarray(1 + np.arange(S * wp).reshape(S, wp), jnp.int32)
    ids = jnp.asarray([3, 5, 7, 9], jnp.int32)
    pos = jnp.asarray([4, 9, 14, 19], jnp.int32)
    outs = {}
    for uk in (True, False):
        hid, _ = qwen.forward_decode_paged(
            params, cfg, ids, pos, dict(cache), pt, page_size=psz, use_kernel=uk
        )
        logits = qwen.compute_logits(params, cfg, hid)
        outs[uk] = np.asarray(jnp.argmax(logits, -1))
    np.testing.assert_array_equal(outs[True], outs[False])
