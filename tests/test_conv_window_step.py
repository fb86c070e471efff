"""A decode step's conv window (``hybrid._conv_window_step``), the one form
the three recurrent mixers share: against a plain NumPy shift-and-dot, and
against the prefill forms it must agree with.

Tolerances. The helper run op by op (no jit) against the prefill forms'
chain ``acc = bias; for k: acc += padded[k] * w[k]`` on the same values: the
same products added in the same order, so equal bit for bit, and the window
it leaves is the rounded inputs themselves. Against NumPy in float64: the
float32 sum of 3 or 4 products of order 1, 2e-6. The mixers whole (a batch
of rows through one matmul against one row at a time): the limits of the
families' own tests (``test_lfm2_model``, ``test_hybrid_model``), which a tap
in the wrong order or a window one token off passes by 1e-2 and more."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))

from areal_tpu.models import hybrid  # noqa: E402

# mixer kind -> (taps, bias): Mamba-2's conv of 4 with a bias, the short conv of 3, the delta rule's three convs of 4 side by side
KINDS = {"mamba": (4, True), "conv": (3, False), "gdn": (4, False)}
S, N = 5, 7  # slots, tokens


def _case(kind, dtype, C):
    K, has_bias = KINDS[kind]
    rng = np.random.default_rng([K, C])
    x = jnp.asarray(rng.normal(0, 1, (S, N, C)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (K, C)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, (C,)), jnp.float32) if has_bias else None
    conv = jnp.asarray(rng.normal(0, 1, (S, (K - 1) * C)), jnp.float32).astype(dtype)
    return K, x, w, bias, conv


@pytest.mark.parametrize("C", [128, 72], ids=["lanes128", "lanes72"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_conv_window_step(kind, dtype, C):
    K, x, w, bias, conv = _case(kind, jnp.dtype(dtype), C)
    active = jnp.asarray([True, False, True, True, False])

    # one step against NumPy: the window is the slot's row cut into K-1 taps with the new input, rounded, behind them
    acc, new = hybrid._conv_window_step(conv, x[:, 0], w, bias, active)
    assert acc.dtype == jnp.float32 and new.dtype == conv.dtype and new.shape == conv.shape
    window = np.concatenate([np.asarray(conv, np.float64).reshape(S, K - 1, C), np.asarray(x[:, :1].astype(dtype), np.float64)], axis=1)
    want = np.einsum("skc,kc->sc", window, np.asarray(w, np.float64)) + (0.0 if bias is None else np.asarray(bias, np.float64))
    np.testing.assert_allclose(np.asarray(acc), want, rtol=0, atol=2e-6)
    shifted = window[:, 1:].reshape(S, -1)
    live = np.asarray(active)
    assert np.array_equal(np.asarray(new, np.float64)[live], shifted[live])
    # a slot that is not active keeps its window bit for bit
    assert np.array_equal(np.asarray(new)[~live].view(np.uint8), np.asarray(conv)[~live].view(np.uint8))
    # and jitted, as the engine runs it (the compiler may fuse a product into its sum)
    acc_j, new_j = jax.jit(hybrid._conv_window_step)(conv, x[:, 0], w, bias, active)
    np.testing.assert_allclose(np.asarray(acc_j), want, rtol=0, atol=2e-6)
    assert np.array_equal(np.asarray(new_j).view(np.uint8), np.asarray(new).view(np.uint8))

    # N steps from the zero window against the prefill forms' chain over the whole row: bit for bit, at every position
    rounded = x.astype(dtype)
    padded = jnp.pad(rounded, ((0, 0), (K - 1, 0), (0, 0)))
    chain = 0.0 if bias is None else bias
    for k in range(K):
        chain = chain + padded[:, k : k + N].astype(jnp.float32) * w[k]
    state = jnp.zeros_like(conv)
    everyone = jnp.ones((S,), bool)
    for t in range(N):
        acc, state = hybrid._conv_window_step(state, x[:, t], w, bias, everyone)
        assert np.array_equal(np.asarray(acc), np.asarray(chain[:, t])), t
    n_state = jnp.full((S,), N, jnp.int32)
    after = hybrid._window_after(padded, n_state, K).astype(dtype)  # what a prefill leaves in the slot
    assert np.array_equal(np.asarray(state).view(np.uint8), np.asarray(after).view(np.uint8))


def _mamba(dtype):
    import chipbench_hybrid_util as hu

    hu.load_run()
    from benchlib import hybrid_weights

    cfg = hu.tiny_model()
    mcfg = hu.model_config(cfg)
    layer = {k: v[1] for k, v in hybrid_weights.make_params(cfg, 11, jnp.float32)["mamba"].items()}
    state = {
        "ssm": jnp.zeros((2, 2, mcfg.mamba_n_heads, mcfg.mamba_d_head, mcfg.mamba_d_state), jnp.float32),
        "conv": jnp.zeros((2, 2, (mcfg.mamba_d_conv - 1) * mcfg.conv_dim), dtype),
    }

    def prefill(h, n):
        out, _, conv = hybrid.mamba_prefill(mcfg, layer, h, n, (jnp.float32, dtype))
        return out, conv

    def decode(h_t, st, active):
        o, st = hybrid.mamba_decode(mcfg, layer, h_t, st, 1, active)
        return o, st, st["conv"]

    return mcfg.hidden_size, state, prefill, decode


def _conv(dtype):
    import chipbench_lfm2_util as lu

    cfg = lu.tiny_model()
    mcfg = lu.model_config(cfg)
    layer = jax.tree.map(lambda a: a[1], lu.make_params(cfg, 3)["conv_moe"])
    state = jnp.zeros((2, 2, (mcfg.conv_L_cache - 1) * mcfg.hidden_size), dtype)

    def decode(h_t, st, active):
        o, st = hybrid.conv_decode(mcfg, layer, h_t, st, 1, active)
        return o, st, st

    return mcfg.hidden_size, state, lambda h, n: hybrid.conv_prefill(mcfg, layer, h, n, dtype), decode


def _gdn(dtype):
    import chipbench_olmo_util as ou

    cfg = ou.tiny_model()
    mcfg = ou.model_config(cfg)
    layer = jax.tree.map(lambda a: a[1], ou.make_params(cfg)["gdn"])
    shapes = mcfg.state_shapes(2)
    state = {"gdn": jnp.zeros((2, *shapes["gdn"][0][1:]), jnp.float32), "conv": jnp.zeros((2, *shapes["conv"][0][1:]), dtype)}

    def prefill(h, n):
        out, _, conv = hybrid.gdn_prefill(mcfg, layer, h, n, (jnp.float32, dtype))
        return out, conv

    def decode(h_t, st, active):
        o, st = hybrid.gdn_decode(mcfg, layer, h_t, st, 1, active)
        return o, st, st["conv"]

    return mcfg.hidden_size, state, prefill, decode


# The state-space mixer's prefill convolves its input unrounded (in the model's type, which on the chip is the window's), so
# under a float32 model its two forms share a bfloat16 window's values only to bfloat16: float32 alone here.
MIXERS = [("mamba", "float32"), ("conv", "float32"), ("conv", "bfloat16"), ("gdn", "float32"), ("gdn", "bfloat16")]


@pytest.mark.parametrize("kind,dtype", MIXERS, ids=[f"{k}-{d}" for k, d in MIXERS])
def test_mixer_decode_form_equals_prefill_form(kind, dtype):
    """Each mixer whole, its window in the state's type: n one-token steps
    from the zero state against the prompt at once, by the output at every
    position and the window left behind, which both forms hold as the
    rounded inputs themselves; layer 0's window and an idle slot's stay as
    they were."""
    dtype = jnp.dtype(dtype)
    D, state, prefill, decode = {"mamba": _mamba, "conv": _conv, "gdn": _gdn}[kind](dtype)
    n = 9
    h = jnp.asarray(np.random.default_rng(n).normal(0, 1, (2, n, D)), jnp.float32)
    out, window = prefill(h, jnp.full((2,), n, jnp.int32))
    assert window.dtype == dtype
    both = jnp.array([True, True])
    for t in range(n):
        o, state, conv_all = decode(h[:, t], state, both)
        np.testing.assert_allclose(np.asarray(o), np.asarray(out[:, t]), rtol=1e-5, atol=2e-6)
    ulp = 1e-5 if dtype == jnp.float32 else 2.0**-7  # an input a float32 ulp apart may round to the next bfloat16
    np.testing.assert_allclose(np.asarray(conv_all[1], np.float32), np.asarray(window, np.float32), rtol=ulp, atol=1e-6)
    assert not np.asarray(conv_all[0]).any()  # the other layer's window untouched
    _, _, held = decode(h[:, 0], state, jnp.array([True, False]))
    assert np.array_equal(np.asarray(held[1, 1]).view(np.uint8), np.asarray(conv_all[1, 1]).view(np.uint8))
    assert not np.array_equal(np.asarray(held[1, 0]), np.asarray(conv_all[1, 0]))
