"""``ops/kda_prompt_scan.py`` under the Pallas interpreter, at heads of 128 x
128 (the published tile; a block of the launch is whole lane tiles), held to
the recurrence itself (``hybrid.kda_decode_step`` a token at a time) AND to
the XLA form it stands in for (``hybrid.kda_chunked_scan``).

Tolerances: float32 on every side, states of order 1.6-1.9 and reads of
order 0.1: against the recurrence 5e-6 absolute, what
tests/test_solar_open2_model.py holds the XLA form to (measured here: 3.6e-6
on the state, where the XLA form reads 3.1e-6, and 9e-8 on ``o``; against a
float64 recurrence the two forms are 3.7e-6 and 3.1e-6 off); launch against
XLA form 1e-5 on the state (measured 4.9e-6: two roundings of one sum). A
token that enters the state when it should not, a sub-block met through the
wrong first token or a state not carried moves either by 1e-2 and more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid
from areal_tpu.ops.kda_prompt_scan import CHUNK, SUB, kda_prompt_scan

H, K, V = 2, 128, 128
TOL = 5e-6


def _inputs(L=150, heads=H, strong=False, seed=0):
    """q, k L2-normalised [L, heads, K], v [L, heads, V], the log decay a <= 0
    and beta in (0, 2). ``strong``: four channels decay by e^-5 a token, -320
    over a chunk (float32's exp underflows past -88), and every third token
    writes with beta 1.98."""
    rng = np.random.default_rng(seed)
    unit = lambda t: t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = (unit(rng.normal(size=(L, heads, K))) * K**-0.5).astype(np.float32)
    k = unit(rng.normal(size=(L, heads, K))).astype(np.float32)
    v = rng.normal(size=(L, heads, V)).astype(np.float32)
    a = -np.exp(rng.uniform(np.log(1e-3), np.log(5.0), size=(L, heads, K))).astype(np.float32)
    beta = (2.0 / (1.0 + np.exp(-2.0 * rng.normal(size=(L, heads))))).astype(np.float32)
    if strong:
        a[:, :, :4] = -5.0
        beta[::3] = 1.98
    return q, k, v, a, beta


def _by_steps(q, k, v, a, beta, n_state, s0=None):
    """``kda_decode_step`` a token at a time over one slot."""

    def step(s, x):
        q_t, k_t, v_t, a_t, b_t, live = x
        s, o = hybrid.kda_decode_step(s, q_t[None], k_t[None], v_t[None], jnp.exp(a_t)[None], b_t[None], live[None])
        return s, o[0]

    s0 = jnp.zeros((1, *q.shape[1:], v.shape[-1])) if s0 is None else jnp.asarray(s0)[None]
    s, outs = jax.jit(lambda *x: jax.lax.scan(step, s0, x))(q, k, v, a, beta, jnp.arange(q.shape[0]) < n_state)
    return np.asarray(s[0]), np.asarray(outs)


@jax.jit
def _launch(q, k, v, a, beta, n_state, s0):
    return kda_prompt_scan(q, k, v, a, beta, n_state, s0, interpret=True)


@jax.jit
def _xla(q, k, v, a, beta, n_state, s0):
    with jax.default_matmul_precision("highest"):
        return hybrid.kda_chunked_scan(q, k, v, a, beta, n_state, s0)


@pytest.mark.parametrize("strong", [False, True], ids=["published-decays", "log-decay--320-a-chunk"])
@pytest.mark.parametrize("n_state", [150, 97, 128, 0, 200], ids=["all", "inside-a-chunk", "a-chunks-edge", "none", "past-L"])
def test_the_launch_is_the_recurrence_and_the_xla_form(strong, n_state):
    """Two chunks and a part (``L`` 150 is no multiple of 64: the launch pads
    with zeros), the cut at ``n_state`` wherever it falls, beta up to 1.98."""
    args = _inputs(strong=strong)
    s0 = jnp.zeros((H, K, V))
    s_k, o_k = _launch(*args, n_state, s0)
    s_x, o_x = _xla(*args, n_state, s0)
    s_t, o_t = _by_steps(*args, n_state)
    n = min(n_state, 150)
    assert np.isfinite(np.asarray(o_k)).all() and np.isfinite(np.asarray(s_k)).all()  # no inf, no nan: every exponent <= 0
    assert o_k.shape == (150, H, V) and s_k.shape == (H, K, V)
    np.testing.assert_allclose(s_k, s_t, atol=TOL, rtol=0)
    np.testing.assert_allclose(s_k, s_x, atol=2 * TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(o_k)[:n], o_t[:n], atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(o_k)[:n], np.asarray(o_x)[:n], atol=TOL, rtol=0)
    if n:
        assert np.abs(o_t[:n]).max() > 0.1 and np.abs(s_t).max() > 0.1
    else:
        assert not np.asarray(s_k).any()


@pytest.mark.parametrize("heads_per_step", [1, 2, 4], ids=lambda h: f"{h}-heads-a-step")
def test_two_blocks_from_a_carried_state_are_the_prompt_in_one(heads_per_step):
    """``kda_prefill`` walks a long prompt in blocks with the float32 state
    carried: the second block from the first's state is the whole, by the
    recurrence too, however many heads share a grid step (3 heads: 4 a step
    falls back to 1)."""
    heads = 3 if heads_per_step == 4 else 2
    q, k, v, a, beta = _inputs(L=192, heads=heads, strong=True, seed=1)
    scan = jax.jit(lambda *x: kda_prompt_scan(*x, heads_per_step=heads_per_step, interpret=True))
    zero = jnp.zeros((heads, K, V))
    whole_s, whole_o = scan(q, k, v, a, beta, 170, zero)
    s, o1 = scan(q[:128], k[:128], v[:128], a[:128], beta[:128], 170, zero)
    assert np.abs(np.asarray(s)).max() > 0.1
    s, o2 = scan(q[128:], k[128:], v[128:], a[128:], beta[128:], 170 - 128, s)
    s_t, o_t = _by_steps(q, k, v, a, beta, 170)
    np.testing.assert_allclose(s, whole_s, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.concatenate([o1, o2])[:170], np.asarray(whole_o)[:170], atol=TOL, rtol=0)
    np.testing.assert_allclose(s, s_t, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(whole_o)[:170], o_t[:170], atol=TOL, rtol=0)


def test_a_state_handed_in_is_read_and_decayed():
    """One chunk from a state that is not zero: the reads of the carried
    state and its decay a key channel (no write: beta 0 from token 0 on
    would hide neither)."""
    q, k, v, a, beta = _inputs(L=64, seed=2)
    s0 = np.random.default_rng(3).normal(size=(H, K, V)).astype(np.float32)
    s_k, o_k = _launch(q, k, v, a, beta, 50, s0)
    s_t, o_t = _by_steps(q, k, v, a, beta, 50, s0)
    np.testing.assert_allclose(s_k, s_t, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(o_k)[:50], o_t[:50], atol=TOL, rtol=0)


def test_the_launchs_chunk_is_the_models():
    assert (CHUNK, SUB) == (hybrid.KDA_CHUNK, hybrid.KDA_SUB)
