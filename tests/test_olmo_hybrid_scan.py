"""The delta rule's chunked scan of ``models/hybrid.py`` against the recurrence
run step by step, and what a padded prompt leaves in a slot (tolerances:
test_olmo_hybrid_model.py, which holds the whole forward to the reference)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()
from benchlib import olmo_hybrid_reference as ref  # noqa: E402

from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import prefill_forward  # noqa: E402


@pytest.mark.parametrize("n_prompt,bucket", [(2, 16), (33, 64), (64, 64)])
def test_a_padded_prompt_leaves_the_state_of_its_last_real_token(n_prompt, bucket):
    """The state and the conv windows a prefill leaves in the slot are those
    after the prompt's tokens before its last one, whatever the bucket: what
    a prefill of exactly those tokens leaves, and the reference's state."""
    cfg = ou.tiny_model()
    mcfg, params = ou.model_config(cfg), ou.make_params(cfg, 7)
    ids = np.random.default_rng(n_prompt).integers(0, cfg["vocab_size"], n_prompt)
    x = jnp.asarray(ids[: n_prompt - 1])[None]
    _, (_, _, exact) = prefill_forward(mcfg)(params, x, jnp.ones_like(x))
    row = np.full((1, bucket), 7, np.int32)
    row[0, :n_prompt] = ids
    seg = (np.arange(bucket)[None] < n_prompt).astype(np.int32)
    _, (_, _, padded) = prefill_forward(mcfg)(params, jnp.asarray(row), jnp.asarray(seg), jnp.asarray([n_prompt - 1]))
    for leaf in ("gdn", "conv"):  # every layer's, to the rounding of another chunk split carried down 6 layers (1e-5 measured)
        assert ou.rel(np.asarray(padded[leaf], np.float64), np.asarray(exact[leaf], np.float64)) < 5e-5
    assert ou.rel(ou.first_state(mcfg, padded, 0), ref.first_layer_state(params, cfg, ids[: n_prompt - 1], pad_to=256)) < 1e-5


@pytest.mark.parametrize("n", [1, 63, 65, 130])
def test_chunked_scan_equals_the_recurrence_at_any_length(n):
    """``gdn_chunked_scan`` against ``gdn_decode_step`` run n times from the
    zero state, at lengths that are no multiple of the chunk; beta up to 2,
    heads that forget in a token beside heads that remember."""
    A, H, K, V = 2, 4, 24, 64
    ks = jax.random.split(jax.random.PRNGKey(n), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (A, n, H, K))) * K**-0.5, unit(jax.random.normal(ks[1], (A, n, H, K)))
    v = jax.random.normal(ks[2], (A, n, H, V))
    g = -jax.random.uniform(ks[3], (A, n, H)) * jnp.array([1e-3, 0.05, 0.5, 4.0])
    beta = 2 * jax.random.uniform(ks[4], (A, n, H))
    n_state = jnp.array([n, max(n - 3, 0)])
    s, o = hybrid.gdn_chunked_scan(q, k, v, g, beta, n_state)
    state = jnp.zeros((A, H, K, V))
    for t in range(n):
        state, o_t = hybrid.gdn_decode_step(state, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t], t < n_state)
        live = np.asarray(t < n_state)
        np.testing.assert_allclose(np.asarray(o[:, t])[live], np.asarray(o_t)[live], rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(state), rtol=1e-4, atol=2e-5)
