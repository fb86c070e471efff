"""The seeded weights, and the plain reference against ``models/qwen.py``
at tiny size in float32 (its int8 control is in ``test_chipbench_control``)."""

import numpy as np
import pytest
from chipbench_util import load_run, program_logprobs, tiny_model


@pytest.fixture(scope="module")
def setup():
    load_run()
    import jax.numpy as jnp
    from benchlib import weights

    cfg = tiny_model()
    params = weights.make_params(cfg, 2**31 + 5, jnp.float32)
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], 50).astype(np.int32)
    return cfg, params, ids


def test_weights_follow_the_seed_and_the_published_shapes(setup):
    import jax.numpy as jnp
    from benchlib import weights

    cfg, params, _ = setup
    again = weights.make_params(cfg, 2**31 + 5, jnp.float32)
    other = weights.make_params(cfg, 2**31 + 6, jnp.float32)
    assert bool((params["embed"] == again["embed"]).all()) and not bool((params["embed"] == other["embed"]).all())
    shp = weights.shapes(cfg)
    assert shp["layers"]["wq"] == (2, 64, 64) and shp["layers"]["wk"] == (2, 64, 32) and shp["embed"] == (256, 64)
    assert "lm_head" not in shp  # tied
    assert float(jnp.abs(params["layers"]["bq"]).max()) > 0  # biases are exercised, not zero


def test_reference_agrees_with_the_program_in_float32(setup):
    from benchlib import reference

    cfg, params, ids = setup
    ref = reference.token_logprobs(params, cfg, ids, pad_to=64)
    got = program_logprobs(cfg, params, ids)
    assert ref.shape == got.shape == (49,)
    # float32 on both sides, different summation order: a few ulps of a logprob near -5.5
    assert np.abs(ref - got).max() < 2e-5
    # padding cannot reach a real position
    assert np.abs(reference.token_logprobs(params, cfg, ids, pad_to=128) - ref).max() < 2e-5


def test_reference_adamw_is_the_published_update():
    """``adamw_delta`` (warm-up step at lr 0, then one step at lr) against optax's adamw on the same gradient."""
    import jax.numpy as jnp
    import optax
    from benchlib import reference

    rng = np.random.default_rng(0)
    p0 = rng.normal(0, 0.02, (64, 8)).astype(np.float32)
    g = rng.normal(0, 1e-3, (64, 8)).astype(np.float32) * rng.choice([1.0, 1e-6], (64, 8))  # some under eps
    opt = {"lr": 1e-3, "weight_decay": 0.05, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8}
    tx = optax.adamw(lambda k: jnp.where(k == 0, 0.0, 1e-3), b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.05)
    p, st = jnp.asarray(p0), None
    st = tx.init(p)
    for _ in range(2):
        upd, st = tx.update(jnp.asarray(g), st, p)
        p = optax.apply_updates(p, upd)
    want = np.asarray(p) - p0
    got = reference.adamw_delta(p0, g, opt, 2, jnp.float32)
    assert np.abs(want).max() > 1e-4 and np.abs(got - want).max() < 1e-7 * 10
