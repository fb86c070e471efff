"""The ``lfm2_moe`` family of ``models/hybrid.py`` at tiny sizes in float32,
against the benchmark's plain reference (which routes for itself); the
expert layer alone is in tests/test_lfm2_experts.py (no file over 8 tests).

Tolerances: float32 on both sides over 7 layers; logprobs agree to 5e-6
(measured 5e-7 to 1e-6). A tap in the wrong order, gates from the biased
scores or a window one token off moves them by 1e-3 and more."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_lfm2_util as lu  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.models import hybrid, moe  # noqa: E402

ATTENTION_FIRST = ("full_attention", "conv", "conv", "full_attention")


@pytest.mark.parametrize("layer_types,n_dense,n", [(lu.KINDS, 2, 53), (lu.KINDS, 2, 1280), (ATTENTION_FIRST, 1, 40)])
def test_full_forward_matches_reference(layer_types, n_dense, n):
    """53 and 40 tokens take the expert layer's dense form, 1280 its routed
    form (``moe.takes_dense_form``); both agree with the reference's loop,
    and the experts the program picks (read by the flips tool's spy on
    ``moe.expert_ffn``) are the reference's own."""
    cfg = lu.tiny_model(layer_types, n_dense)
    mcfg, params = lu.model_config(cfg), lu.make_params(cfg, 11)
    from benchlib import lfm2_reference

    assert mcfg.ffns == ("dense",) * n_dense + ("moe",) * (len(layer_types) - n_dense)
    assert moe.takes_dense_form(n, 8) == (n != 1280)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], n)
    want = lfm2_reference.token_logprobs(params, cfg, ids, pad_to=1280)
    got = lu.program_logprobs(cfg, params, ids)
    assert np.abs(got - want).max() < 5e-6
    assert np.std(want) > 0.01  # the model says something: not a uniform distribution
    # the experts the program picked are the reference's own picks (float32 on both sides)
    sys.path.insert(0, os.path.join(lu.CHIP, "tools"))
    import lfm2_routing_flips

    lp, chosen = lfm2_routing_flips.program_forward(mcfg)(params, ids)
    assert np.abs(lp - want).max() < 5e-6
    ref, margin = lfm2_reference.routing_of(params, cfg, ids, pad_to=1280)
    assert np.array_equal(np.sort(chosen.reshape(ref.shape), -1), np.sort(ref, -1))
    assert margin.shape == (len(layer_types) - n_dense, n) and (margin >= 0).all() and np.median(margin) > 1e-3


@pytest.mark.parametrize("n", [1, 2, 37])
def test_conv_decode_form_equals_prefill_form(n):
    """The two forms of the short-conv mixer: the whole prompt at once
    against n one-token steps from the zero window, by the mixer's output at
    every position and the window left after n tokens; and the prefill's
    window after fewer tokens than the row holds (what the engine asks for:
    everything before the token decode feeds first)."""
    cfg = lu.tiny_model()
    mcfg, params = lu.model_config(cfg), lu.make_params(cfg, 3)
    layer = jax.tree.map(lambda a: a[1], params["conv_moe"])
    h = jnp.asarray(np.random.default_rng(n).normal(0, 1, (2, n, 64)), jnp.float32)
    out, window = hybrid.conv_prefill(mcfg, layer, h, jnp.full((2,), n, jnp.int32), jnp.float32)
    state = jnp.zeros((3, 2, 2 * 64), jnp.float32)
    active = jnp.array([True, True])
    for t in range(n):
        o, state = hybrid.conv_decode(mcfg, layer, h[:, t], state, 1, active)
        np.testing.assert_allclose(np.asarray(o), np.asarray(out[:, t]), atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(window), atol=1e-6, rtol=0)
    assert not np.asarray(state[0]).any() and not np.asarray(state[2]).any()  # the other layers' windows untouched
    if n > 2:
        _, early = hybrid.conv_prefill(mcfg, layer, h, jnp.array([n - 1, 1], jnp.int32), jnp.float32)
        _, shorter = hybrid.conv_prefill(mcfg, layer, h[:, : n - 1], jnp.array([n - 1, 1], jnp.int32), jnp.float32)
        np.testing.assert_array_equal(np.asarray(early), np.asarray(shorter))
    # a slot that is not active keeps its window bit for bit
    _, held = hybrid.conv_decode(mcfg, layer, h[:, 0], state, 1, jnp.array([True, False]))
    assert np.array_equal(np.asarray(held[1, 1]), np.asarray(state[1, 1])) and not np.array_equal(np.asarray(held[1, 0]), np.asarray(state[1, 0]))


def test_config_from_a_published_config_and_what_it_refuses():
    cfg = lu.tiny_model()
    hf = {k: v for k, v in cfg.items() if k != "assumed"}
    mcfg = models.config_from_hf_dict(hf)
    assert models.family_of(mcfg) is hybrid and mcfg.model_type == "lfm2_moe"
    assert mcfg.layer_types == ("conv", "conv", "attention", "conv", "conv", "attention", "conv")
    assert (mcfg.router_score, mcfg.router_bias, mcfg.router_norm_eps, mcfg.qk_norm, mcfg.rope_theta) == ("sigmoid", True, 1e-6, True, 1e6)
    assert mcfg.has_recurrent_state and mcfg.num_kv_layers == 2 and mcfg.num_moe_layers == 5
    assert mcfg.state_shapes(4) == {"conv": ((5, 4, 2 * 64), jnp.dtype("bfloat16"))}
    assert mcfg.moe_count_shapes == {"moe_load": (5, 8), "moe_touched": (5,), "moe_streamed": (5,)}
    assert models.config_from_hf_dict(mcfg.to_hf_dict()) == mcfg  # a saved checkpoint's config.json reads back
    assert set(hybrid.param_partition_specs(mcfg)) == {"embed", "final_norm", "conv", "attention_moe", "conv_moe"}
    names = hybrid.hf_name_map(mcfg)
    assert names["conv/1/w_gate"] == ("model.layers.1.feed_forward.w1.weight", True)
    assert names["attention_moe/1/we_down/7"] == ("model.layers.5.feed_forward.experts.7.w2.weight", True)
    assert names["conv_moe/2/router_bias"] == ("model.layers.6.feed_forward.expert_bias", False)
    assert names["final_norm"] == ("model.embedding_norm.weight", False)
    for bad, msg in (
        ({"conv_bias": True}, "conv bias"),
        ({"layer_types": ["conv"] * 6 + ["mamba"]}, "only conv and full_attention"),
        ({"rope_scaling": {"rope_type": "yarn"}}, "scaled rotary"),
        ({"num_experts": 0}, "num_experts"),
        ({"num_hidden_layers": 6}, "for 6 layers"),
        ({"model_type": "lfm2_vl"}, "not implemented"),
    ):
        with pytest.raises(ValueError, match=msg):
            models.config_from_hf_dict({**hf, **bad})
