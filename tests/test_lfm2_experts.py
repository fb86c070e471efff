"""The expert layer of ``models/moe.py`` as the ``lfm2_moe`` family configures
it (sigmoid router with a selection bias, top-3 of 8 experts at tiny sizes,
float32): the router against its formula written out, and both forms of the
expert computation against each other and against a loop over experts.

Tolerances: float32 against float64 by hand: gates to 1e-6, outputs of order
0.1 to 2e-5. Gates from the biased scores differ by 0.1 and more."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_lfm2_util as lu  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.models import moe  # noqa: E402


def _router_by_hand(x, w, bias, k, eps=1e-6):
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w.astype(np.float64))))
    chosen = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(s, chosen, -1)
    return s, picked / (picked.sum(-1, keepdims=True) + eps), chosen


def test_router_selects_by_biased_scores_and_gates_by_unbiased():
    """Biases large enough to change most selections: the experts are the
    top-k of score + bias, the gates the UNBIASED scores over their sum +
    1e-6. Gates taken from the biased scores would differ by 0.1 and more."""
    cfg = lu.model_config(lu.tiny_model())
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 64)).astype(np.float32)
    w = rng.normal(0, 0.3, (64, 8)).astype(np.float32)
    bias = rng.normal(0, 0.5, 8).astype(np.float32)
    scores, gates, chosen = moe.route(jnp.asarray(x), jnp.asarray(w), cfg, jnp.asarray(bias))
    s, want_gates, want_chosen = _router_by_hand(x, w, bias, 3)
    assert np.array_equal(np.asarray(chosen), want_chosen)
    np.testing.assert_allclose(np.asarray(scores), s, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates), want_gates, atol=1e-6)
    _, _, unbiased_choice = _router_by_hand(x, w, 0 * bias, 3)
    assert (np.sort(want_chosen, -1) != np.sort(unbiased_choice, -1)).any(-1).mean() > 0.5  # the bias decides
    biased = np.take_along_axis(s + bias, want_chosen, -1)
    assert np.abs(biased / (biased.sum(-1, keepdims=True) + 1e-6) - np.asarray(gates)).max() > 0.1
    # softmax routing (qwen3_moe) is what it was: no bias, gates = top-k probabilities over their sum
    soft = models.qwen.ModelConfig(num_experts=8, num_experts_per_tok=3, norm_topk_prob=True)
    probs, g2, c2 = moe.route(jnp.asarray(x), jnp.asarray(w), soft)
    p = np.asarray(jax.nn.softmax(jnp.asarray(x) @ jnp.asarray(w), axis=-1))
    top = np.argsort(-p, -1, kind="stable")[:, :3]
    assert np.array_equal(np.asarray(c2), top)
    np.testing.assert_allclose(np.asarray(g2), np.take_along_axis(p, top, -1) / np.take_along_axis(p, top, -1).sum(-1, keepdims=True), atol=1e-6)


def _experts_by_loop(x, layer, gates, chosen):
    out = np.zeros_like(x, dtype=np.float64)
    for t in range(x.shape[0]):
        for g, e in zip(gates[t], chosen[t]):
            a = x[t].astype(np.float64) @ np.asarray(layer["we_gate"][e], np.float64)
            u = x[t].astype(np.float64) @ np.asarray(layer["we_up"][e], np.float64)
            out[t] += g * ((a / (1 + np.exp(-a)) * u) @ np.asarray(layer["we_down"][e], np.float64))
    return out


@pytest.mark.parametrize("case,dtype", [("spread", "float32"), ("one_expert_gets_all", "float32"), ("dead_rows", "float32"), ("spread", "bfloat16"), ("dead_rows", "bfloat16")])
def test_expert_layer_forms_agree_with_a_loop_over_experts(case, dtype, monkeypatch):
    """The dense form (every expert on every row) and the routed form
    (sort, grouped matmuls, gather back) on the same rows, against a loop.
    ``one_expert_gets_all``: the bias sends every row to expert 5 first and
    leaves expert 2 with no row at all. ``dead_rows``: rows of slots with
    no request count as no load and (dense form) get no output. In
    bfloat16 (the served type; rows and weights rounded to it before the
    float64 loop) both forms round the gate and up projections to the rows'
    type: outputs of up to 0.6 then read up to 5e-3 off, 1e-2 is the limit."""
    cfg = lu.model_config(lu.tiny_model(), dtype)
    rng = np.random.default_rng(1)
    T = 48
    x = rng.normal(0, 1, (T, 64)).astype(np.float32)
    layer = {
        "w_router": rng.normal(0, 0.3, (64, 8)).astype(np.float32),
        "router_bias": rng.normal(0, 0.1, 8).astype(np.float32),
        "we_gate": rng.normal(0, 0.1, (8, 64, 48)).astype(np.float32),
        "we_up": rng.normal(0, 0.1, (8, 64, 48)).astype(np.float32),
        "we_down": rng.normal(0, 0.1, (8, 48, 64)).astype(np.float32),
    }
    if case == "one_expert_gets_all":
        layer["router_bias"][5], layer["router_bias"][2] = 10.0, -10.0
    live = None if case != "dead_rows" else jnp.asarray(np.arange(T) % 3 != 0)
    jl = jax.tree.map(lambda a: jnp.asarray(a, cfg.jax_dtype), layer)
    jl["router_bias"] = jnp.asarray(layer["router_bias"])  # a float32 buffer in every type
    jx = jnp.asarray(x, cfg.jax_dtype)
    x, layer = np.asarray(jx, np.float32), {**jax.tree.map(lambda a: np.asarray(a, np.float32), jl)}
    assert moe.takes_dense_form(T, 8) and not moe.takes_dense_form(1025, 8) and not moe.takes_dense_form(1024, 128)
    dense, _, chosen, load = moe.expert_ffn(jx, jl, cfg, live=live)
    monkeypatch.setattr(moe, "DENSE_ROWS", 0)
    routed, _, chosen_r, load_r = moe.expert_ffn(jx, jl, cfg, live=live)
    _, gates, by_hand = _router_by_hand(x, layer["w_router"], layer["router_bias"], 3)
    assert np.array_equal(np.asarray(chosen), by_hand) and np.array_equal(np.asarray(chosen_r), by_hand)
    want = _experts_by_loop(x, layer, gates, by_hand)
    counts = np.bincount(by_hand.reshape(-1), minlength=8)
    if case == "one_expert_gets_all":
        assert counts[5] == T and counts[2] == 0
    if live is not None:
        keep = np.asarray(live)
        want = want * keep[:, None]
        counts = np.bincount(by_hand[keep].reshape(-1), minlength=8)
    assert np.array_equal(np.asarray(load), counts) and np.array_equal(np.asarray(load_r), counts)
    tol = 2e-5 if dtype == "float32" else 1e-2
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(dense), want, atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(routed), want, atol=tol, rtol=0)
