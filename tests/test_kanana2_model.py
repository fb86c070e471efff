"""The ``deepseek_v3`` family (models/hybrid.py mixer ``mla``, the shared
block, a share of the experts) at a tiny size, float32, seeded weights,
against the benchmark's plain reference (``benchlib/kanana2_reference.py``:
attention in its first form for every token, no cache, no absorption) by
LOGITS, never by sampled tokens. The tiny model holds experts 4-7 of the 8
its router scores, and a vocabulary of 500: no multiple of 128.

Tolerances: float32 on both sides, four layers, logits of order 1: 2e-5
(measured under 3e-6). The latent rows a page stores are float32 here, so the
kernel's rounding of probabilities to the pages' type is no rounding. A
rotary key one position off, a latent read without its norm, the shared block
left out or an absent expert's part added moves a logit by 1e-2 and more."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_kanana2_util as ku  # noqa: E402

from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import decode_step, fresh_cache, prefill_into_slot, program_logits, with_counts  # noqa: E402

TOL = 2e-5
PSZ, WP = 8, 8


def _reference():
    ku.load_run()
    from benchlib import kanana2_reference

    return kanana2_reference


@pytest.fixture(scope="module")
def model():
    cfg = ku.tiny_model(held=4, first=4)
    return cfg, ku.model_config(cfg), ku.make_params(cfg, 11)


def _interpreted(monkeypatch):
    import areal_tpu.ops.paged_kv_write as pkw
    import areal_tpu.ops.paged_latent_attention as pla

    monkeypatch.setattr(pla, "paged_latent_attention_stacked", functools.partial(pla.paged_latent_attention_stacked, interpret=True))
    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))


def test_prefill_forward_agrees_with_the_reference(model):
    cfg, mcfg, params = model
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 45)
    want = _reference().logits(params, cfg, ids)
    assert want.shape == (45, 500) and want.std() > 0.05
    np.testing.assert_allclose(program_logits(mcfg, params, ids), want, atol=TOL, rtol=0)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_decode_through_latent_pages_agrees_with_the_reference(model, use_kernel, monkeypatch):
    """Two prompts of different lengths prefilled in the plain form into
    their pages, then every further token decoded in the absorbed form over
    the latent rows (the gather path, and the Pallas kernels under the
    interpreter): each step's logits against the reference's full forward. A
    third slot holds no request: it reads nothing and counts as no load."""
    if use_kernel:
        _interpreted(monkeypatch)
    cfg, mcfg, params = model
    rng = np.random.default_rng(1)
    T, plens, S = 41, (13, 24), 3
    ids = rng.integers(0, cfg["vocab_size"], (2, T)).astype(np.int32)
    want = [_reference().logits(params, cfg, row) for row in ids]
    cache, pt = fresh_cache(mcfg, S, WP, PSZ)
    assert set(cache) == {"k"} and cache["k"].shape == (4, 1, S * WP + 1, PSZ, 256)  # one latent row, no V pool, no state
    pt[1] = 0
    cache = with_counts(mcfg, prefill_into_slot(mcfg, params, cache, pt, [(0, ids[0, : plens[0]]), (2, ids[1, : plens[1]])], 24, PSZ))
    step = decode_step(mcfg, PSZ, use_kernel)
    pos = np.array([plens[0] - 1, 0, plens[1] - 1])
    read = 0
    while (pos[[0, 2]] < T - 1).any():
        active = np.array([pos[0] < T - 1, False, pos[2] < T - 1])
        cur = np.array([ids[0, min(pos[0], T - 1)], 0, ids[1, min(pos[2], T - 1)]], np.int32)
        logits, cache = step(params, jnp.asarray(cur), jnp.asarray(pos), cache, jnp.asarray(pt), jnp.asarray(active))
        logits = np.asarray(logits)
        for slot, row in ((0, 0), (2, 1)):
            if active[slot]:
                np.testing.assert_allclose(logits[slot], want[row][pos[slot]], atol=TOL, rtol=0)
        # the gather path counts the live slots' cached tokens; the launch what it FETCHES: every slot's that holds pages,
        # an ended one's among them until its pages go (since PR 54: ops/paged_attention_q8.py DecodeFetch.tokens)
        read += int(((pos + 1) * (pt[:, 0] != 0 if use_kernel else active)).sum())
        pos = pos + active
    # every latent layer read those cached tokens, this step's own row among them
    assert np.asarray(cache["latent_tokens_read"]).tolist() == [read] * 4
    assert np.asarray(cache["k"])[:, 0, 0].any() == 0 or not use_kernel  # the kernel's writer never touches the trash page


def test_absorbed_form_equals_plain_form_on_one_layer(model):
    """One layer's weights, two functions: the plain form makes every head's
    key and value from the latent (``W_kvb c``); the absorbed form puts
    ``W_UK^T q`` against the latent rows themselves and moves ``W_UV`` across
    the sum. The last token's output over its whole prefix, both ways."""
    _, mcfg, params = model
    layer = {k: v[1] for k, v in params["mla_moe"].items()}
    L = 29
    h = jax.random.normal(jax.random.PRNGKey(3), (1, L, mcfg.hidden_size), jnp.float32)
    positions = jnp.arange(L, dtype=jnp.int32)[None]
    q_nope, q_rope, c, k_r, q_r = hybrid._mla_in(mcfg, layer, h, positions)
    assert q_r is None  # a full-rank query: no low-rank path in this family's published configuration
    plain = hybrid.mla_prefill_attend(mcfg, layer, (q_nope[0], q_rope[0]), c[0], k_r[0])[-1]  # [D]: the output projection is in the block
    rows = hybrid._latent_row(mcfg, c, k_r)[0, :, 0, :]  # what the pages hold: [L, 256], 136 values and zeros
    assert rows.shape == (L, 256) and not np.asarray(rows[:, 136:]).any()
    q = hybrid.mla_absorbed_query(mcfg, layer, q_nope[0, -1:], q_rope[0, -1:])  # [1, H, 256]
    probs = jax.nn.softmax(jnp.einsum("shl,tl->sht", q, rows) * mcfg.sm_scale, axis=-1)
    absorbed = hybrid.mla_absorbed_out(mcfg, layer, jnp.einsum("sht,tr->shr", probs, rows[:, : mcfg.kv_lora_rank]))[0] @ layer["wo"]
    assert float(jnp.abs(plain).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain), atol=2e-6, rtol=0)


def test_the_shares_of_eight_ranks_add_up_to_the_uncut_layer():
    """16 experts over 8 ranks, 2 each: the layer's FFN with rank r's experts
    (router and bias whole, the shared block on every rank), summed over the
    ranks with the shared block counted once, is the uncut reference's layer.
    The program's share and the reference's share, both; float32 rounding."""
    ref = _reference()
    whole = ku.tiny_model(held=16, experts=16, layers=2)
    params = ku.make_params(whole, 17)
    lp = {k: v[0] for k, v in params["mla_moe"].items()}
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (37, 64), jnp.float32)
    d = ref.dims(whole)
    kw = dict(eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"])
    uncut = np.asarray(ref._expert_ffn(x, lp, e0=0, **kw)[0] - x)
    shared = np.asarray(ref._swiglu(ref._rms(x, lp["post_norm"], d["eps"]), lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
    assert np.abs(uncut - shared).max() > 0.01 and np.abs(shared).max() > 0.01  # both parts are there to be lost
    by_program, by_reference = np.zeros_like(uncut), np.zeros_like(uncut)
    for rank in range(8):
        cfg_r = ref.share_of(whole, rank, 8)
        assert (cfg_r["n_routed_experts"], cfg_r["assumed"]["router_experts"], cfg_r["assumed"]["expert_first"]) == (2, 16, 2 * rank)
        lp_r = {k: (v[2 * rank : 2 * rank + 2] if k.startswith("we_") else v) for k, v in lp.items()}
        by_reference += np.asarray(ref._expert_ffn(x, lp_r, e0=2 * rank, shared=False, **kw)[0] - x)
        mcfg = ku.model_config(cfg_r)
        assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (2, 16, 2 * rank)
        out, load = hybrid._ffn(mcfg, "moe", lp_r, x)
        by_program += np.asarray(out - x) - shared
        assert load.shape == (16,) and int(load.sum()) == 37 * 3  # the load keeps the router's width on every rank
    np.testing.assert_allclose(by_reference + shared, uncut, atol=2e-6, rtol=0)
    np.testing.assert_allclose(by_program + shared, uncut, atol=2e-6, rtol=0)


def test_moe_touched_counts_held_experts_only():
    """By hand: a selection bias of 10 on experts 0, 1 and 5 makes every
    token choose exactly those; the stack holds experts 4-7, so each expert
    layer touches ONE held expert a step, not three. Two of three slots are
    live: ``moe_load`` has the router's width and 2 rows on each chosen
    expert."""
    cfg = ku.tiny_model(held=4, first=4)
    mcfg, params = ku.model_config(cfg), ku.make_params(cfg, 3)
    bias = jnp.zeros((3, 8)).at[:, jnp.array([0, 1, 5])].set(10.0)
    params = {**params, "mla_moe": {**params["mla_moe"], "router_bias": bias}}
    S = 3
    cache = paged_kv.init_paged_cache(mcfg, S * WP + 1, PSZ, slots=S)
    cache = {**cache, **{k: jnp.zeros(s, jnp.int32) for k, s in mcfg.count_shapes.items()}}
    assert mcfg.moe_count_shapes == {"moe_load": (3, 8), "moe_touched": (3,), "moe_streamed": (3,)}
    pt = np.zeros((S, WP), np.int32)
    pt[0], pt[1] = np.arange(1, WP + 1), np.arange(WP + 1, 2 * WP + 1)
    for t in range(2):
        _, cache = hybrid.forward_decode_paged(
            params, mcfg, jnp.array([7, 9, 0]), jnp.array([t, t, 0]), cache, jnp.asarray(pt), page_size=PSZ,
            active=jnp.array([True, True, False]), use_kernel=False,
        )
    load = np.asarray(cache["moe_load"])
    assert load.tolist() == [[4, 4, 0, 0, 0, 4, 0, 0]] * 3  # 2 live rows x 2 steps on each of the three chosen
    assert np.asarray(cache["moe_touched"]).tolist() == [2, 2, 2]  # expert 5 alone is here: once a step and layer


def test_configuration_refuses_what_the_module_does_not_implement():
    base = {k: v for k, v in ku.tiny_model().items() if k != "assumed"}
    for change, msg in (
        ({"n_group": 2}, "group-limited"),
        ({"topk_group": 2}, "group-limited"),
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"attention_bias": True}, "biases"),
        ({"scoring_func": "softmax"}, "sigmoid"),
        ({"router_experts": 8, "expert_first": 6}, "not among"),
    ):
        with pytest.raises(ValueError, match=msg):
            hybrid.HybridConfig.from_hf_dict({**base, **change})
    cfg = hybrid.HybridConfig.from_hf_dict({**base, "router_experts": 8, "expert_first": 4})
    assert hybrid.HybridConfig.from_hf_dict(cfg.to_hf_dict() | {"latent_row_lanes": 256}).kv_pools == {"k": (1, 256)}
    assert cfg.latent_lanes == 256 and cfg.latent_dim == 136 and cfg.sm_scale == 24**-0.5  # 136 values in whole lane tiles
    limits = hybrid.serving_limits(cfg)
    assert limits["reason"] == "latent_pages" and {"prefix_cache", "speculative", "int8_weights", "int8_pages", "sharded"} <= set(limits)
    for refused in (hybrid.forward_prefill_paged, hybrid.forward_verify_paged):
        with pytest.raises(NotImplementedError, match="latent pages"):
            refused()
