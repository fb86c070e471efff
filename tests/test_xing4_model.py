"""The ``xing4_0`` family (models/hybrid.py: the ``mla`` mixer with a low-rank
query under a YaRN-scaled rotary key, experts beside a shared one, on a
residual path of FOUR streams, ``residual_form`` ``mhc``) at a tiny size with
every width's ratio kept, float32, seeded weights, against the benchmark's
plain reference (``benchlib/xing4_reference.py``: streams as an array, the
coefficients and the Sinkhorn rounds as written, attention in its first form,
no cache, no absorption) by LOGITS, never by sampled tokens. YaRN's original
length is 32 at this size: prompts of 20 tokens lie inside it, of 77 past it.

Tolerances: float32 on both sides, six layers, logits of order 1: 2e-5
(measured 3e-6). With the seeded coefficients (gains of 1, ``B_res = 2 I + N(0,
0.5)``) ONE Sinkhorn round for 20, ``H_res`` the identity, plain rotary
frequencies or the softmax scale without YaRN's factor each move a logit by
1e-2 and more; the same forward in bfloat16 misses the reference by 3e-2."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_xing4_util as xu  # noqa: E402
from chipbench_util import CHIP  # noqa: E402

from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import decode_step, fresh_cache, prefill_into_slot, program_logits, with_counts  # noqa: E402

TOL = 2e-5
PSZ, WP = 8, 16
N, D = 4, 56


@pytest.fixture(scope="module")
def model():
    cfg = xu.tiny_model(held=4, first=4)
    return cfg, xu.model_config(cfg), xu.make_params(cfg, 11)


def _catalog() -> dict:
    with open(os.path.join(CHIP, "configs", xu.CONFIG + ".json")) as f:
        cell = json.load(f)
    published = {k: v for k, v in cell.items() if k not in ("source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for")}
    return cell, {**published, **cell["reduced_from"]}  # the config.json as it is published


def test_one_sublayers_coefficients_agree_and_h_res_is_doubly_stochastic_after_20_rounds_not_after_1(model):
    cfg, mcfg, params = model
    ref = xu.reference()
    X = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (2, 19, N * D), jnp.float32)
    for stack, tag in (("mla", "attn"), ("mla_moe", "ffn")):
        lp = {k: v[1] for k, v in params[stack].items()}
        pre, post, res = hybrid.mhc_coefficients(mcfg, lp, tag, X)
        assert pre.shape == (2, 19, N) and post.shape == (2, 19, N) and res.shape == (2, 19, N * N)
        streams = jnp.moveaxis(X.reshape(38, N, D), 1, 0)  # the reference's [n, T, D]
        w_pre, w_post, w_res = ref._coeff_of(streams, lp, tag, ref.dims(cfg))
        for got, want in ((pre, w_pre), (post, w_post), (res, w_res.reshape(38, N * N))):
            np.testing.assert_allclose(np.asarray(got).reshape(want.shape), np.asarray(want), atol=2e-6, rtol=0)
        H = np.asarray(res).reshape(38, N, N)
        # doubly stochastic to the rounds' precision: the columns exactly (a round ends on them), the rows of the
        # typical token within 1e-5; a token whose logits spread widest is still 1e-4 to 6e-3 off after 20 rounds
        # (4,000 draws at these seeded biases: median 1e-6, 99th percentile 4e-4, worst 6e-3; 40 rounds: 3e-4)
        rows = np.abs(H.sum(-1) - 1).max(-1)
        assert np.abs(H.sum(-2) - 1).max() < 1e-5 and np.median(rows) < 1e-5 and rows.max() < 1e-2 and H.min() > 0
        # a token's coefficients move with its streams (the seeded gains are 1, not the papers' 0): nothing here is a constant
        assert np.asarray(pre).std(axis=(0, 1)).min() > 0.05 and H.std(axis=0).min() > 0.01 and 0 < np.asarray(post).min() and np.asarray(post).max() < 2
        # ONE round leaves the typical token's rows 0.2 off 1 (its columns are exact too)
        one = np.asarray(hybrid.mhc_coefficients(xu.model_config({**cfg, "hc_sinkhorn_iters": 1}), lp, tag, X)[2]).reshape(38, N, N)
        assert np.median(np.abs(one.sum(-1) - 1).max(-1)) > 1e-2 and np.abs(one.sum(-2) - 1).max() < 1e-4


def test_the_rounds_unrolled_and_as_a_loop_are_the_same_round():
    """On a TPU the 20 rounds are a static chain (one fused kernel's worth of
    elementwise ops), elsewhere the body of a ``lax.fori_loop`` (XLA:CPU takes
    half a minute to compile the chain): the same round, to the last bit or
    two (XLA:CPU fuses a chain's divisions otherwise than a loop body's: 1.2e-7
    on entries of order 0.3)."""
    m = [[jnp.exp(jax.random.normal(jax.random.PRNGKey(4 * i + j), (37,), jnp.float32) + 2.0 * (i == j)) for j in range(N)] for i in range(N)]
    chain = jax.jit(lambda m: hybrid.sinkhorn_rows(m, 20, 1e-6, unrolled=True))(m)
    loop = jax.jit(lambda m: hybrid.sinkhorn_rows(m, 20, 1e-6, unrolled=False))(m)
    for i in range(N):
        for j in range(N):
            np.testing.assert_allclose(np.asarray(chain[i][j]), np.asarray(loop[i][j]), atol=5e-7, rtol=0)
    rows = sum(np.asarray(chain[0][j]) for j in range(N))
    assert np.abs(rows - 1).max() < 1e-2 and np.median(np.abs(rows - 1)) < 1e-5


@pytest.mark.parametrize("n_tokens", [20, 77], ids=["inside-the-original-length", "past-the-original-length"])
def test_prefill_logits_agree_with_the_reference(model, n_tokens):
    cfg, mcfg, params = model
    ids = np.random.default_rng(n_tokens).integers(0, cfg["vocab_size"], n_tokens)
    ref = xu.reference()
    want = ref.logits(params, cfg, ids)
    assert want.shape == (n_tokens, 500) and want.std() > 0.05
    np.testing.assert_allclose(program_logits(mcfg, params, ids), want, atol=TOL, rtol=0)
    # the mechanisms are no formality at these weights: the reference without each of them reads elsewhere
    res_is_identity = jax.tree.map(lambda a: a, params)
    for stack in ("mla", "mla_moe"):
        for tag in ("attn", "ffn"):
            alpha, bias = params[stack][f"hc_{tag}_alpha"], params[stack][f"hc_{tag}_bias"]
            eye = jnp.broadcast_to(jnp.where(jnp.eye(N, dtype=bool), 30.0, -30.0).reshape(-1), (bias.shape[0], N * N))
            res_is_identity[stack] = {
                **res_is_identity[stack], f"hc_{tag}_alpha": alpha.at[:, 2].set(0.0), f"hc_{tag}_bias": bias.at[:, 2 * N :].set(eye),
            }
    plain_rope = {k: v for k, v in cfg.items() if k != "rope_scaling"}
    for name, other in (
        ("H_res the identity", ref.logits(res_is_identity, cfg, ids)),
        ("one Sinkhorn round", ref.logits(params, {**cfg, "hc_sinkhorn_iters": 1}, ids)),
        ("no YaRN", ref.logits(params, plain_rope, ids)),
    ):
        assert np.abs(other - want).max() > 1e-2, name
    # and bfloat16 where float32 is stated would not pass: the tolerance can tell
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    x = jnp.asarray(ids)[None]
    hidden, *_ = hybrid.forward_prefill(half, xu.model_config(cfg, "bfloat16"), x, jnp.ones_like(x))
    assert np.abs(np.asarray(hybrid.compute_logits(half, mcfg, hidden)[0], np.float32) - want).max() > 100 * TOL


@pytest.mark.parametrize("use_kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_decode_through_the_latent_pages_agrees_with_the_reference(model, use_kernel, monkeypatch):
    """Two prompts (11 tokens: inside YaRN's original length of 32; 45: past
    it) prefilled into the latent pool, then 40 decode steps each through the
    pages (the gather path, or the Pallas launches under the interpreter):
    every step's logits against the reference's full forward. The first slot
    crosses the original length while decoding. A third slot holds no request:
    it counts for nothing."""
    if use_kernel:
        import areal_tpu.ops.paged_kv_write as pkw
        import areal_tpu.ops.paged_latent_attention as pla

        monkeypatch.setattr(pla, "paged_latent_attention_stacked", functools.partial(pla.paged_latent_attention_stacked, interpret=True))
        monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    cfg, mcfg, params = model
    ref = xu.reference()
    rng = np.random.default_rng(3)
    plens, new = (11, 45), 40
    seqs = [rng.integers(0, cfg["vocab_size"], p + new) for p in plens]
    want = [ref.logits(params, cfg, s) for s in seqs]
    S = 3
    assert mcfg.kv_pools == {"k": (1, 256)} and mcfg.kv_groups["full"]["writers"] == tuple(range(6))
    cache, pt = fresh_cache(mcfg, S, WP, PSZ)
    assert {n: a.shape for n, a in cache.items()} == {"k": (6, 1, S * WP + 1, PSZ, 256)}
    pt[2] = 0
    cache = with_counts(mcfg, prefill_into_slot(mcfg, params, cache, pt, [(i, seqs[i][:p]) for i, p in enumerate(plens)], 48, PSZ))
    step, pt, active = decode_step(mcfg, PSZ, use_kernel), jnp.asarray(pt), jnp.array([True, True, False])
    hidden, _ = jax.eval_shape(lambda c: hybrid.forward_decode_paged(params, mcfg, pt[:, 0], pt[:, 0], c, pt, page_size=PSZ, active=active, use_kernel=False), cache)
    assert hidden.shape == (S, D)  # four streams go in, ONE vector a row comes out
    worst = 0.0
    for t in range(new):
        tok = jnp.array([seqs[0][plens[0] - 1 + t], seqs[1][plens[1] - 1 + t], 0])
        pos = jnp.array([plens[0] - 1 + t, plens[1] - 1 + t, 0])
        logits, cache = step(params, tok, pos, cache, pt, active)
        logits = np.asarray(logits)
        for i in range(2):
            worst = max(worst, np.abs(logits[i] - want[i][plens[i] - 1 + t]).max())
    assert worst < TOL, worst
    # counted on the device: 2 live rows x 2 sublayers x 6 layers a step; a live slot's cached tokens a step and layer
    assert np.asarray(cache["mhc_row_sublayers"]).tolist() == [2 * 12 * new]
    assert np.asarray(cache["latent_tokens_read"]).tolist() == [sum(p + t for p in plens for t in range(new))] * 6


def test_the_shares_of_four_ranks_add_up_to_the_uncut_layer():
    """16 experts over 4 ranks, 4 each (the cell: 64 over 4): an expert
    layer's feed-forward block with rank r's experts (router and bias whole,
    the shared expert on every rank), summed over the ranks with the shared
    expert counted once, is the uncut reference's block; what every rank
    computes alike (the stream coefficients, both mixes, the attention, the
    norms) is the same on each and enters once. The program's share and the
    reference's share, both; float32 rounding."""
    ref = xu.reference()
    whole = xu.tiny_model(held=16, experts=16, layers=3)
    params = xu.make_params(whole, 17)
    ids = jnp.asarray(np.random.default_rng(9).integers(0, 500, 37))
    before = ref.streams_after(params, whole, ids, layers=2)  # the streams the first expert layer reads: no experts before it
    uncut = np.asarray(ref.streams_after(params, whole, ids, layers=3))
    uncut_ffn = np.asarray(ref.streams_after(params, whole, ids, layers=3, parts="routed"))
    lp = {k: v[0] for k, v in params["mla_moe"].items()}
    d = ref.dims(whole)
    by_program, by_reference = np.zeros_like(uncut_ffn), np.zeros_like(uncut_ffn)
    shared = None
    for rank in range(4):
        cfg_r = ref.share_of(whole, rank, 4)
        assert (cfg_r["n_routed_experts"], cfg_r["assumed"]["router_experts"], cfg_r["assumed"]["expert_first"]) == (4, 16, 4 * rank)
        params_r = {**params, "mla_moe": {k: (v[:, 4 * rank : 4 * rank + 4] if k.startswith("we_") else v) for k, v in params["mla_moe"].items()}}
        routed = np.asarray(ref.streams_after(params_r, cfg_r, ids, layers=3, parts="routed", shared=False))
        with_shared = np.asarray(ref.streams_after(params_r, cfg_r, ids, layers=3, parts="routed"))
        shared = with_shared - routed if shared is None else shared
        by_reference += routed
        # what every rank computes alike: the two dense layers before, with all of their mixes
        np.testing.assert_array_equal(np.asarray(ref.streams_after(params_r, cfg_r, ids, layers=2)), np.asarray(before))
        # the program's share of the block: the same pre-mix in, the block's output alone out
        mcfg = xu.model_config(cfg_r)
        assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (4, 16, 4 * rank)
        lp_r = {k: (v[4 * rank : 4 * rank + 4] if k.startswith("we_") else v) for k, v in lp.items()}
        after_attn = _after_attention(ref, params, whole, before, d)
        pre, _, _ = hybrid.mhc_coefficients(mcfg, lp_r, "ffn", after_attn)
        out, load = hybrid._ffn(mcfg, "moe", lp_r, hybrid.mhc_pre(mcfg, after_attn, pre), residual=False)
        by_program += np.asarray(out) - shared
        assert load.shape == (16,) and int(load.sum()) == 37 * 4
    assert np.abs(uncut_ffn - shared).max() > 0.01 and np.abs(shared).max() > 0.01  # both parts are there to be lost
    np.testing.assert_allclose(by_reference + shared, uncut_ffn, atol=2e-6, rtol=0)
    np.testing.assert_allclose(by_program + shared, uncut_ffn, atol=2e-6, rtol=0)
    # ... and the mixes counted ONCE carry the summed block into the uncut layer's streams
    pre, post, res = ref._coeff_of(_streams_major(after_attn), lp, "ffn", d)
    np.testing.assert_allclose(np.asarray(ref._post_mix(_streams_major(after_attn), post, res, jnp.asarray(by_program + shared))), uncut, atol=2e-6, rtol=0)


def _streams_major(X):
    """The program's [T, n * D] as the reference's [n, T, D]."""
    return jnp.moveaxis(X.reshape(X.shape[0], N, D), 1, 0)


def _after_attention(ref, params, cfg, before, d):
    """The streams behind the first expert layer's attention sublayer, in the program's layout [T, n * D]."""
    lp = ref.layer_params(params, cfg, 2)
    pre, post, res = ref._coeff_of(before, lp, "attn", d)
    X = ref._post_mix(before, post, res, ref._attention(ref._pre_mix(before, pre), lp, d, ref.yarn_table(cfg)))
    return jnp.moveaxis(X, 0, 1).reshape(X.shape[1], N * D)


def test_the_published_configuration_loads_round_trips_and_what_is_not_implemented_is_refused():
    cell, published = _catalog()
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        hybrid.HybridConfig.from_hf_dict(published)  # the prediction layer is not served as a draft: refused by name
    cfg = hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0})
    assert (cfg.model_type, cfg.num_layers, cfg.num_moe_layers, cfg.num_experts, cfg.vocab_size) == ("xing4_0", 40, 38, 64, 131072)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (768, 512, 128, 64, 128)
    assert (cfg.residual_form, cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp, cfg.stream_width) == ("mhc", 4, 20, 1e-6, (-30.0, 30.0), 14336)
    assert cfg.kv_pools == {"k": (1, 640)} and cfg.moe_shared_intermediate_size == 1024 and cfg.rope_interleave
    assert cfg.kv_groups == {"full": {"pools": ("k",), "writers": tuple(range(40)), "readers": tuple(range(40)), "keeps": None}}
    back = hybrid.HybridConfig.from_hf_dict(cfg.to_hf_dict())
    assert back == cfg
    hf = cfg.to_hf_dict()
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "rope_scaling", "q_lora_rank", "n_routed_experts"):
        assert hf[key] == published[key], key
    # the configuration file as the cell hands it over: the cut, the share and every assumption the program reads
    served = xu.model_config(cell, "bfloat16")
    assert (served.num_layers, served.num_moe_layers, served.num_experts, served.router_width, served.expert_first, served.vocab_size) == (10, 8, 16, 64, 0, 32768)
    assert served.ffns == ("dense",) * 2 + ("moe",) * 8 and served.residual_form == "mhc" and served.latent_lanes == 640
    names = hybrid.hf_name_map(hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0, "num_hidden_layers": 4, "n_routed_experts": 2}))
    assert names["mla/0/w_qa"] == ("model.layers.0.self_attn.q_a_proj.weight", True)
    assert names["mla_moe/1/hc_ffn_phi"] == ("model.layers.3.mlp_hc.phi.weight", True) and "mla/0/wi_k" not in names
    base = {k: v for k, v in xu.tiny_model().items() if k != "assumed"}
    for change, msg in (
        ({"n_group": 2}, "group-limited"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"rope_scaling": {**base["rope_scaling"], "type": "linear"}}, "rope_scaling"),
        ({"rope_scaling": {**base["rope_scaling"], "type": "dynamic"}}, "only type 'yarn'"),
        ({"rope_scaling": {**base["rope_scaling"], "mscale_all_dim": 0}}, "mscale"),
        ({"yarn_form": "transformers"}, "yarn_form"),
        ({"stream_init": "first_stream"}, "stream_init"),
        ({"stream_merge": "learned"}, "stream_merge"),
        ({"hc_per_sublayer": False}, "hc_per_sublayer"),
        ({"hc_norm_weight": "learned"}, "hc_norm_weight"),
        ({"hc_eps_in": "once_before"}, "hc_eps_in"),
        ({"hc_coeff_dtype": "bfloat16"}, "hc_coeff_dtype"),
        ({"index_topk": 16, "index_n_heads": 4, "index_head_dim": 128}, "index"),
        ({"hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters"),
        ({"scoring_func": "softmax"}, "sigmoid"),
    ):
        with pytest.raises(ValueError, match=msg):
            hybrid.HybridConfig.from_hf_dict({**base, **change})
    limits = hybrid.serving_limits(hybrid.HybridConfig.from_hf_dict(base))
    assert limits["reason"] == "latent_pages" and {"prefix_cache", "speculative", "int8_weights", "int8_pages", "sharded"} <= set(limits)
    # a plain rotary embedding is still served: no table, no factor on the softmax scale
    plain = hybrid.HybridConfig.from_hf_dict({k: v for k, v in base.items() if k != "rope_scaling"})
    assert plain.rope_inv_freq is None and plain.sm_scale == 24**-0.5


def test_yarns_table_and_scale_by_hand():
    """``lo`` 10, ``hi`` 23 and 2.0047 from the five published constants: d(b) =
    64 ln(4096 / (2 pi b)) / (2 ln 10000) is 10.47 at 32 turns and 22.51 at 1."""
    _, published = _catalog()
    cfg = hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0})
    table, lo, hi = hybrid.yarn_inv_freq(64, 10000.0, 64.0, 4096.0, 32.0, 1.0)
    assert (lo, hi) == (10, 23) and table == cfg.rope_inv_freq and len(table) == 32
    d32, d1 = (64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000)) for b in (32, 1))
    assert 10 < d32 < 11 and 22 < d1 < 23
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    assert table[:11] == tuple(plain[:11])  # pairs up to lo keep their frequency
    assert all(abs(table[i] - plain[i] / 64) < 1e-12 for i in range(23, 32))  # from hi on a 64th
    assert abs(table[16] - (plain[16] * (1 - 6 / 13) + plain[16] / 64 * (6 / 13))) < 1e-12  # the ramp between: r = (16 - 10) / 13
    gain = (0.1 * math.log(64) + 1) ** 2
    assert abs(gain - 2.0047) < 1e-4 and cfg.sm_scale == pytest.approx(192**-0.5 * gain, rel=1e-12)
    ref_table, ref_gain, ref_lo, ref_hi = xu.reference().yarn_table(published)
    assert (ref_lo, ref_hi) == (10, 23) and ref_gain == pytest.approx(gain) and np.allclose(ref_table, np.asarray(table, np.float32), rtol=1e-6, atol=0)


def _leaf_count(cfg) -> tuple[int, int]:
    """(every parameter, those a token's forward reads beside the embedding's lookup) from the module's own shapes."""
    sizes, shapes = hybrid._stack_sizes(cfg), hybrid._layer_shapes(cfg)
    total = active = 0
    for stack, leaves in shapes.items():
        for leaf, shape in leaves.items():
            n = sizes[stack] * math.prod(shape)
            total += n
            active += n * cfg.num_experts_per_tok // cfg.num_experts if leaf.startswith("we_") else n
    top = cfg.hidden_size + 2 * cfg.vocab_size * cfg.hidden_size
    return total + top, active + cfg.hidden_size + cfg.vocab_size * cfg.hidden_size


def test_the_parameter_counts_by_hand():
    """29.5 B and 3.9 B active as published (29B-A4B), 2.22 B in the cell's cut."""
    cell, published = _catalog()
    cfg = hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0})
    Dm, H = 3584, 32
    attn = Dm * 768 + 768 * H * 192 + Dm * 576 + 512 * H * 256 + H * 128 * Dm  # 2.75 + 4.72 + 2.06 + 4.19 + 14.68 M
    assert round(attn / 1e6, 2) == 28.41
    phi = 2 * (4 * Dm * 24 + 3 + 24)  # two sublayers' Phi, gains and biases: 0.69 M
    norms = 2 * Dm + 768 + 512
    expert = 3 * Dm * 1024  # 11.01 M
    outside = attn + phi + norms + expert + Dm * 64 + 64  # the shared expert, the router and its bias: 40.35 M
    assert round(outside / 1e6, 2) == 40.35 and round(expert / 1e6, 2) == 11.01
    dense = attn + phi + norms + 3 * Dm * 9216  # 128.2 M
    top = 2 * 131072 * Dm + Dm  # 939.5 M
    by_hand = 2 * dense + 38 * (outside + 64 * expert) + top
    total, active = _leaf_count(cfg)
    assert total == by_hand and round(total / 1e9, 1) == 29.5
    assert active == 2 * dense + 38 * (outside + 4 * expert) + top - 131072 * Dm and round(active / 1e9, 1) == 3.9
    served = xu.model_config(cell, "bfloat16")
    cut = 2 * dense + 8 * (outside + 16 * expert) + 2 * 32768 * Dm + Dm
    assert _leaf_count(served)[0] == cut and round(cut / 1e9, 2) == 2.22
    # ... which is what the seeded weights hold, leaf for leaf
    xu.reference()
    from benchlib import xing4_weights

    seeded = xing4_weights.shapes(cell)
    assert sum(math.prod(s) for s in jax.tree.leaves(seeded, is_leaf=lambda x: isinstance(x, tuple))) == cut
    assert {k: tuple(v) for k, v in seeded["mla_moe"].items()} == {k: (8, *s) for k, s in hybrid._layer_shapes(served)["mla_moe"].items()}


def test_four_streams_in_the_prompt_programs_budget_of_bytes():
    """``prefill_row_bytes`` at four streams: the old and the new streams of a
    sublayer, 8 x one residual vector's bytes; a 16k prompt goes alone."""
    cell, _ = _catalog()
    served = xu.model_config(cell, "bfloat16")
    assert hybrid.prefill_row_bytes(served, 16384) == 2 * 4 * 16384 * 3584 * 2 == 939_524_096
    assert hybrid.prefill_row_bytes(served, 256) == 8 * 256 * 3584 * 2  # 14.7 MB: four prompts of 256 to a program
    assert hybrid.ffn_block_rows(served, "moe", 16384) == 8192 and hybrid.ffn_block_rows(served, "dense", 16384) == 8192
    from areal_tpu.inference import decode_programs

    sizes = lambda bucket: tuple(a for a in decode_programs.PREFILL_SIZES if a * hybrid.prefill_row_bytes(served, bucket) <= decode_programs._PREFILL_STREAM_BYTES) or (1,)  # noqa: E731
    assert sizes(256) == (4, 2, 1) and sizes(1024) == (1,) and sizes(16384) == (1,)


# sha256 of str(jaxpr) of a ``sum``-form model's programs AT THE PARENT COMMIT (PR 52, 1585a99), the benchmark's
# kanana-2 rehearsal preset on the CPU's gather path: the residual form is data of the configuration, and a model
# that has one vector a token traces as it did before the streams came. A later PR that changes what these families
# trace on purpose (any edit of the forward's ops) records its own digests here; one that does not must not move them.
_PARENT_JAXPRS = {
    "prefill": "333649b3e57f55d6dbd9be506f5acbb754c3033dd45485b1616db6b7cc64f571",
    "decode": "074f6e3942165d95630f007bee5bbe302797ec57e6460bce7804617ee0232fd8",
}


def sum_form_jaxprs() -> dict[str, str]:
    """{"prefill", "decode"}: str(jaxpr) of the two entry points for the kanana-2 tiny preset (``sum`` form)."""
    import chipbench_kanana2_util as ku

    cfg = ku.tiny_model()
    mcfg = ku.model_config(cfg)
    assert mcfg.residual_form == "sum" and mcfg.stream_width == mcfg.hidden_size
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), mcfg, jnp.float32))
    S, L = 4, 32
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(mcfg, 9, 8, slots=S))
    prefill = jax.make_jaxpr(lambda p, ids, seg: hybrid.forward_prefill(p, mcfg, ids, seg))(params, i32(2, L), i32(2, L))
    decode = jax.make_jaxpr(
        lambda p, ids, pos, c, pt, act: hybrid.forward_decode_paged(p, mcfg, ids, pos, c, pt, page_size=8, active=act, use_kernel=False)
    )(params, i32(S), i32(S), cache, i32(S, 2), jax.ShapeDtypeStruct((S,), jnp.bool_))
    return {"prefill": str(prefill), "decode": str(decode)}


def test_a_sum_form_models_programs_are_the_parents(monkeypatch):
    import hashlib

    # none of the stream code is reached on a residual path of one vector
    for name in ("mhc_coefficients", "mhc_pre", "mhc_post", "sinkhorn_rows"):
        monkeypatch.setattr(hybrid, name, lambda *a, **k: pytest.fail("the sum form reached the stream mixes"))
    got = {k: hashlib.sha256(v.encode()).hexdigest() for k, v in sum_form_jaxprs().items()}
    assert got == _PARENT_JAXPRS
