"""The ``glm_moe_dsa`` family (models/hybrid.py mixer ``mla`` with a low-rank
query and a learned index that picks the ``index_topk`` cached tokens a query
attends to, the shared block, a share of the experts) at a tiny size,
float32, seeded weights, against the benchmark's plain reference
(``benchlib/glm5_reference.py``: attention in its first form for every token,
the selection as a mask over ``s <= t``, no cache, no absorption, no bit
search) by LOGITS, never by sampled tokens. ``index_topk`` is 16 against
contexts of 45-96 tokens, so the selection prunes; positions under 16 select
everything. The tiny model holds experts 4-7 of the 8 its router scores, and
a vocabulary of 500: no multiple of 128.

Tolerances: float32 on both sides, three layers, logits of order 1: 2e-5
(measured under 6e-6). The seeded ``w_qb`` is drawn 4 times wider, so a
query's mass sits on a few tokens: ONE token selected that the reference does
not select (or the reverse) moves a logit by 1e-2 and more, as does a rotary
key one position off, an index key without its norm's bias, or the shared
block left out; the same forward in bfloat16 misses the reference by 3e-2."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_glm5_util as gu  # noqa: E402

from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import decode_step, fresh_cache, prefill_into_slot, program_logits, with_counts  # noqa: E402

TOL = 2e-5
PSZ, WP = 8, 12


def _reference():
    gu.load_run()
    from benchlib import glm5_reference

    return glm5_reference


@pytest.fixture(scope="module")
def model():
    cfg = gu.tiny_model(held=4, first=4)
    return cfg, gu.model_config(cfg), gu.make_params(cfg, 11)


def _interpreted(monkeypatch):
    import areal_tpu.ops.paged_kv_write as pkw
    import areal_tpu.ops.paged_latent_attention as pla

    monkeypatch.setattr(pla, "paged_latent_attention_stacked", functools.partial(pla.paged_latent_attention_stacked, interpret=True))
    monkeypatch.setattr(pla, "paged_index_scores_stacked", functools.partial(pla.paged_index_scores_stacked, interpret=True))
    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))


def test_prefill_forward_agrees_with_the_reference_where_the_selection_prunes(model):
    cfg, mcfg, params = model
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 77)
    ref = _reference()
    want = ref.logits(params, cfg, ids)
    assert want.shape == (77, 500) and want.std() > 0.05
    got = program_logits(mcfg, params, ids)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # the selection is no formality here: with index_topk past the context (everything selected) the logits differ
    loose = ref.logits(params, {**cfg, "index_topk": 128}, ids)
    assert np.abs(loose[:16] - want[:16]).max() < TOL and np.abs(loose[40:] - want[40:]).max() > 1e-2
    # and bfloat16 where float32 is stated would not pass: the tolerance can tell
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    hidden, *_ = hybrid.forward_prefill(half, gu.model_config(cfg, "bfloat16"), jnp.asarray(ids)[None], jnp.ones((1, 77), jnp.int32))
    assert np.abs(np.asarray(hybrid.compute_logits(half, mcfg, hidden)[0], np.float32) - want).max() > 100 * TOL


@pytest.mark.parametrize("blocks", [(16, 256), (32, 1024)], ids=["4x4-blocks", "2x2-blocks"])
def test_the_blocked_prompt_pass_is_the_same_in_any_blocks(model, blocks, monkeypatch):
    """Two prompts of 64 tokens in one block of queries and keys, and cut
    into blocks of 16 or 32 of each by the byte rule: the running softmax
    over key blocks, the key blocks past the diagonal never visited, the
    selection made a query block at a time (index_topk 16: it prunes)."""
    cfg, mcfg, params = model
    ids = jnp.asarray(np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, 64)))
    seg = jnp.ones((2, 64), jnp.int32)
    assert hybrid.prefill_blocks(mcfg, 64) == (64, 64) and hybrid.prefill_blocks(mcfg, 96) == (32, 32)  # blocks divide the prompt
    # the shapes the cell runs: 64 heads against 2,048 keys -> 256 queries a block; 32 heads at 1,024: one block, as before the blocks
    big = hybrid.HybridConfig.from_hf_dict({**{k: v for k, v in cfg.items() if k != "assumed"}, "num_attention_heads": 64, "num_key_value_heads": 64})
    assert hybrid.prefill_blocks(big, 16384) == (256, 2048) and hybrid.prefill_blocks(big, 6144) == (256, 2048)
    assert hybrid.prefill_blocks(hybrid.HybridConfig.from_hf_dict({**{k: v for k, v in cfg.items() if k != "assumed"}, "num_attention_heads": 32, "num_key_value_heads": 32}), 1024) == (1024, 1024)
    whole = hybrid.forward_prefill(params, mcfg, ids, seg)
    monkeypatch.setattr(hybrid, "_PREFILL_KEY_BLOCK", blocks[0])
    monkeypatch.setattr(hybrid, "_PREFILL_LOGIT_BYTES", blocks[1] * 4 * mcfg.num_heads)
    tq, tk = hybrid.prefill_blocks(mcfg, 64)
    assert tk == blocks[0] and tq * tk == blocks[1] and 64 // tq > 1 and 64 // tk > 1
    cut = hybrid.forward_prefill(params, mcfg, ids, seg)
    for a, b in zip(whole[:3], cut[:3]):  # hidden, latent rows, index keys
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6, rtol=0)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_decode_through_both_pools_agrees_with_the_reference(model, use_kernel, monkeypatch):
    """Two prompts (11 tokens: under index_topk; 37: over it) prefilled into
    the latent pool AND the index pool, then 40 decode steps each through the
    pages (the gather path, or the three Pallas launches under the
    interpreter): every step's logits against the reference's full forward.
    The first slot crosses index_topk while decoding. A third slot holds no
    request: its pages stay zero and it counts for nothing."""
    if use_kernel:
        _interpreted(monkeypatch)
    cfg, mcfg, params = model
    ref = _reference()
    rng = np.random.default_rng(3)
    plens, new = (11, 37), 40
    seqs = [rng.integers(0, cfg["vocab_size"], p + new) for p in plens]
    want = [ref.logits(params, cfg, s) for s in seqs]
    S = 3
    assert mcfg.kv_pools == {"k": (1, 256), "idx": (1, 128)} and mcfg.index_topk == 16
    cache, pt = fresh_cache(mcfg, S, WP, PSZ)
    assert {n: a.shape for n, a in cache.items()} == {"k": (3, 1, S * WP + 1, PSZ, 256), "idx": (3, 1, S * WP + 1, PSZ, 128)}
    pt[2] = 0
    cache = with_counts(mcfg, prefill_into_slot(mcfg, params, cache, pt, [(i, seqs[i][:p]) for i, p in enumerate(plens)], 40, PSZ))
    step, pt, active = decode_step(mcfg, PSZ, use_kernel), jnp.asarray(pt), jnp.array([True, True, False])
    worst = 0.0
    for t in range(new):
        tok = jnp.array([seqs[0][plens[0] - 1 + t], seqs[1][plens[1] - 1 + t], 0])
        pos = jnp.array([plens[0] - 1 + t, plens[1] - 1 + t, 0])
        logits, cache = step(params, tok, pos, cache, pt, active)
        logits = np.asarray(logits)
        for i in range(2):
            worst = max(worst, np.abs(logits[i] - want[i][plens[i] - 1 + t]).max())
    assert worst < TOL, worst
    # counted on the device: a live slot's cached tokens a step and layer are scored (and fetched: the masked form);
    # min(index_topk, cached) of them are selected, counted from the selection itself
    cached = [p + t for p in plens for t in range(new)]
    assert np.asarray(cache["index_tokens_scored"]).tolist() == [sum(cached)] * 3
    assert np.asarray(cache["latent_tokens_read"]).tolist() == [sum(cached)] * 3
    assert np.asarray(cache["latent_tokens_selected"]).tolist() == [sum(min(16, n) for n in cached)] * 3
    assert not np.asarray(cache["idx"])[:, 0, 2 * WP + 1 :].any()  # the empty slot wrote no key


def test_select_top_is_exact_and_breaks_ties_by_position():
    """The k-th largest score found bit by bit against a sort: random
    float32 scores (negative, zero, subnormal and equal ones among them),
    every row with its own number of valid positions; between equal scores
    the lower position is in, as ``jax.lax.top_k`` orders them."""
    rng = np.random.default_rng(5)
    scores = rng.standard_normal((9, 200)).astype(np.float32)
    scores[0, :] = 0.0  # all equal: the first k
    scores[1, ::3] = scores[1, 0]  # a third equal
    scores[2, :50] = -np.abs(scores[2, :50]) * 1e-42  # negative subnormals
    scores[3] = np.round(scores[3])  # a few distinct values, many ties
    n_valid = np.array([200, 200, 200, 200, 7, 16, 17, 120, 0])
    valid = np.arange(200)[None, :] < n_valid[:, None]
    got = np.asarray(hybrid.select_top(jnp.asarray(scores), jnp.asarray(valid), 16))
    for r in range(9):
        k = min(16, n_valid[r])
        order = np.argsort(-scores[r, : n_valid[r]], kind="stable")[:k]  # equal scores: lower position first
        want = np.zeros(200, bool)
        want[order] = True
        assert got[r].sum() == k and np.array_equal(got[r], want), r
    ref = _reference()
    pos = jnp.asarray(n_valid[:8] - 1)
    assert np.array_equal(np.asarray(ref.select(jnp.asarray(scores[:8]), pos, 16)), got[:8])  # the reference's rule is the same rule


def test_the_shares_of_two_ranks_add_up_to_the_uncut_layer():
    """8 experts over 2 ranks, 4 each (the cell: 256 over 16): the layer's
    FFN with rank r's experts (router and bias whole, the shared expert on
    every rank), summed over the ranks with the shared expert counted once,
    is the uncut reference's layer; what every rank computes alike (the
    attention, the index, the norms) is the same on each. The program's
    share and the reference's share, both; float32 rounding."""
    ref = _reference()
    whole = gu.tiny_model(held=8, experts=8, layers=2)
    params = gu.make_params(whole, 17)
    lp = {k: v[0] for k, v in params["mla_moe"].items()}
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (37, 64), jnp.float32)
    d = ref.dims(whole)
    kw = dict(eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"])
    uncut = np.asarray(ref._expert_ffn(x, lp, e0=0, **kw)[0] - x)
    by_program, by_reference = np.zeros_like(uncut), np.zeros_like(uncut)
    shared = None
    for rank in range(2):
        cfg_r = ref.share_of(whole, rank, 2)
        assert (cfg_r["n_routed_experts"], cfg_r["assumed"]["router_experts"], cfg_r["assumed"]["expert_first"]) == (4, 8, 4 * rank)
        lp_r = {k: (v[4 * rank : 4 * rank + 4] if k.startswith("we_") else v) for k, v in lp.items()}
        routed = np.asarray(ref._expert_ffn(x, lp_r, e0=4 * rank, shared=False, **kw)[0] - x)
        with_shared = np.asarray(ref._expert_ffn(x, lp_r, e0=4 * rank, **kw)[0] - x)
        shared = with_shared - routed if shared is None else shared
        by_reference += routed
        mcfg = gu.model_config(cfg_r)
        assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (4, 8, 4 * rank)
        out, load = hybrid._ffn(mcfg, "moe", lp_r, x)
        by_program += np.asarray(out - x) - shared
        assert load.shape == (8,) and int(load.sum()) == 37 * 3
        # what every rank computes alike: the whole attention sublayer (the index and the selection in it)
        params_r = {**params, "mla_moe": {k: (v[:, 4 * rank : 4 * rank + 4] if k.startswith("we_") else v) for k, v in params["mla_moe"].items()}}
        ids = np.random.default_rng(9).integers(0, 500, 40)
        first = ref.hidden_states(params_r, cfg_r, jnp.asarray(ids), layers=1)  # the dense layer: no experts in it
        np.testing.assert_allclose(np.asarray(first), np.asarray(ref.hidden_states(params, whole, jnp.asarray(ids), layers=1)), atol=0, rtol=0)
    assert np.abs(uncut - shared).max() > 0.01 and np.abs(shared).max() > 0.01  # both parts are there to be lost
    np.testing.assert_allclose(by_reference + shared, uncut, atol=2e-6, rtol=0)
    np.testing.assert_allclose(by_program + shared, uncut, atol=2e-6, rtol=0)


def test_a_long_prompts_feed_forward_rows_go_through_in_blocks(model, monkeypatch):
    """The rule from shapes (``ffn_block_rows``) and its result: the expert
    block and the dense block over 96 rows at once and 32 at a time."""
    cfg, mcfg, params = model
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(7), (1, 96, 64), jnp.float32)
    live = jnp.arange(96)[None] < 90
    # at the cells' sizes: a prompt pass of 8 x 1,024 rows at hidden 2,048 top-6 goes through at once, 16k rows of hidden 6,144 top-8 in 2,048s
    kanana = hybrid.HybridConfig(vocab_size=8, hidden_size=2048, intermediate_size=6144, layer_types=("mla",), num_heads=32, num_kv_heads=32, num_experts=16, num_experts_per_tok=6)
    glm = hybrid.HybridConfig(vocab_size=8, hidden_size=6144, intermediate_size=12288, layer_types=("mla",), num_heads=64, num_kv_heads=64, num_experts=16, num_experts_per_tok=8)
    assert hybrid.ffn_block_rows(kanana, "moe", 8192) == 8192 and hybrid.ffn_block_rows(kanana, "dense", 8192) == 8192
    assert hybrid.ffn_block_rows(glm, "moe", 16384) == 2048 and hybrid.ffn_block_rows(glm, "dense", 16384) == 8192
    for ffn, stack in (("moe", "mla_moe"), ("dense", "mla")):
        lp = {k: v[0] for k, v in params[stack].items()}
        once, load = hybrid._ffn(mcfg, ffn, lp, x, live)
        assert hybrid.ffn_block_rows(mcfg, ffn, 96) == 96
        wide = mcfg.num_experts_per_tok * mcfg.hidden_size if ffn == "moe" else 2 * mcfg.intermediate_size
        monkeypatch.setitem(hybrid._FFN_BYTES, ffn, 40 * wide * 4)  # room for 40 rows: blocks of 32
        assert hybrid.ffn_block_rows(mcfg, ffn, 96) == 32 and hybrid.ffn_block_rows(mcfg, ffn, 100) == 100
        cut, load_cut = hybrid._ffn(mcfg, ffn, lp, x, live)
        np.testing.assert_allclose(np.asarray(cut), np.asarray(once), atol=2e-6, rtol=0)
        assert load is None and load_cut is None or np.array_equal(np.asarray(load), np.asarray(load_cut)) and int(load.sum()) == 90 * 3


def test_the_published_configuration_loads_and_what_is_not_implemented_is_refused():
    import json

    with open(os.path.join(gu.CHIP, "configs", gu.CONFIG + ".json")) as f:
        cell = json.load(f)
    published = {k: v for k, v in cell.items() if k not in ("source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for")}
    published.update(cell["reduced_from"])  # the config.json as zai-org publishes it
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        hybrid.HybridConfig.from_hf_dict(published)  # an MTP layer is not served as a draft: refused by name
    cfg = hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0})
    assert (cfg.model_type, cfg.num_layers, cfg.num_moe_layers, cfg.num_experts, cfg.vocab_size) == ("glm_moe_dsa", 78, 75, 256, 154880)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (2048, 512, 192, 64, 256)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk, cfg.rope_theta, cfg.sm_scale) == (32, 128, 2048, 1e6, 256**-0.5)
    assert cfg.kv_pools == {"k": (1, 640), "idx": (1, 128)} and cfg.moe_shared_intermediate_size == 2048
    back = hybrid.HybridConfig.from_hf_dict(cfg.to_hf_dict())
    assert back.kv_pools == cfg.kv_pools and back.q_lora_rank == 2048 and back.index_topk == 2048
    names = hybrid.hf_name_map(hybrid.HybridConfig.from_hf_dict({**published, "num_nextn_predict_layers": 0, "num_hidden_layers": 4, "n_routed_experts": 2}))
    assert names["mla/0/w_qa"] == ("model.layers.0.self_attn.q_a_proj.weight", True)
    assert names["mla_moe/0/wi_k_norm_bias"] == ("model.layers.3.self_attn.indexer.k_norm.bias", False)
    assert names["mla_moe/0/wi_qb"] == ("model.layers.3.self_attn.indexer.wq_b.weight", True) and "mla/0/wq" not in names
    base = {k: v for k, v in gu.tiny_model().items() if k != "assumed"}
    for change, msg in (
        ({"n_group": 2}, "group-limited"),
        ({"topk_group": 2}, "group-limited"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_type"),
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"q_lora_rank": None}, "low-rank query"),
        ({"index_head_dim": 64}, "index_head_dim"),
        ({"scoring_func": "softmax"}, "sigmoid"),
    ):
        with pytest.raises(ValueError, match=msg):
            hybrid.HybridConfig.from_hf_dict({**base, **change})
    # deepseek_v3 with a low-rank query and no index is served too: the refusal of PR 37 is gone
    plain = hybrid.HybridConfig.from_hf_dict({**base, "model_type": "deepseek_v3", "index_n_heads": 0, "index_head_dim": 0, "index_topk": 0})
    assert plain.q_lora_rank == 48 and plain.kv_pools == {"k": (1, 256)} and "index_tokens_scored" not in plain.count_shapes
    limits = hybrid.serving_limits(hybrid.HybridConfig.from_hf_dict(base))
    assert limits["reason"] == "latent_pages" and {"prefix_cache", "speculative", "int8_weights", "int8_pages", "sharded"} <= set(limits)
