"""The ``cohere2_moe`` family of ``models/hybrid.py`` at a tiny size in float32
(one period S S S F; 16 query heads over 4 KV heads of 16, a window of 16
tokens in two-page rings; the router scoring 16 experts, top-4, of which a
share is held, beside four shared experts that are averaged), against the
benchmark's plain reference, whose window is a plain mask over the full
causal softmax and whose rotary embedding turns channel pairs. The engine
(group copy, preemption, the counters) is in tests/test_cohere2_moe_engine.py.

Tolerances: float32 on both sides over 4 layers: logits agree to 2e-5 of a
largest logit near 3 (measured 2e-6). A window one token off, a ring read at
the wrong length, a key rotated at another position, halves rotated where
pairs are published, a second norm in the block, the shared experts summed
and not averaged or a bias in the LayerNorm each move the logits by 1e-2 and
more."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_cohere2_moe_util as cu  # noqa: E402
from chipbench_util import CHIP, load_run  # noqa: E402

load_run()
from benchlib import cohere2_moe_reference as ref  # noqa: E402
from benchlib import cohere2_moe_weights  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import program_logits, through_the_cache  # noqa: E402
from areal_tpu.ops.window_prefill_attention import band_tiles, swa_prefill_flash  # noqa: E402

PSZ = 8
W = cu.WINDOW
TOL = 2e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_layernorm_has_a_weight_and_no_bias():
    cfg = cu.tiny_model()
    mcfg = cu.model_config(cfg)
    assert (mcfg.norm_kind, mcfg.norm_bias, mcfg.block_form, mcfg.rms_norm_eps) == ("layer", False, "parallel", 1e-5)
    x = 3.0 + 2.0 * jax.random.normal(jax.random.PRNGKey(0), (5, 64), jnp.float32)  # a mean to take away
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)
    got = hybrid._norm(mcfg, x, w)
    np.testing.assert_allclose(got, ref.layernorm(x, w, 1e-5), atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(got / w).mean(-1), 0.0, atol=1e-6)
    shapes = hybrid._layer_shapes(mcfg)
    assert set(shapes) == {"swa_moe", "attention_moe"}
    for stack in shapes.values():  # ONE norm a block, no bias, no second norm
        assert "input_norm" in stack and not {"post_norm", "input_norm_bias", "post_norm_bias"} & set(stack)
    params = hybrid.init_params(jax.random.PRNGKey(0), mcfg)
    assert "final_norm_bias" not in params and "lm_head" not in params


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_one_parallel_block_against_the_reference(kind):
    """ONE layer of each kind: ``x + Attn(u) + MoE(u)`` with the one ``u``;
    a serial block over the same weights (the experts reading a norm of ``x +
    Attn``) is another model."""
    cfg = {**cu.tiny_model(), "num_hidden_layers": 1, "layer_types": [kind]}
    params = cu.make_params(cfg, 2)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    want = ref.logits(params, cfg, ids)
    assert np.abs(program_logits(cu.model_config(cfg), params, ids) - want).max() < TOL and np.abs(want).max() > 0.3
    x = params["embed"][ids].astype(jnp.float32)
    lp = ref.layer_params(params, cfg, 0)
    d = ref.dims(cfg)
    u = ref.layernorm(x, lp["input_norm"], d["eps"])
    a = ref.attention(u, lp, heads=16, kv_heads=4, hd=16, window=W if kind == "sliding_attention" else 0, theta=50000.0)
    serial = ref.moe(ref.layernorm(x + a, lp["input_norm"], d["eps"]), lp, top_k=4, norm_topk=True, e0=0, n_shared=4)[0]
    parallel = ref.moe(u, lp, top_k=4, norm_topk=True, e0=0, n_shared=4)[0]
    assert np.abs(np.asarray(serial - parallel)).max() > 1e-2


@pytest.mark.parametrize("n", [5, W, 70])
def test_full_forward_matches_reference(n):
    """Prompts shorter than, equal to and several times the window."""
    cfg = cu.tiny_model()
    params = cu.make_params(cfg, 11)
    ids = np.random.default_rng(n).integers(0, cfg["vocab_size"], n)
    want = ref.logits(params, cfg, ids)
    assert np.abs(program_logits(cu.model_config(cfg), params, ids) - want).max() < TOL and np.abs(want).max() > 0.3
    if n > W:  # the mechanisms are there to be lost: no rotary embedding, a window one token short
        assert np.abs(ref.logits(params, cfg, ids, rope=False) - want).max() > 1e-2
        assert np.abs(ref.logits(params, cfg, ids, window=W - 1) - want).max() > 1e-3


@pytest.mark.parametrize("n_prompt,total,bucket", [(9, 30, 16), (W, 40, 16), (53, 90, 64)], ids=["shorter", "equal", "several-windows"])
def test_prefill_then_paged_decode_through_the_rings_matches_the_reference(n_prompt, total, bucket):
    """A prompt shorter than, equal to and several times the window (the ring
    wraps in the prompt pass), then decode steps past further windows (it
    wraps in decoding): every step's LOGITS are the reference's full
    forward's. The ring of the first layer holds the ROTATED keys of the
    last ``window`` tokens, token t at position t % window, as [evens |
    odds]: read as it lies."""
    cfg = cu.tiny_model()
    mcfg, params = cu.model_config(cfg), cu.make_params(cfg, 3)
    assert mcfg.layer_types == ("swa", "swa", "swa", "attention") and set(mcfg.ffns) == {"moe"}
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], total)
    want = ref.logits(params, cfg, ids)
    got, cache = through_the_cache(mcfg, params, ids, n_prompt, bucket, page_size=PSZ)
    assert np.abs(got - want[n_prompt - 1 :]).max() < TOL
    assert cache["ring_k"].shape == (3, 4, 4, 2, PSZ, 128) and set(cache) == {"k", "v", "ring_k", "ring_v"}
    assert not np.asarray(cache["ring_k"][:, :, 0]).any() and not np.asarray(cache["ring_k"][:, :, 2]).any()  # the other slots' rings
    lp = ref.layer_params(params, cfg, 0)
    u = ref.layernorm(params["embed"][ids].astype(jnp.float32), lp["input_norm"], 1e-5)
    k = np.asarray(ref.rope_pairs((u @ lp["wk"]).reshape(total, 4, 16), 50000.0))  # [T, KH, hd], pairs rotated
    ring = np.asarray(cache["ring_k"][0, :, 1]).reshape(4, 2 * PSZ, 128)
    for r in range(W):
        t = (total - 1) - ((total - 1) - r) % W  # the last token that landed at ring position r
        np.testing.assert_allclose(ring[:, r, :16], np.concatenate([k[t, :, 0::2], k[t, :, 1::2]], axis=-1), atol=2e-6, rtol=0)


def test_banded_prompt_pass_in_both_forms():
    """The window layers' prompt pass as XLA computes it (blocks of ``window``
    queries against two blocks of keys) and under the banded launch
    (interpreted, tiles of 8: three key tiles a query tile): the same
    attention, both equal to the plain mask. A key tile wholly outside a
    query tile's band is never NAMED: poisoned, it reaches no query past it.
    The shape rule takes the launch where a block's logits would not fit."""
    mcfg = cu.model_config(cu.tiny_model())
    rng = np.random.default_rng(2)
    A, L, H, KH, hd = 2, 64, mcfg.num_heads, mcfg.num_kv_heads, 16
    q, k, v = (jnp.asarray(rng.normal(size=(A, L, n, hd)), jnp.float32) for n in (H, KH, KH))
    logits = jnp.einsum("atkgd,askd->akgts", q.reshape(A, L, KH, H // KH, hd), k) * hd**-0.5
    behind = np.arange(L)[:, None] - np.arange(L)[None, :]
    probs = jax.nn.softmax(jnp.where(((behind >= 0) & (behind < W))[None, None, None], logits, -1e30), axis=-1)
    want = np.asarray(jnp.einsum("akgts,askd->atkgd", probs, v).reshape(A, L, H * hd))
    np.testing.assert_allclose(hybrid.swa_attend(mcfg, q, k, v), want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(hybrid.swa_flash_attend(mcfg, q, k, v, interpret=True, edge=8), want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(hybrid.swa_flash_attend(mcfg, q, k, v, interpret=True, edge=16), want, atol=2e-6, rtol=0)
    assert (band_tiles(W, 8), band_tiles(W, 16), band_tiles(4096, 1024), band_tiles(4096, 512)) == (3, 2, 5, 9)
    # key tile 0 (tokens 0-7) lies wholly outside the band of every query from 8 + 15 on: query tiles 3-7 never fetch it
    bad_k, bad_v = k.at[:, :8].set(jnp.nan), v.at[:, :8].set(jnp.nan)
    got = np.asarray(hybrid.swa_flash_attend(mcfg, q, bad_k, bad_v, interpret=True, edge=8))
    np.testing.assert_allclose(got[:, 24:], want[:, 24:], atol=2e-6, rtol=0)
    assert not np.isfinite(got[:, :8]).any()
    short = hybrid.swa_attend(mcfg, q[:, :8], k[:, :8], v[:, :8])  # a prompt under the window is ONE block of its own length
    np.testing.assert_allclose(short, want[:, :8], atol=2e-6, rtol=0)
    with open(os.path.join(CHIP, "configs", cu.CONFIG + ".json")) as f:
        full = cu.model_config(json.load(f), dtype="bfloat16")
    assert not hybrid.swa_prefill_launch(full, 16384)  # off a TPU the XLA form stays
    on_tpu = pytest.MonkeyPatch()
    on_tpu.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert [hybrid.swa_prefill_launch(full, n) for n in (256, 512, 768, 4096, 16384)] == [False, False, True, True, True]
        assert [hybrid.gqa_prefill_launch(full, n) for n in (1024, 1280, 16384)] == [False, True, True]  # the full layer's, as ever: past 512 MB of [H, L, L]
        assert not hybrid.swa_prefill_launch(mcfg, 16384)  # heads of 16: not the kernel's shape
    finally:
        on_tpu.undo()


def test_the_banded_launch_binds_lax_primitives_only():
    """The launch's traced size, as tests/test_paged_decode_budget.py holds
    the paged launch's: ONE ``pallas_call`` a site, its two matmuls, no jitted
    ``jnp`` function inside the body, and an equation count that a rewrite
    with ``jnp`` operators would pass at once."""
    from test_paged_decode_budget import count

    sds = jax.ShapeDtypeStruct
    args = [sds((1, 16384, 128 * 128), jnp.bfloat16), sds((1, 8, 16384, 128), jnp.bfloat16), sds((1, 8, 16384, 128), jnp.bfloat16)]
    jaxpr = jax.make_jaxpr(lambda q, k, v: swa_prefill_flash(q, k, v, heads=128, window=4096, sm_scale=128**-0.5))(*args).jaxpr
    assert count(jaxpr, "pallas_call") == 1 and count(jaxpr, "dot_general") == 2
    kernel = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    assert count(kernel.params["jaxpr"], "jit") + count(kernel.params["jaxpr"], "pjit") == 0
    assert count(jaxpr) <= 90, count(jaxpr)  # 70 as written
    assert kernel.params["grid_mapping"].grid == (1, 128, 16, 5)  # five key tiles a query tile: the band is the grid


def test_the_shares_of_16_ranks_add_up_to_the_uncut_layer():
    """The tiny model's 16 experts over 4 ranks of 4 AND over 16 ranks of 1
    (the cell: 128 over 16 ranks of 8): an expert layer with rank r's experts
    (the router whole, the four shared experts on every rank), summed over
    the ranks with the shared experts' MEAN counted ONCE, is the uncut
    reference's layer. The program's share and the reference's."""
    whole = cu.tiny_model(held=16, experts=16)
    params = cu.make_params(whole, 17)
    lp = {k: v[0] for k, v in params["swa_moe"].items()}
    u = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (37, 64), jnp.float32)
    kw = dict(top_k=4, norm_topk=True, n_shared=4)
    uncut = np.asarray(ref.moe(u, lp, e0=0, **kw)[0])
    shared = uncut - np.asarray(ref.moe(u, lp, e0=0, shared=False, **kw)[0])
    one_by_one = sum(np.asarray(ref._swiglu(u, lp["ws_gate"][:, 64 * j : 64 * (j + 1)], lp["ws_up"][:, 64 * j : 64 * (j + 1)], lp["ws_down"][64 * j : 64 * (j + 1)])) for j in range(4)) / 4
    np.testing.assert_allclose(shared, one_by_one, atol=3e-6, rtol=0)  # the MEAN of four, not their sum
    for ranks in (4, 16):
        per = 16 // ranks
        by_program, by_reference = np.zeros_like(uncut), np.zeros_like(uncut)
        for rank in range(ranks):
            cfg_r = ref.share_of(whole, rank, ranks)
            assert (cfg_r["num_experts"], cfg_r["assumed"]["router_experts"], cfg_r["assumed"]["expert_first"]) == (per, 16, per * rank)
            lp_r = {k: (v[per * rank : per * (rank + 1)] if k.startswith("we_") else v) for k, v in lp.items()}
            by_reference += np.asarray(ref.moe(u, lp_r, e0=per * rank, shared=False, **kw)[0])
            mcfg = cu.model_config(cfg_r)
            assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first, mcfg.moe_shared_mean_of) == (per, 16, per * rank, 4)
            out, load = hybrid._ffn(mcfg, "moe", lp_r, jnp.zeros_like(u), u=u)
            by_program += np.asarray(out) - shared
            assert load.shape == (16,) and int(load.sum()) == 37 * 4
        assert np.abs(uncut - shared).max() > 0.01 and np.abs(shared).max() > 0.01  # both parts are there to be lost
        np.testing.assert_allclose(by_reference + shared, uncut, atol=3e-6, rtol=0)
        np.testing.assert_allclose(by_program + shared, uncut, atol=3e-6, rtol=0)


def _catalog_config():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "command-a-plus-05-2026")["config"]


def test_published_configuration_round_trips_and_what_is_not_implemented_is_refused():
    pub = _catalog_config()
    mcfg = models.config_from_hf_dict(pub)
    assert isinstance(mcfg, hybrid.HybridConfig) and mcfg.model_type == "cohere2_moe" and models.family_of(mcfg) is hybrid
    assert mcfg.layer_types == ("swa", "swa", "swa", "attention") * 8 and mcfg.ffns == ("moe",) * 32
    assert (mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim_, mcfg.sliding_window, mcfg.qk_norm, mcfg.diff_attn) == (128, 8, 128, 4096, False, False)
    assert (mcfg.rope_theta, mcfg.rope_kinds, mcfg.rope_interleave) == (50000.0, ("swa",), True)
    assert mcfg.rotates("swa") and not mcfg.rotates("attention")  # the full layers carry no position
    assert (mcfg.num_experts, mcfg.router_width, mcfg.num_experts_per_tok, mcfg.moe_intermediate_size) == (128, 128, 8, 4096)
    assert (mcfg.moe_shared_intermediate_size, mcfg.moe_shared_mean_of) == (16384, 4)
    assert (mcfg.router_score, mcfg.router_bias, mcfg.norm_topk_prob, mcfg.logits_scaling, mcfg.tie_word_embeddings) == ("sigmoid", False, True, 1.0, True)
    back = mcfg.to_hf_dict()
    unread = {"max_position_embeddings", "prefix_dense_intermediate_size", "prefix_dense_sliding_window_pattern", "tf_legacy_loss"}  # no layer reads them
    assert {k: back[k] for k in pub if k not in unread} == {k: v for k, v in pub.items() if k not in unread}
    assert models.config_from_hf_dict(back) == mcfg
    groups = mcfg.kv_groups
    assert groups["full"]["writers"] == tuple(range(3, 32, 4)) and groups["window"]["writers"] == tuple(i for i in range(32) if i % 4 != 3)
    assert groups["window"]["keeps"] == 4096 and groups["full"]["keeps"] is None and mcfg.ring_pages(128) == 32
    assert not mcfg.has_recurrent_state and mcfg.has_slot_tenant and mcfg.state_shapes(2) == {}
    assert mcfg.count_shapes["window_tokens_read"] == (1,) and mcfg.count_shapes["moe_load"] == (32, 128)
    limits = hybrid.serving_limits(mcfg)
    assert limits["reason"] == "window_rings" and {"prefix_cache", "speculative", "int8_weights", "int8_pages", "sharded"} <= set(limits)
    small = models.config_from_hf_dict({**pub, "num_hidden_layers": 4, "layer_types": pub["layer_types"][:4], "num_experts": 2})
    assert small.ring_shapes(64, 128)["ring_k"] == ((3, 8, 65, 32, 128, 128), jnp.dtype("bfloat16"))  # 16.8 MB a slot and layer
    names = hybrid.hf_name_map(small)
    assert names["swa_moe/1/wq"] == ("model.layers.1.self_attn.q_proj.weight", True) and names["attention_moe/0/input_norm"][0] == "model.layers.3.input_layernorm.weight"
    assert names["swa_moe/2/we_down/1"][0] == "model.layers.2.mlp.experts.1.down_proj.weight"
    assert [names[f"attention_moe/0/ws_gate/s{j}"][0] for j in range(4)] == [f"model.layers.3.mlp.shared_experts.{j}.gate_proj.weight" for j in range(4)]
    assert not any("post" in n or "bias" in n for n, _ in names.values())
    for bad in ({"use_parallel_block": False}, {"use_qk_norm": True}, {"attention_bias": True}, {"first_k_dense_replace": 1}, {"rotary_pct": 0.5},
                {"position_embedding_type": "rope"}, {"expert_selection_fn": "softmax"}, {"shared_expert_combination_strategy": "sum"},
                {"layer_types": ["linear_attention"] * 32}, {"rope_parameters": {"rope_type": "yarn"}}, {"tie_word_embeddings": False}):
        with pytest.raises(ValueError):
            models.config_from_hf_dict({**pub, **bad})


def test_a_checkpoint_round_trips_with_the_four_shared_experts_as_four_blocks(tmp_path):
    from areal_tpu.models import hf

    mcfg = cu.model_config(cu.tiny_model())
    params = hybrid.init_params(jax.random.PRNGKey(4), mcfg)
    hf.save_params_to_hf(params, mcfg, str(tmp_path))
    from safetensors import safe_open

    with safe_open(os.path.join(str(tmp_path), "model.safetensors"), framework="numpy") as f:
        keys = set(f.keys())
        assert {f"model.layers.0.mlp.shared_experts.{j}.down_proj.weight" for j in range(4)} <= keys
        assert f.get_tensor("model.layers.0.mlp.shared_experts.1.gate_proj.weight").shape == (64, 64)
    loaded, _ = hf.load_params_from_hf(str(tmp_path), mcfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parameter_counts_by_hand():
    """218.3 B / 25.0 B active as published (218B-A25B), and the cell's 3.12
    B: by hand from the widths, against the weights' own shapes."""
    pub = _catalog_config()
    D, E, Fe, V = 4096, 128, 4096, 262144
    expert = 3 * D * Fe  # 50.33 M
    attn = 2 * D * 16384 + 2 * D * 1024  # q, o, k, v: 142.6 M
    rest = attn + 4 * expert + D * E + D  # + the four shared experts, the router, the ONE norm: 344.4 M
    assert (round(attn / 1e6, 1), round(4 * expert / 1e6, 1), round(expert / 1e6, 2)) == (142.6, 201.3, 50.33) and 344.4e6 < rest < 344.5e6
    total = 32 * (rest + E * expert) + V * D + D
    active = 32 * (rest + 8 * expert) + V * D + D
    assert (round(total / 1e9, 1), round(active / 1e9, 1)) == (218.3, 25.0)
    whole = {**pub, "assumed": {}}
    assert cohere2_moe_weights.count(whole) == total and cohere2_moe_weights.count(whole, active=True) == active
    with open(os.path.join(CHIP, "configs", cu.CONFIG + ".json")) as f:
        cell = json.load(f)
    held = 4 * (rest + 8 * expert) + 32768 * D + D
    assert cohere2_moe_weights.count(cell) == held and round(held / 1e9, 2) == 3.12 and round(2 * held / 1e9, 2) == 6.25
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), cu.model_config(cell, dtype="bfloat16")))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == held  # the program's own leaves
    assert round(32 * (attn + expert + D * E + D + E * expert + 8 * 0) / 1e9 + V * D / 1e9, 0) == 213  # ONE shared expert: 213B, not the published 218B
    with pytest.raises(AssertionError):
        assert round((32 * (rest + E * 3 * D * 16384) + V * D) / 1e9, 1) == 218.3  # experts of prefix_dense_intermediate_size: four times the size


def test_the_scaled_output_projection_moves_no_other_leaf():
    """``attn_out_init_scale`` (the benchmark's, not the program's): ``wo``
    alone is drawn at that share of the range; every other leaf is the seed's
    own draw, and the program's configuration does not see the key."""
    plain = cu.tiny_model()
    cfg = {**plain, "assumed": {**plain["assumed"], "attn_out_init_scale": 0.125}}
    drawn, scaled = cu.make_params(plain, 11), cu.make_params(cfg, 11)
    for stack in ("swa_moe", "attention_moe"):
        for leaf in drawn[stack]:
            same = np.array_equal(np.asarray(drawn[stack][leaf]), np.asarray(scaled[stack][leaf]))
            assert same == (leaf != "wo"), (stack, leaf)
        np.testing.assert_allclose(np.asarray(scaled[stack]["wo"]), 0.125 * np.asarray(drawn[stack]["wo"]), rtol=1e-6)
    seq = np.random.default_rng(3).integers(0, cfg["vocab_size"], 40)
    assert np.abs(program_logits(cu.model_config(cfg), scaled, seq) - ref.logits(scaled, cfg, seq)).max() < TOL
    assert cu.model_config(cfg) == cu.model_config(plain)
