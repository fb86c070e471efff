"""The prompt pass's latent attention as ONE Pallas launch a query block
(``ops/latent_prefill_attention.py mla_prefill_flash``), under the
interpreter, against the XLA loop it replaces on a TPU
(``hybrid.mla_prefill_attend(launch=False)``: the launch's oracle and the CPU
path), on ONE layer's seeded weights at the two cells' head shapes: 32 heads
of 128 + 64 | 128 with the queries handed over as heads and no index (the
kanana cell's), 64 heads of 192 + 64 | 256 with a low-rank query made block by
block and a learned index that selects 64 keys a query (the GLM-5 cell's; 64
against prompts of 256-512 tokens, so the selection prunes, the first 64
queries see fewer keys than it asks for, and the first query sees one).

float32 on both sides: the launch's arithmetic is the loop's term by term
(float32 logits, the same mask value, the probabilities cast to the values'
type, which is float32 here), so the two differ by the order of a float32
sum: 2e-5 on outputs of order 1 (measured under 4e-6). ONE key a query
attends to that the loop masks (or the reverse), a key block past the
diagonal visited, or a block without a pick counted with exp(0) moves an
output by 1e-2 and more. One case runs bfloat16, where the cast rounds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu import models
from areal_tpu.models import hybrid, qwen
from areal_tpu.ops import latent_prefill_attention as flash

TOL = 2e-5
TOPK = 64
HEADS = {
    "32-heads-causal": dict(model_type="deepseek_v3", num_attention_heads=32, qk_nope_head_dim=128, v_head_dim=128, q_lora_rank=None),
    "64-heads-selection": dict(
        model_type="glm_moe_dsa", num_attention_heads=64, qk_nope_head_dim=192, v_head_dim=256, q_lora_rank=96,
        index_n_heads=4, index_head_dim=128, index_topk=TOPK, indexer_rope_interleave=True,
    ),
}


def _config(shape: str, dtype: str = "float32", **over):
    d = dict(
        vocab_size=300, hidden_size=128, intermediate_size=64, moe_intermediate_size=32, num_hidden_layers=1, head_dim=64, kv_lora_rank=64,
        qk_rope_head_dim=64, rms_norm_eps=1e-6, rope_theta=1000000, rope_interleave=True, attention_bias=False, hidden_act="silu",
        first_k_dense_replace=1, moe_layer_freq=1, n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1, tie_word_embeddings=False,
        max_position_embeddings=32768, latent_row_lanes=256, dtype=dtype, **HEADS[shape],
    )
    d.update(num_key_value_heads=d["num_attention_heads"], qk_head_dim=d["qk_nope_head_dim"] + 64, **over)
    return models.config_from_hf_dict(d)


def _inputs(cfg, L: int, seed: int = 0):
    """One latent-attention layer's weights drawn wide enough that a query's
    mass sits on a few keys, and what ``forward_prefill`` hands
    ``mla_prefill_attend`` for a prompt of ``L`` tokens: (layer, q, c, k_r,
    index)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    stack = hybrid.init_params(jax.random.PRNGKey(1), cfg)["mla"]
    layer = {}
    for name, w in stack.items():
        w = w[0]
        if w.ndim == 2:  # a projection: unit-variance outputs, the queries' four times that
            gain = 4.0 if name in ("wq", "w_qb") else 1.0
            w = (gain * w.shape[0] ** -0.5 * jax.random.normal(next(keys), w.shape, jnp.float32)).astype(w.dtype)
        layer[name] = w
    h = jax.random.normal(next(keys), (1, L, cfg.hidden_size), jnp.float32).astype(cfg.jax_dtype)
    positions = jnp.arange(L, dtype=jnp.int32)[None]
    q_nope, q_rope, c, k_r, q_r = hybrid._mla_in(cfg, layer, h, positions, query=not cfg.q_lora_rank)
    q = q_r[0] if cfg.q_lora_rank else (q_nope[0], q_rope[0])
    index = (h[0], hybrid.index_key(cfg, layer, h, positions)[0]) if cfg.index_topk else None
    return layer, q, c[0], k_r[0], index


@functools.lru_cache(maxsize=None)
def _oracle(shape: str, L: int, dtype: str):
    """What the cases of one head shape, prompt length and type share, made
    once a worker: (the configuration, ``_inputs``, the XLA loop's output
    and, where the layer has an index, the loop's without the selection as
    float32). The loop's blocks do not depend on the launch's cap."""
    cfg = _config(shape, dtype)
    inputs = _inputs(cfg, L)
    layer, q, c, k_r, index = inputs
    want = hybrid.mla_prefill_attend(cfg, layer, q, c, k_r, index, launch=False)
    loose = np.asarray(hybrid.mla_prefill_attend(cfg, layer, q, c, k_r, None, launch=False), np.float32) if cfg.index_topk else None
    return cfg, inputs, want, loose


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(flash, "mla_prefill_flash", functools.partial(flash.mla_prefill_flash, interpret=True))
    return monkeypatch


def _crafted_selection(L: int, tq: int, tk: int):
    """bool [L, L] under the causal mask: a query of the first key block
    picks every key it sees; a later one picks its own position, key 3 of
    the first block if it is an even query, and nothing else: every key
    block between the first and the diagonal is left without a pick by
    every query, and the odd queries have none in the first either (their
    running maximum is still the mask value when the diagonal comes)."""
    t, s = np.arange(L)[:, None], np.arange(L)[None, :]
    return jnp.asarray(np.where(t < tk, s <= t, (s == t) | ((s == 3) & (t % 2 == 0))))


CASES = {
    # id: (heads, L, the launch's (queries, keys) cap, dtype)
    "32-heads-causal/one-key-block": ("32-heads-causal", 256, (1024, 1024), "float32"),
    "32-heads-causal/several-key-blocks": ("32-heads-causal", 512, (256, 128), "float32"),
    "32-heads-causal/blocks-by-gcd": ("32-heads-causal", 384, (256, 256), "float32"),
    "64-heads-selection/one-key-block": ("64-heads-selection", 256, (1024, 1024), "float32"),
    "64-heads-selection/several-key-blocks": ("64-heads-selection", 512, (256, 128), "float32"),
    "64-heads-selection/blocks-by-gcd": ("64-heads-selection", 384, (256, 256), "float32"),
    "64-heads-selection/keys-wider-than-queries": ("64-heads-selection", 512, (128, 256), "float32"),
    "64-heads-selection/bfloat16": ("64-heads-selection", 256, (128, 128), "bfloat16"),
    "32-heads-causal/bfloat16": ("32-heads-causal", 256, (128, 128), "bfloat16"),
    "a-key-block-without-a-pick": ("64-heads-selection", 512, (256, 128), "float32"),
    "two-query-block-sizes": ("64-heads-selection", 512, (128, 128), "float32"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_launch_is_the_xla_loop(case, interpreted):
    shape, L, cap, dtype = CASES[case]
    cfg, (layer, q, c, k_r, index), want, loose = _oracle(shape, L, dtype)
    interpreted.setattr(hybrid, "_PREFILL_LAUNCH_BLOCKS", cap)
    interpreted.setattr(hybrid, "_PREFILL_LAUNCH_TOKENS", 256)  # prompts the interpreter holds: the rule's floor of 1,024 is a speed's, not the launch's
    tq, tk = hybrid.prefill_blocks(cfg, L, launch=True)
    assert hybrid.prefill_takes_launch(cfg, L) and L % tq == 0 and L % tk == 0 and tq <= cap[0] and tk <= cap[1]
    tol = TOL if dtype == "float32" else 3e-2  # bfloat16: the probabilities' and the output's rounding, 2^-9 of values of order 1-4
    if case == "a-key-block-without-a-pick":
        # the walk alone under a selection made by hand: what an index's own picks would hardly ever leave
        chosen = _crafted_selection(L, tq, tk)
        qn, qr = hybrid.mla_query(cfg, layer, q, jnp.arange(L, dtype=jnp.int32))
        kv = (c @ layer["w_kvb"]).reshape(L, cfg.num_heads, -1)
        kv_lanes = c @ flash.padded_w_kvb(layer["w_kvb"], cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim)
        assert not np.asarray(chosen[tq:, tk : tq]).any() and not np.asarray(chosen[tq + 1 :: 2, :tk]).any()
        for i in range(L // tq):
            rows = slice(i * tq, (i + 1) * tq)
            by_loop = hybrid.prefill_attend_block(cfg, qn[rows], qr[rows], kv, k_r, i, (tq, tk), chosen[rows])
            got = hybrid.prefill_attend_block(cfg, qn[rows], qr[rows], kv_lanes, k_r, i, (tq, tk), chosen[rows], launch=True)
            assert np.isfinite(np.asarray(got)).all() and float(jnp.abs(by_loop).max()) > 0.1
            np.testing.assert_allclose(np.asarray(got), np.asarray(by_loop), atol=tol, rtol=0)
        return
    got = hybrid.mla_prefill_attend(cfg, layer, q, c, k_r, index, launch=True)
    assert want.shape == (L, cfg.hidden_size) and float(jnp.abs(want.astype(jnp.float32)).max()) > 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=0)
    if cfg.index_topk:  # the selection is no formality: every key up to the query gives another output past the first index_topk queries
        assert np.abs(loose[:TOPK] - np.asarray(want, np.float32)[:TOPK]).max() <= tol
        assert np.abs(loose[2 * TOPK :] - np.asarray(want, np.float32)[2 * TOPK :]).max() > 0.3
    if case == "two-query-block-sizes":
        interpreted.setattr(hybrid, "_PREFILL_LAUNCH_BLOCKS", (256, 256))
        assert hybrid.prefill_blocks(cfg, L, launch=True) == (256, 256)
        again = hybrid.mla_prefill_attend(cfg, layer, q, c, k_r, index, launch=True)
        np.testing.assert_allclose(np.asarray(again), np.asarray(got), atol=TOL, rtol=0)


KERNELCHECK_CASES = ["selection-f32-last-block", "sparse-picks-f32-block2", "causal-f32-keys-wider", "selection-bf16-block1", "causal-bf16-first-block"]


@pytest.mark.parametrize("case", KERNELCHECK_CASES)
def test_one_block_of_the_launch_is_the_whole_softmax(case):
    """``kernelcheck --kernel mla_prefill_flash``: ONE query block's launch
    against the softmax over the whole [H, queries, L] logits in float32
    (no blocks, no running maximum), under a drawn selection, under one
    that picks a query's own position and key 0 alone (every key block
    between them without a pick), and causal; ``--compiled`` runs the two
    cells' shapes on the chip."""
    from areal_tpu.tools import kernelcheck

    assert [c["case"] for c in kernelcheck.REGISTRY["mla_prefill_flash"]()] == KERNELCHECK_CASES
    (result,) = kernelcheck.run_kernel("mla_prefill_flash", case=case)
    assert result["ok"], result


def _published(name: str):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "chip", "configs", name + ".json")) as f:
        cfg = json.load(f)
    hf = {k: v for k, v in cfg.items() if k not in ("source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for")}
    hf.update({k: cfg["assumed"][k] for k in ("router_experts", "expert_first", "latent_row_lanes", "index_norm_eps") if k in cfg["assumed"]})
    return models.config_from_hf_dict({**hf, "dtype": "bfloat16"})


@pytest.mark.parametrize(
    "name, L, takes, blocks",
    [
        # a prompt under 1,024 tokens is one block of the XLA loop, and the probe has that ahead of the launch
        ("kanana-2-30b-a3b-ep8", 256, False, None),
        ("kanana-2-30b-a3b-ep8", 768, False, None),
        ("kanana-2-30b-a3b-ep8", 1024, True, (1024, 1024)),
        ("kanana-2-30b-a3b-ep8", 1280, True, (256, 256)),
        ("glm-5-ep16-d6", 4096, True, (1024, 1024)),
        ("glm-5-ep16-d6", 6144, True, (1024, 1024)),
        ("glm-5-ep16-d6", 8192, True, (1024, 1024)),
        # the index's float32 [queries, L] scores hold the queries to 32 MB
        ("glm-5-ep16-d6", 12288, True, (512, 1024)),
        ("glm-5-ep16-d6", 16384, True, (512, 1024)),
        ("glm-5-ep16-d6", 32768, True, (256, 1024)),
        # a prompt that is no whole lane tiles: the XLA loop
        ("glm-5-ep16-d6", 96, False, None),
        ("kanana-2-30b-a3b-ep8", 1000, False, None),
    ],
)
def test_which_shapes_take_the_launch_and_in_which_blocks(name, L, takes, blocks, monkeypatch):
    cfg = _published(name)
    assert hybrid.prefill_takes_launch(cfg, L) is takes
    if takes:
        assert hybrid.prefill_blocks(cfg, L, launch=True) == blocks
    # the launch is a TPU's: on this backend the program counts no prompt token under it
    assert not hybrid.prefill_attn_launch(cfg, L)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert hybrid.prefill_attn_launch(cfg, L) is takes


def test_a_model_without_latent_attention_counts_nothing_under_the_launch(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # head widths of whole lane tiles do not make a latent-attention layer
    tiny = _config("32-heads-causal", v_head_dim=16)
    assert not hybrid.prefill_takes_launch(tiny, 1024) and not hybrid.prefill_attn_launch(tiny, 1024)
    assert not hybrid.prefill_attn_launch(_published("granite-4.0-h-micro"), 1024)  # attention layers of K/V heads, none latent
    assert not qwen.prefill_attn_launch(object(), 1024)
