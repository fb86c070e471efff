"""The ``glm_moe_dsa`` family's latent attention with a low-rank query and its
expert layer against a published implementation: a tiny random
``DeepseekV3ForCausalLM`` of HF transformers WITH ``q_lora_rank`` (the block
GLM-5 builds on: ``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``; interleaved
rotary pairs, one leading dense layer, 8 sigmoid-routed experts with a
selection bias, top-3, one shared expert) saved as a checkpoint, loaded through
``models/hf.py`` by the family's name map, and compared by logits in float32:
the program's prefill forward as ``deepseek_v3`` (no index), and as
``glm_moe_dsa`` with seeded index weights whose ``index_topk`` is past the
context (the selection inactive: everything is selected, and the index must
not touch the result), beside the benchmark's plain reference
(``benchlib/glm5_reference.py``). The installed transformers has no
``glm_moe_dsa``: the index itself is held to the equations by hand on four
tokens.

Tolerance: float32 on all sides over three layers; logits of order 1 agree
to 5e-5 (measured 3.5e-5 against torch's matmuls: the low-rank query's norm
divides by an RMS that carries one more matmul's rounding than
tests/test_kanana2_hf_parity.py's 2e-5 has); a query read without its low-rank norm, halves rotated where the
checkpoint holds pairs, or a dropped shared expert move them by 1e-2 and
more."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))


TOL = 5e-5


def _tiny():
    import chipbench_glm5_util as gu

    return {k: v for k, v in gu.tiny_model(held=8).items() if k != "assumed"}


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3Config"):
        pytest.skip("this transformers has no deepseek_v3")
    d = {k: v for k, v in _tiny().items() if not k.startswith("index") and k not in ("rope_parameters", "num_nextn_predict_layers")}
    d.update(model_type="deepseek_v3", rope_theta=1000000, rope_scaling=None)
    hf_cfg = transformers.DeepseekV3Config(**{k: v for k, v in d.items() if k != "model_type"})
    assert hf_cfg.q_lora_rank == 48
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(hf_cfg).eval().to(torch.float32)
    with torch.no_grad():  # norms start at 1 and the bias at 0: move them, or dropping one would not show
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(0.1 * torch.randn_like(p))
            elif p.ndim == 2:
                p.copy_(0.08 * torch.randn_like(p))
        for name, b in model.named_buffers():
            if name.endswith("e_score_correction_bias"):
                b.copy_(0.05 * torch.randn_like(b))
    path = tmp_path_factory.mktemp("dsv3q")
    model.save_pretrained(str(path), safe_serialization=True)
    ids = np.random.default_rng(0).integers(0, d["vocab_size"], (2, 23))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    return str(path), ids, want


def _load(path):
    import jax.numpy as jnp

    from areal_tpu import models
    from areal_tpu.models import hybrid
    from areal_tpu.models.hf import load_params_from_hf

    cfg = hybrid.serving_config(models.config_from_hf_path(path), "float32")
    params, _ = load_params_from_hf(path, cfg, dtype=jnp.float32)
    return cfg, params


def _with_index(params, seed: int = 5):
    """The loaded ``deepseek_v3`` tree with seeded index weights beside its attention's, as ``glm_moe_dsa`` stacks them."""
    import jax
    import jax.numpy as jnp

    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in params.items()}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    for stack in ("mla", "mla_moe"):
        n = out[stack]["w_qa"].shape[0]
        out[stack].update(
            wi_qb=0.1 * jax.random.normal(next(keys), (n, 48, 4 * 128), jnp.float32),
            wi_k=0.1 * jax.random.normal(next(keys), (n, 64, 128), jnp.float32),
            wi_k_norm=1 + 0.1 * jax.random.normal(next(keys), (n, 128), jnp.float32),
            wi_k_norm_bias=0.1 * jax.random.normal(next(keys), (n, 128), jnp.float32),
            wi_w=0.1 * jax.random.normal(next(keys), (n, 64, 4), jnp.float32),
        )
    return out


def test_the_name_map_loads_the_low_rank_query(checkpoint):
    from areal_tpu.models import hybrid

    path, _, _ = checkpoint
    cfg, params = _load(path)
    assert isinstance(cfg, hybrid.HybridConfig) and cfg.model_type == "deepseek_v3" and cfg.q_lora_rank == 48 and cfg.index_topk == 0
    assert cfg.ffns == ("dense", "moe", "moe") and cfg.moe_shared_intermediate_size == 32
    assert params["mla"]["w_qa"].shape == (1, 64, 48) and params["mla"]["q_a_norm"].shape == (1, 48) and params["mla"]["w_qb"].shape == (1, 48, 4 * 24)
    assert "wq" not in params["mla"] and "wi_k" not in params["mla"]
    from safetensors import safe_open

    with safe_open(os.path.join(path, "model.safetensors"), "np") as f:
        assert set(f.keys()) == {name for name, _ in hybrid.hf_name_map(cfg).values()}  # nothing published is left unread


def test_program_prefill_agrees_with_transformers(checkpoint):
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    path, ids, want = checkpoint
    cfg, params = _load(path)
    x = jnp.asarray(ids)
    hidden, ks, vs, _ = hybrid.forward_prefill(params, cfg, x, jnp.ones_like(x))
    assert vs is None and ks.shape == (3, 2, 23, 1, 256) and want.std() > 0.3
    np.testing.assert_allclose(np.asarray(hybrid.compute_logits(params, cfg, hidden)), want, atol=TOL, rtol=0)
    # the same weights under an index whose top-k is past the context: the selection is inactive and must not touch the result
    glm = hybrid.HybridConfig.from_hf_dict({**_tiny(), "index_topk": 64, "dtype": "float32"})
    hidden, ks, idx, _ = hybrid.forward_prefill(_with_index(params), glm, x, jnp.ones_like(x))
    assert idx.shape == (3, 2, 23, 1, 128) and float(np.abs(np.asarray(idx)).min(axis=-1).max()) > 0
    np.testing.assert_allclose(np.asarray(hybrid.compute_logits(params, glm, hidden)), want, atol=TOL, rtol=0)


def test_reference_agrees_with_transformers_where_the_selection_is_inactive(checkpoint):
    from chipbench_util import load_run

    load_run()
    from benchlib import glm5_reference

    path, ids, want = checkpoint
    _, params = _load(path)
    d = {**_tiny(), "index_topk": 64}
    for row, w in zip(ids, want):
        np.testing.assert_allclose(glm5_reference.logits(_with_index(params), d, row), w, atol=TOL, rtol=0)


def test_the_index_by_hand_on_four_tokens():
    """I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) and S_t = the min(2, t +
    1) best s <= t, on numbers small enough to check on paper: 2 index heads
    of 4 values, 4 tokens, index_topk 2. The program's scoring and selection,
    and the reference's selection rule, against the table below."""
    import jax.numpy as jnp

    from chipbench_util import load_run

    from areal_tpu.models import hybrid

    load_run()
    from benchlib import glm5_reference

    k = np.array([[1, 0, 0, 0], [0, 1, 0, 0], [1, 1, 0, 0], [0, 0, 1, 0]], np.float32)  # one key a token
    q = np.zeros((4, 2, 4), np.float32)
    q[:, 0] = [[1, 0, 0, 0], [1, 2, 0, 0], [-1, 3, 0, 0], [2, 2, 1, 0]]  # head 0
    q[:, 1] = [[0, 1, 0, 0], [0, -1, 0, 0], [1, 0, 0, 0], [0, 0, -5, 0]]  # head 1
    w = np.array([[1, 1], [1, 2], [2, -1], [1, 1]], np.float32)
    # head 0 dots q.k over s:  t0: 1 0 1 0;  t1: 1 2 3 0;  t2: -1 3 2 0;  t3: 2 2 4 1
    # head 1 dots:             t0: 0 1 1 0;  t1: 0 -1 -1 0; t2: 1 0 1 0;  t3: 0 0 0 -5
    # I = w0 relu(h0) + w1 relu(h1):
    want = np.array([[1, 1, 2, 0], [1, 2, 3, 0], [-1, 6, 3, 0], [2, 2, 4, 1]], np.float32)
    got = np.asarray(hybrid.index_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(k)))
    np.testing.assert_array_equal(got, want)
    causal = np.tril(np.ones((4, 4), bool))
    # S_0 = {0}; S_1 = {0, 1}; S_2: scores -1 6 3 -> {1, 2}; S_3: 2 2 4 1 -> 4 first, then the tie 2 = 2: the lower position, {0, 2}
    chosen = np.array([[1, 0, 0, 0], [1, 1, 0, 0], [0, 1, 1, 0], [1, 0, 1, 0]], bool)
    np.testing.assert_array_equal(np.asarray(hybrid.select_top(jnp.asarray(want), jnp.asarray(causal), 2)), chosen)
    np.testing.assert_array_equal(np.asarray(glm5_reference.select(jnp.asarray(want), jnp.arange(4), 2)), chosen)
    # the key's norm: a LayerNorm with weight and bias over the projection, by hand on one row
    x = np.array([1.0, 2.0, 3.0, 6.0], np.float32)
    normed = (x - 3.0) / np.sqrt(3.5 + 1e-6)  # mean 3, variance (4 + 1 + 0 + 9) / 4
    cfg = hybrid.HybridConfig(vocab_size=8, hidden_size=4, intermediate_size=8, layer_types=("mla",), num_heads=1, num_kv_heads=1,
                              qk_rope_head_dim=0, index_head_dim=4, index_n_heads=2, index_topk=2, rope_theta=1e4, dtype="float32")
    layer = {"wi_k": jnp.eye(4), "wi_k_norm": jnp.asarray([1.0, 2.0, 1.0, 1.0]), "wi_k_norm_bias": jnp.asarray([0.0, 0.0, 0.5, 0.0])}
    key = np.asarray(hybrid.index_key(cfg, layer, jnp.asarray(x)[None], jnp.zeros((1,), jnp.int32)))[0]
    np.testing.assert_allclose(key, normed * [1, 2, 1, 1] + [0, 0, 0.5, 0], atol=1e-6, rtol=0)
