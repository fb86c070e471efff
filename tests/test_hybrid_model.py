"""The hybrid family (state-space layers beside attention,
``areal_tpu/models/hybrid.py``) at a tiny size of the benchmark
configuration's shape, program in float32 against the benchmark's plain
reference (``benchmarks/chip/benchlib/hybrid_reference.py``: the recurrence
token by token) on seeded weights.

Tolerances: both sides compute in float32 with different association (a
chunked scan against a token loop, fused against separate matmuls), so
logprobs agree to a few float32 ulps of a logit of order 1: 5e-6. States and
conv windows of order 1 come out of matmuls of other shapes (a batch of rows
against one row): 1e-5 to 1e-4 relative, 1e-6 absolute; a wrong token in or
out of the state moves them by 1e-2 and more."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_hybrid_util as hu  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.models import hybrid, qwen  # noqa: E402
from tests.family_harness import prefill_forward  # noqa: E402

IRREGULAR = ("mamba", "mamba", "attention", "mamba", "attention")


def _seeded(layer_types=("mamba", "attention", "mamba"), seed=11):
    hu.load_run()
    from benchlib import hybrid_weights

    cfg = hu.tiny_model(layer_types)
    return cfg, hu.model_config(cfg), hybrid_weights.make_params(cfg, seed, jnp.float32)


@pytest.mark.parametrize("layer_types", [("mamba", "attention", "mamba"), IRREGULAR])
def test_full_forward_matches_reference(layer_types):
    cfg, mcfg, params = _seeded(layer_types)
    from benchlib import hybrid_reference
    assert [run[0] for run in hybrid._runs(mcfg)] == [
        k for i, k in enumerate(layer_types) if i == 0 or layer_types[i - 1] != k
    ]
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 53)  # 3 chunks of 16 and 5 more
    want = hybrid_reference.token_logprobs(params, cfg, ids, pad_to=64)
    got = hu.program_logprobs(cfg, params, ids)
    assert np.abs(got - want).max() < 5e-6
    assert np.std(want) > 0.01  # the model says something: not a uniform distribution


@pytest.mark.parametrize("n", [1, 16, 37])
def test_decode_form_equals_chunked_form(n):
    """The two forms of the mixer: the chunked scan over a prompt of n tokens
    (not a multiple of the chunk of 16) against n one-token state updates,
    by the mixer's output at every position, the final SSM state and the
    conv window."""
    _, mcfg, params = _seeded()
    layer = {k: v[1] for k, v in params["mamba"].items()}
    h = jax.random.normal(jax.random.PRNGKey(n), (2, n, mcfg.hidden_size), jnp.float32)
    dtypes = (jnp.float32, jnp.float32)
    out, ssm, conv = hybrid.mamba_prefill(mcfg, layer, h, jnp.full((2,), n, jnp.int32), dtypes)
    st = {
        "ssm": jnp.zeros((1, 2, mcfg.mamba_n_heads, mcfg.mamba_d_head, mcfg.mamba_d_state), jnp.float32),
        "conv": jnp.zeros((1, 2, 3 * mcfg.conv_dim), jnp.float32),
    }
    live = jnp.ones((2,), bool)
    for t in range(n):
        o, st = hybrid.mamba_decode(mcfg, layer, h[:, t], st, 0, live)
        np.testing.assert_allclose(o, out[:, t], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ssm, st["ssm"][0], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(conv, st["conv"][0], rtol=1e-5, atol=1e-6)  # one matmul over n rows against n matmuls


def test_prefill_state_is_the_state_before_the_masked_tokens():
    """Rows of different lengths in one padded batch, the state cut one token
    before each row's end (what the engine asks for): equal to prefilling
    exactly those tokens alone; what follows, padding included, leaves no
    trace in the state or the conv window."""
    cfg, mcfg, params = _seeded()
    rng = np.random.default_rng(3)
    lens = [41, 7, 1, 23]
    ids = rng.integers(0, cfg["vocab_size"], (4, 48))
    seg = (np.arange(48)[None] < np.asarray(lens)[:, None]).astype(np.int32)
    n_state = jnp.asarray(lens, jnp.int32) - 1
    _, (ks, _, st) = prefill_forward(mcfg)(params, jnp.asarray(ids), jnp.asarray(seg), n_state)
    assert ks.shape == (1, 4, 48, 2, 128) and not np.asarray(ks[..., 16:]).any()  # lane padding
    for j, n in enumerate(lens):
        if n == 1:
            assert not np.asarray(st["ssm"][:, j]).any() and not np.asarray(st["conv"][:, j]).any()
            continue
        alone = jnp.asarray(ids[j : j + 1, : n - 1])
        _, (_, _, want) = prefill_forward(mcfg)(params, alone, jnp.ones_like(alone))
        np.testing.assert_allclose(st["ssm"][:, j], want["ssm"][:, 0], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(st["conv"][:, j], want["conv"][:, 0], rtol=1e-4, atol=1e-6)


def test_config_from_published_json_and_refusals():
    with open(os.path.join(hu.bench().root, "benchmarks/chip/configs/granite-4.0-h-micro.json")) as f:
        published = json.load(f)
    mcfg = hu.model_config(published, "bfloat16")
    assert (mcfg.num_layers, mcfg.count("mamba"), mcfg.num_kv_layers) == (40, 36, 4)
    assert [i for i, k in enumerate(mcfg.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert (mcfg.head_dim_, mcfg.kv_head_dim, mcfg.sm_scale, mcfg.conv_dim) == (64, 128, 1 / 64, 4352)
    shapes = mcfg.state_shapes(64)
    assert shapes["ssm"] == ((36, 64, 64, 64, 128), jnp.dtype("float32"))
    assert shapes["conv"] == ((36, 64, 3 * 4352), jnp.dtype("bfloat16"))
    n_params = sum(int(np.prod(s)) for s in jax.tree.leaves(
        jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), mcfg)), is_leaf=lambda x: hasattr(x, "shape")
    ) for s in [s.shape])
    assert n_params == 3_191_396_096
    # a model_type nobody implements builds nothing, and Qwen's parser does
    # not take this family's config for a dense Qwen
    hf = {k: v for k, v in published.items() if k not in ("assumed", "assumed_notes")}
    with pytest.raises(ValueError, match="granitemoehybrid"):
        qwen.ModelConfig.from_hf_dict(hf)
    with pytest.raises(ValueError, match="not implemented"):
        models.config_from_hf_dict({**hf, "model_type": "falcon_h1"})
    with pytest.raises(ValueError, match="experts"):
        models.config_from_hf_dict({**hf, "num_local_experts": 8})
    assert models.family_of(mcfg) is hybrid and models.family_of(qwen.ModelConfig()) is qwen


def test_checkpoint_names_round_trip(tmp_path):
    """Save under the ``granitemoehybrid`` checkpoint names, load back: every
    leaf equal, the config too; the names are the published ones."""
    from safetensors import safe_open

    from areal_tpu.models.hf import load_params_from_hf, save_params_to_hf

    _, mcfg, params = _seeded(IRREGULAR)
    save_params_to_hf(params, mcfg, str(tmp_path))
    with safe_open(str(tmp_path / "model.safetensors"), framework="numpy") as f:
        names = set(f.keys())
        assert f.get_tensor("model.layers.0.mamba.conv1d.weight").shape == (mcfg.conv_dim, 1, 4)
        assert f.get_tensor("model.layers.2.shared_mlp.input_linear.weight").shape == (2 * 96, 64)
    assert {"model.embed_tokens.weight", "model.norm.weight", "model.layers.1.mamba.in_proj.weight",
            "model.layers.3.mamba.A_log", "model.layers.3.mamba.norm.weight",
            "model.layers.2.self_attn.q_proj.weight", "model.layers.4.post_attention_layernorm.weight"} <= names
    assert not any(".mamba." in n for n in names if n.startswith(("model.layers.2.", "model.layers.4.")))
    loaded, cfg2 = load_params_from_hf(str(tmp_path), dtype=jnp.float32)
    assert cfg2 == mcfg.__class__.from_hf_path(str(tmp_path)) and cfg2.layer_types == IRREGULAR
    flat_a, flat_b = jax.tree.flatten_with_path(params)[0], jax.tree.flatten_with_path(loaded)[0]
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    for (_, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
