"""The ``lfm2_moe`` family against the published implementation of its
mixers: a tiny random ``Lfm2ForCausalLM`` of HF transformers (the dense
sibling: the installed transformers has ``lfm2`` but not ``lfm2_moe``) saved
as a checkpoint, loaded through ``models/hf.py`` by the family's name map
with every layer dense, and compared by logits in float32. Holds the
short-conv mixer, the rotary attention with q/k norms, the norms, the dense
MLP, the tied head and their checkpoint names to the source, not to this
repo's own reference. The expert block (router, ``expert_bias``, the experts'
names) is held to the repo's reference alone (tests/test_lfm2_model.py).

Tolerance: float32 on both sides over five layers; logits of order 1e-1
agree to 1e-5 (measured 3e-7); a tap in the wrong order, a norm after the
rotary embedding or a swapped B / C / x third moves them by 1e-3 and more."""

import json
import os

import numpy as np
import pytest


def test_hf_transformers_parity(tmp_path):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Lfm2Config"):
        pytest.skip("this transformers has no lfm2")
    import jax.numpy as jnp

    from areal_tpu import models
    from areal_tpu.models import hybrid
    from areal_tpu.models.hf import load_params_from_hf

    kinds = ["conv", "full_attention", "conv", "conv", "full_attention"]
    hf_cfg = transformers.Lfm2Config(
        vocab_size=128, hidden_size=32, intermediate_size=48, num_hidden_layers=5, layer_types=kinds,
        num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5, rope_theta=1000000.0, conv_bias=False,
        conv_L_cache=3, block_auto_adjust_ff_dim=False, tie_word_embeddings=True, max_position_embeddings=256,
    )
    torch.manual_seed(0)
    model = transformers.Lfm2ForCausalLM(hf_cfg).eval().to(torch.float32)
    with torch.no_grad():  # norms start at 1: move them, or dropping one would not show
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(0.1 * torch.randn_like(p))
            elif name.endswith("conv.conv.weight"):
                p.copy_(torch.rand_like(p) - 0.5)
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    # the published lfm2_moe config with every layer dense is this model
    with open(os.path.join(tmp_path, "config.json")) as f:
        d = json.load(f)
    d.update(model_type="lfm2_moe", num_dense_layers=5, num_experts=0, num_experts_per_tok=0)
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump(d, f)

    cfg = hybrid.serving_config(models.config_from_hf_path(str(tmp_path)), "float32")
    assert isinstance(cfg, hybrid.HybridConfig) and cfg.ffns == ("dense",) * 5
    assert cfg.layer_types == ("conv", "attention", "conv", "conv", "attention")
    params, _ = load_params_from_hf(str(tmp_path), cfg, dtype=jnp.float32)
    assert set(params) == {"embed", "final_norm", "conv", "attention"}
    ids = np.random.default_rng(0).integers(0, 128, (2, 21))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    hidden, *_ = hybrid.forward_prefill(params, cfg, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids)))
    got = np.asarray(hybrid.compute_logits(params, cfg, hidden))
    assert want.std() > 1e-2
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
