"""The hybrid family against the published implementation: a tiny random
``GraniteMoeHybridForCausalLM`` of HF transformers (its plain torch path)
saved as a checkpoint, loaded through ``models/hf.py`` by the family's name
map, and compared by logits in float32. Holds the equations and the
checkpoint names to the source, not to this repo's own reference.

Tolerance: float32 on both sides over four layers; logits of order 1e-2
agree to 1e-6 (measured 1.5e-8); a swapped projection half, a wrong
multiplier or a conv tap in the wrong order moves them by 1e-3 and more."""

import numpy as np
import pytest


def test_hf_transformers_parity(tmp_path):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "GraniteMoeHybridConfig"):
        pytest.skip("this transformers has no granitemoehybrid")
    import jax.numpy as jnp

    from areal_tpu import models
    from areal_tpu.models import hybrid
    from areal_tpu.models.hf import load_params_from_hf

    hf_cfg = transformers.GraniteMoeHybridConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48, shared_intermediate_size=48, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"], num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=0, num_experts_per_tok=0, mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
        mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True,
        mamba_proj_bias=False, attention_bias=False, position_embedding_type="nope", embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.1, logits_scaling=8.0, tie_word_embeddings=True, rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    model = transformers.GraniteMoeHybridForCausalLM(hf_cfg).eval().to(torch.float32)
    with torch.no_grad():  # norms and the conv bias start at 1 and 0: move them, or dropping one would not show
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight", "conv1d.bias")):
                p.add_(0.1 * torch.randn_like(p))
    model.save_pretrained(str(tmp_path), safe_serialization=True)

    cfg = hybrid.serving_config(models.config_from_hf_path(str(tmp_path)), "float32")
    assert isinstance(cfg, hybrid.HybridConfig) and cfg.layer_types == ("mamba", "attention", "mamba", "mamba")
    params, _ = load_params_from_hf(str(tmp_path), cfg, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 128, (2, 21))  # 2 chunks of 8 and 5 more
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    hidden, *_ = hybrid.forward_prefill(params, cfg, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids)))
    got = np.asarray(hybrid.compute_logits(params, cfg, hidden))
    assert want.std() > 1e-3
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
