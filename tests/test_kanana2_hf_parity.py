"""The ``deepseek_v3`` family against its published implementation: a tiny
random ``DeepseekV3ForCausalLM`` of HF transformers (no low-rank query path,
interleaved rotary pairs, one leading dense layer, 8 sigmoid-routed experts
with a selection bias, top-3, two shared experts) saved as a checkpoint,
loaded through ``models/hf.py`` by the family's name map, and compared by
logits in float32: the benchmark's plain reference
(``benchlib/kanana2_reference.py``) and the program's prefill forward, both
with whole experts and vocabulary. Holds the latent attention (the rotary
pairs, the one shared rotary key, the latent's norm), the router (the biased
selection, unbiased gates, 1e-20, the scaling factor), the shared block and
every checkpoint name to the source.

Tolerance: float32 on all sides over four layers; logits of order 1 agree to
2e-5 (measured 2e-6); rotating halves where the checkpoint holds pairs, gates
from the biased scores or a dropped shared block move them by 1e-2 and more."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3Config"):
        pytest.skip("this transformers has no deepseek_v3")
    import chipbench_kanana2_util as ku

    d = {k: v for k, v in ku.tiny_model(held=8).items() if k != "assumed"}
    hf_cfg = transformers.DeepseekV3Config(**{k: v for k, v in d.items() if k != "model_type"})
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
    path = tmp_path_factory.mktemp("dsv3")
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


def test_the_name_map_loads_every_published_tensor(checkpoint):
    from areal_tpu.models import hybrid

    path, _, _ = checkpoint
    cfg, params = _load(path)
    assert isinstance(cfg, hybrid.HybridConfig) and cfg.layer_types == ("mla",) * 4 and cfg.ffns == ("dense", "moe", "moe", "moe")
    assert (cfg.router_width, cfg.num_experts, cfg.expert_first, cfg.moe_shared_intermediate_size, cfg.router_norm_eps) == (8, 8, 0, 64, 1e-20)
    assert set(params) == {"embed", "lm_head", "final_norm", "mla", "mla_moe"}
    assert params["mla_moe"]["we_gate"].shape == (3, 8, 64, 32) and params["mla_moe"]["router_bias"].shape == (3, 8)
    assert params["mla"]["w_kva"].shape == (1, 64, 136) and params["mla"]["w_kvb"].shape == (1, 128, 4 * 32)
    assert float(np.abs(np.asarray(params["mla_moe"]["router_bias"])).max()) > 0  # the buffer came with the checkpoint
    from safetensors import safe_open

    with safe_open(os.path.join(path, "model.safetensors"), "np") as f:
        assert set(f.keys()) == {name for name, _ in hybrid.hf_name_map(cfg).values()}  # nothing published is left unread


def test_reference_agrees_with_transformers(checkpoint):
    from chipbench_util import load_run

    load_run()
    from benchlib import kanana2_reference

    path, ids, want = checkpoint
    _, params = _load(path)
    with open(os.path.join(path, "config.json")) as f:
        d = json.load(f)
    assert want.std() > 0.3
    for row, w in zip(ids, want):
        np.testing.assert_allclose(kanana2_reference.logits(params, d, row), w, atol=2e-5, rtol=0)


def test_program_prefill_agrees_with_transformers(checkpoint):
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    path, ids, want = checkpoint
    cfg, params = _load(path)
    hidden, ks, vs, _ = hybrid.forward_prefill(params, cfg, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids)))
    assert vs is None and ks.shape == (4, 2, 23, 1, 256)  # one latent row a token and layer, 136 values in 256 lanes
    np.testing.assert_allclose(np.asarray(hybrid.compute_logits(params, cfg, hidden)), want, atol=2e-5, rtol=0)
