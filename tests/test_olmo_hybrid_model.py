"""The ``olmo_hybrid`` family of ``models/hybrid.py`` at tiny sizes in
float32, against the benchmark's plain reference, whose delta rule runs token
by token (no file over 8 tests; the scan alone and padded prompts:
test_olmo_hybrid_scan.py; kernels: test_olmo_hybrid_kernels.py).

Tolerances: float32 on both sides over 8 layers, the program's chunked scan
against the reference's token loop: logits agree to 2e-4 of a largest logit
near 1 (measured 2e-5), a slot's state to 1e-5 of its norm (measured 2e-7 to
2e-6). A tap in the wrong order, a read after the write instead of before it,
beta without its factor 2 or a window one token off moves the logits by 1e-2
and more; a bfloat16 state stands 2e-3 off and must fail the state test."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()
from benchlib import olmo_hybrid_reference as ref  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import prefill_forward, through_the_cache  # noqa: E402

PSZ = 16


@pytest.mark.parametrize("n", [5, 64, 150])
def test_full_forward_matches_reference(n):
    """The program's prefill (chunked scan: 5 is under a chunk, 64 one whole,
    150 two and a part) against the reference's token-by-token forward."""
    cfg = ou.tiny_model()
    mcfg, params = ou.model_config(cfg), ou.make_params(cfg, 11)
    ids = np.random.default_rng(n).integers(0, cfg["vocab_size"], n)
    want = ref.token_logits(params, cfg, ids)
    x = jnp.asarray(ids)[None]
    logits, (_, _, state) = prefill_forward(mcfg)(params, x, jnp.ones_like(x))
    got = np.asarray(logits)[0]
    assert np.abs(got - want).max() < 2e-4 and np.abs(want).max() > 0.3
    s_ref = ref.first_layer_state(params, cfg, ids, pad_to=256)
    assert ou.rel(ou.first_state(mcfg, state, 0), s_ref) < 1e-5


def test_prefill_then_paged_decode_matches_reference():
    """37 prompt tokens padded to a bucket of 64, then 30 decode steps through
    the cache: every step's logits are the reference's full forward's, and
    the slot's state after the prefill (the prompt less its last token) and
    after n decode steps is the reference's token-by-token state."""
    cfg = ou.tiny_model()
    mcfg, params = ou.model_config(cfg), ou.make_params(cfg, 3)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 67)
    want = ref.token_logits(params, cfg, ids)
    got, cache = through_the_cache(mcfg, params, ids, 37, 64, page_size=PSZ)
    assert np.abs(got - want[36:]).max() < 2e-4
    assert ou.rel(ou.first_state(mcfg, cache, 1), ref.first_layer_state(params, cfg, ids, pad_to=256)) < 1e-5
    assert not np.asarray(cache["gdn"][:, 0]).any() and not np.asarray(cache["gdn"][:, 2]).any()  # the other slots' rows
    _, cache0 = through_the_cache(mcfg, params, ids[:37], 37, 64, page_size=PSZ)  # one step: the prompt's last token
    assert ou.rel(ou.first_state(mcfg, cache0, 1), ref.first_layer_state(params, cfg, ids[:37], pad_to=256)) < 1e-5


def test_a_bfloat16_state_fails_the_state_tolerance():
    cfg = ou.tiny_model()
    mcfg, params = ou.model_config(cfg, gdn_state_dtype="bfloat16"), ou.make_params(cfg, 3)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 67)
    _, cache = through_the_cache(mcfg, params, ids, 37, 64, page_size=PSZ)
    assert cache["gdn"].dtype == jnp.bfloat16
    assert ou.rel(ou.first_state(mcfg, cache, 1), ref.first_layer_state(params, cfg, ids, pad_to=256)) > 1e-3


def test_config_from_a_published_config_and_what_it_refuses():
    cfg = ou.tiny_model()
    hf = {k: v for k, v in cfg.items() if k != "assumed"}
    mcfg = models.config_from_hf_dict({**hf, "head_dim": 64})
    assert models.family_of(mcfg) is hybrid and mcfg.model_type == "olmo_hybrid"
    assert mcfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    assert (mcfg.norm_placement, mcfg.qk_norm, mcfg.qk_norm_over, mcfg.rope_theta, mcfg.gdn_neg_eigval) == ("post", True, "whole", None, True)
    assert mcfg.has_recurrent_state and mcfg.num_kv_layers == 2 and not mcfg.tie_word_embeddings
    assert mcfg.gdn_head_pack == 2 and mcfg.count_shapes == {"gdn_updates": (6,), "attn_blocks_listed": (1,), "attn_blocks_fetched": (1,)}
    assert mcfg.state_shapes(4) == {
        "gdn": ((6, 4, 2, 24, 128), jnp.dtype("float32")),
        "conv": ((6, 4, 3 * 4 * (24 + 24 + 64)), jnp.dtype("bfloat16")),
    }
    assert models.config_from_hf_dict({**mcfg.to_hf_dict(), "head_dim": 64}) == mcfg  # a saved config.json reads back
    assert set(hybrid.param_partition_specs(mcfg)) == {"embed", "final_norm", "lm_head", "gdn", "attention"}
    names = hybrid.hf_name_map(mcfg)
    assert names["gdn/3/q_conv_w"] == ("model.layers.4.linear_attn.q_conv1d.weight", True)
    assert names["attention/1/q_norm"] == ("model.layers.7.self_attn.q_norm.weight", False)
    assert names["gdn/0/input_norm"] == ("model.layers.0.post_attention_layernorm.weight", False)
    assert names["lm_head"] == ("lm_head.weight", False)
    for bad, msg in (
        ({"rope_parameters": {"rope_theta": 500000.0}}, "rotary"),
        ({"attention_bias": True}, "biases"),
        ({"layer_types": ["linear_attention"] * 7 + ["sliding_attention"]}, "only"),
        ({"linear_num_key_heads": 2}, "fewer key heads"),
        ({"norm_placement": "pre"}, "post-sublayer"),
        ({"num_hidden_layers": 6}, "for 6 layers"),
    ):
        with pytest.raises(ValueError, match=msg):
            models.config_from_hf_dict({**hf, **bad})
