"""Tiny sizes of the ``lfm2_moe`` family (short-conv layers beside rotary
attention, sparse experts behind a biased sigmoid router) for the CPU tests:
a configuration of the shape the benchmark's ``lfm2-8b-a1b-d14`` has (two
leading conv layers with dense FFNs, then attention and conv layers with 8
experts, top-3), the test-only rehearsal override of the ``rollout_family``
cell kind, and the program's own logprobs in float32. Used by
``tests/test_lfm2_*.py`` too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-lfm2-8b-a1b-d14-grpo"
KINDS = ("conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv")


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(layer_types=KINDS, num_dense_layers: int = 2) -> dict:
    return {
        "model_type": "lfm2_moe",
        "vocab_size": 512,
        "hidden_size": 64,
        "intermediate_size": 96,
        "moe_intermediate_size": 48,
        "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types),
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "norm_eps": 1e-5,
        "rope_theta": 1000000,
        "conv_L_cache": 3,
        "conv_bias": False,
        "num_dense_layers": num_dense_layers,
        "num_experts": 8,
        "num_experts_per_tok": 3,
        "norm_topk_prob": True,
        "routed_scaling_factor": 1,
        "use_expert_bias": True,
        "max_position_embeddings": 128000,
        "assumed": {"tie_word_embeddings": True, "head_dim": 16, "kv_lane_pad": 128, "initializer_range": 0.05},
    }


def model_config(cfg: dict, dtype: str = "float32"):
    """The program's configuration of a configuration dict, as the cell kind builds it."""
    return bench().cell_kind("rollout_family").model_config(cfg, family(), dtype)


def make_params(cfg: dict, seed: int):
    import jax.numpy as jnp

    load_run()
    from benchlib import lfm2_weights

    return lfm2_weights.make_params(cfg, seed, jnp.float32)


def rehearsal(limit: float = 1e-5) -> dict:
    return {
        "model": tiny_model(),
        "traffic": TINY_MIX,
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.2,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 0.3,
            "check": {"sample": 4, "limit": limit},
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, limit: float = 1e-5) -> dict:
    reh = {**rehearsal(limit), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 23, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)


def program_logprobs(cfg: dict, params, ids):
    """log p(ids[t] | ids[:t]) from the program's own prefill forward in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import hybrid

    mcfg = model_config(cfg)
    x = jnp.asarray(ids)[None]
    hidden, *_ = hybrid.forward_prefill(params, mcfg, x, jnp.ones_like(x))
    lp = jax.nn.log_softmax(hybrid.compute_logits(params, mcfg, hidden)[0], axis=-1)
    return np.asarray(lp[np.arange(len(ids) - 1), np.asarray(ids)[1:]])
