"""Tiny sizes of the hybrid family (state-space layers beside attention) for
the CPU tests: a configuration of the shape the benchmark's
``granite-4.0-h-micro`` has (two Mamba-2 layers around one attention layer,
``layer_types`` irregular on purpose, every multiplier set), the test-only
rehearsal override of the ``rollout_hybrid`` cell kind, and the program's
own logprobs in float32. Used by ``tests/test_hybrid_*.py`` too."""

from __future__ import annotations

import time

from chipbench_util import TINY_MIX, bench, load_run

CELL = "rollout-granite-h-micro-grpo"


def tiny_model(layer_types=("mamba", "attention", "mamba")) -> dict:
    return {
        "model_type": "granitemoehybrid",
        "vocab_size": 512,
        "hidden_size": 64,
        "intermediate_size": 96,
        "shared_intermediate_size": 96,
        "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types),
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "attention_bias": False,
        "attention_multiplier": 0.0625,
        "embedding_multiplier": 12,
        "residual_multiplier": 0.22,
        "logits_scaling": 8,
        "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True,
        "position_embedding_type": "nope",
        "hidden_act": "silu",
        "num_local_experts": 0,
        "mamba_n_heads": 8,
        "mamba_d_head": 16,
        "mamba_d_state": 16,
        "mamba_n_groups": 1,
        "mamba_d_conv": 4,
        "mamba_expand": 2,
        "mamba_chunk_size": 16,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "assumed": {"head_dim": 16, "ssm_state_dtype": "float32", "kv_lane_pad": 128, "initializer_range": 0.05},
    }


def model_config(cfg: dict, dtype: str = "float32", control: bool = False):
    """The program's configuration of a configuration dict, as the cell kind builds it."""
    return bench().cell_kind("rollout_hybrid").model_config(cfg, dtype, control)


def rehearsal(limit: float = 1e-5, limit_state: float = 1e-5) -> dict:
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
            "check": {
                "sample": 4,
                "limit_mean_abs_logprob": limit,
                "state_probe": {"requests": 2, "prompt_len": 12, "new_tokens": 24},
                "limit_state_rel": limit_state,
            },
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, limit: float = 1e-5, limit_state: float = 1e-5) -> dict:
    reh = {**rehearsal(limit, limit_state), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 19, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)


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
