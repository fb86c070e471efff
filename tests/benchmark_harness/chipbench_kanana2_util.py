"""Tiny sizes of the ``deepseek_v3`` family (latent attention, sigmoid-routed
experts beside a shared block, a chip's share of the experts) for the CPU
tests: a configuration of the shape the benchmark's ``kanana-2-30b-a3b-ep8``
has (one leading dense layer, then expert layers; the router scores 8 experts
of which the stack holds 4; a vocabulary that is no multiple of 128), the
test-only rehearsal override of the ``rollout_family`` cell kind, and the
program's own logits in float32. Used by ``tests/test_kanana2_*.py`` too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-kanana-2-30b-a3b-ep8-grpo"
CONFIG = "kanana-2-30b-a3b-ep8"


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(held: int = 4, first: int = 0, experts: int = 8, layers: int = 4) -> dict:
    """``held`` of the router's ``experts`` experts from id ``first``; held ==
    experts is the uncut model."""
    return {
        "model_type": "deepseek_v3",
        "vocab_size": 500,
        "hidden_size": 64,
        "intermediate_size": 96,
        "moe_intermediate_size": 32,
        "num_hidden_layers": layers,
        "num_attention_heads": 4,
        "num_key_value_heads": 4,
        "head_dim": 8,  # the published config gives the rotary part here (64 at kanana-2)
        "kv_lora_rank": 128,
        "q_lora_rank": None,
        "qk_head_dim": 24,
        "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8,
        "v_head_dim": 16,
        "rms_norm_eps": 1e-6,
        "rope_theta": 1000000,
        "rope_scaling": None,
        "rope_interleave": True,
        "attention_bias": False,
        "hidden_act": "silu",
        "first_k_dense_replace": 1,
        "moe_layer_freq": 1,
        "n_routed_experts": held,
        "n_shared_experts": 2,
        "num_experts_per_tok": 3,
        "norm_topk_prob": True,
        "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid",
        "topk_method": "noaux_tc",
        "n_group": 1,
        "topk_group": 1,
        "tie_word_embeddings": False,
        "max_position_embeddings": 32768,
        "assumed": {"router_experts": experts, "expert_first": first, "latent_row_lanes": 256, "initializer_range": 0.05},
    }


def model_config(cfg: dict, dtype: str = "float32"):
    """The program's configuration of a configuration dict, as the cell kind builds it."""
    return bench().cell_kind("rollout_family").model_config(cfg, family(), dtype)


def make_params(cfg: dict, seed: int):
    import jax.numpy as jnp

    load_run()
    from benchlib import kanana2_weights

    return kanana2_weights.make_params(cfg, seed, jnp.float32)


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
    return load_run().run_cell(bench(), CELL, 2**31 + 29, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)


def program_logits(cfg: dict, params, ids):
    """The program's own prefill forward in float32: logits [len(ids), V]."""
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import hybrid

    mcfg = model_config(cfg)
    x = jnp.asarray(ids)[None]
    hidden, *_ = hybrid.forward_prefill(params, mcfg, x, jnp.ones_like(x))
    return np.asarray(hybrid.compute_logits(params, mcfg, hidden)[0])
