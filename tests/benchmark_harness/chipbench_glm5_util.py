"""Tiny sizes of the ``glm_moe_dsa`` family (latent attention with a low-rank
query, a learned index that picks ``index_topk`` cached tokens a query,
sigmoid-routed experts beside a shared one, a chip's share of the experts)
for the CPU tests: a configuration of the shape the benchmark's
``glm-5-ep16-d6`` has (one leading dense layer, then expert layers; the
router scores 8 experts of which the stack holds 4; ``index_topk`` 16, so
that contexts of 48-96 tokens are pruned; a vocabulary that is no multiple of
128), the test-only rehearsal override of the ``rollout_family_select`` cell
kind, and the program's own logits in float32. Used by ``tests/test_glm5_*.py``
too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-glm-5-ep16-d6-longctx-grpo"
CONFIG = "glm-5-ep16-d6"


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(held: int = 4, first: int = 0, experts: int = 8, layers: int = 3, topk: int = 16) -> dict:
    """``held`` of the router's ``experts`` experts from id ``first``; held ==
    experts is the uncut model."""
    return {
        "model_type": "glm_moe_dsa",
        "vocab_size": 500,
        "hidden_size": 64,
        "intermediate_size": 96,
        "moe_intermediate_size": 32,
        "num_hidden_layers": layers,
        "num_attention_heads": 4,
        "num_key_value_heads": 4,
        "head_dim": 8,  # the published config gives the rotary part here (64 at GLM-5)
        "q_lora_rank": 48,
        "kv_lora_rank": 128,
        "qk_head_dim": 24,
        "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8,
        "v_head_dim": 16,
        "index_n_heads": 4,
        "index_head_dim": 128,
        "index_topk": topk,
        "indexer_rope_interleave": True,
        "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "rope_interleave": True,
        "attention_bias": False,
        "hidden_act": "silu",
        "first_k_dense_replace": 1,
        "moe_layer_freq": 1,
        "n_routed_experts": held,
        "n_shared_experts": 1,
        "num_experts_per_tok": 3,
        "norm_topk_prob": True,
        "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid",
        "topk_method": "noaux_tc",
        "n_group": 1,
        "topk_group": 1,
        "num_nextn_predict_layers": 0,
        "tie_word_embeddings": False,
        "max_position_embeddings": 202752,
        "assumed": {
            "router_experts": experts, "expert_first": first, "latent_row_lanes": 256, "index_norm_eps": 1e-6,
            "initializer_range": 0.05, "attn_query_gain": 4.0,
        },
    }


def model_config(cfg: dict, dtype: str = "float32"):
    """The program's configuration of a configuration dict, as the cell kind builds it."""
    return bench().cell_kind("rollout_family").model_config(cfg, family(), dtype)


def make_params(cfg: dict, seed: int):
    import jax.numpy as jnp

    load_run()
    from benchlib import glm5_weights

    return glm5_weights.make_params(cfg, seed, jnp.float32)


def rehearsal(limit: float = 1e-5) -> dict:
    model = tiny_model()
    model["assumed"]["router_balance_tokens"] = 64  # as the cell's file: the router's bias settled on seeded tokens, under the mesh
    return {
        "model": model,
        "traffic": TINY_MIX,
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.2,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 0.3,
            "check": {
                "sample": 4, "limit": limit, "limit_key_rel": 1e-5, "limit_selected_common": 0.999,
                "select_probe": {"requests": 2, "prompt_len": 60, "new_tokens": 40, "min_new_tokens": 8, "positions": 5},
            },
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, limit: float = 1e-5) -> dict:
    reh = {**rehearsal(limit), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 31, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)


def program_logits(cfg: dict, params, ids):
    """The program's own prefill forward in float32: logits [len(ids), V]."""
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import hybrid

    mcfg = model_config(cfg)
    x = jnp.asarray(ids)[None]
    hidden, *_ = hybrid.forward_prefill(params, mcfg, x, jnp.ones_like(x))
    return np.asarray(hybrid.compute_logits(params, mcfg, hidden)[0])
