"""Tiny sizes of the ``sdar_moe`` family (Qwen3-MoE's layer, generation by
diffusion over blocks of 4 positions) for the CPU tests: a configuration of
the shape the benchmark's ``sdar-30b-a3b-d7`` has (3 layers; hidden 32; 4
query heads over 2 KV heads of 16, each with its RMSNorm; 8 softmax-routed
experts of 16, top-2, gates renormalised; an untied head over a vocabulary of
300 whose LAST id is the mask token, so that seeded prompts and candidates do
produce it), the test-only rehearsal override of the cell, and the program's
own pieces in float32. Used by ``tests/test_sdar_*.py`` too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-sdar-30b-a3b-d7-block4-grpo"
CONFIG = "sdar-30b-a3b-d7"
PARAMS = 4_984_176_384  # 7 x 623,120,640 a layer + 2 x 151,936 x 2,048 + 2,048


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(vocab: int = 300, layers: int = 3, block: int = 4, steps: int = 2, rule: str = "sequential", mask_id: int | None = None) -> dict:
    return {
        "model_type": "sdar_moe",
        "attention_bias": False,
        "head_dim": 16,
        "hidden_act": "silu",
        "hidden_size": 32,
        "intermediate_size": 96,
        "max_position_embeddings": 2048,
        "moe_intermediate_size": 16,
        "norm_topk_prob": True,
        "num_attention_heads": 4,
        "num_experts": 8,
        "num_experts_per_tok": 2,
        "num_hidden_layers": layers,
        "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "tie_word_embeddings": False,
        "vocab_size": vocab,
        "assumed": {
            "qk_norm": True, "block_length": block, "mask_token_id": vocab - 1 if mask_id is None else mask_id,
            "denoising_steps": steps, "remasking_strategy": rule, "confidence_threshold": 0.9,
            # a std at which logits of order 1 come out of 3 layers of width 32 (0.02 leaves them flat to 1e-3)
            "initializer_range": 0.3, "expert_own_share": 0.25,
        },
    }


def model_config(cfg: dict, dtype: str = "float32", **over):
    """The program's configuration of a tiny model, as the cell builds it."""
    load_run()
    from benchlib.cells import rollout_family

    hf = {**cfg, "assumed": {**cfg["assumed"], **over}}
    return rollout_family.model_config(hf, family(), dtype)


def make_params(cfg: dict, seed: int = 5, dtype="float32"):
    import jax.numpy as jnp

    load_run()
    from benchlib import sdar_weights

    return sdar_weights.make_params(cfg, seed, jnp.dtype(dtype))


def reference():
    load_run()
    from benchlib import sdar_reference

    return sdar_reference


def rehearsal(limit: float = 2e-5) -> dict:
    return {
        "model": tiny_model(),
        # prompts of 10 tokens: 2 past a block boundary, where the ids alone give every pass's state at two passes a block
        "traffic": {**TINY_MIX, "prompt_len": {"dist": "fixed", "value": 10, "lo": 10, "hi": 10}},
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.5,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 2.0,
            "check": {
                "sample": 4, "limit": limit, "limit_trace": limit,
                "trace_probe": {
                    "requests": 4, "prompt_len": 9, "new_tokens": 10, "pad_to": 32, "denoising_steps": 2,
                    "rules": ["sequential", "low_confidence_static", "low_confidence_dynamic"],
                },
            },
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, **limits) -> dict:
    reh = {**rehearsal(**limits), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 58, 3.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
