"""Tiny sizes of the ``cohere2_moe`` family (a parallel block under ONE
LayerNorm without bias; rotary window layers beside full layers without a
position, 3:1; sigmoid-routed experts beside four shared ones that are
averaged, a chip's share of them) for the CPU tests: a configuration of the
shape the benchmark's ``command-a-plus-ep16-d4`` has with every width's ratio
kept (one whole period S S S F; 16 query heads of 16 over 4 KV heads: queries
four times the hidden size and a query group of 4; a window of 16 tokens, two
pages of 8 a ring; experts as wide as the hidden size, the router scoring 16
of which the stack holds 4, top-4; four shared experts), the test-only
rehearsal override of the cell, and the program's own pieces in float32. Used
by ``tests/test_cohere2_moe_*.py`` too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-command-a-plus-ep16-d4-longctx-grpo"
CONFIG = "command-a-plus-ep16-d4"
WINDOW = 16


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(held: int = 4, first: int = 0, experts: int = 16, vocab: int = 500, window: int = WINDOW) -> dict:
    """``held`` of the router's ``experts`` experts from id ``first``; held ==
    experts is the uncut model."""
    return {
        "model_type": "cohere2_moe",
        "attention_bias": False,
        "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0,
        "head_dim": 16,
        "hidden_act": "silu",
        "hidden_size": 64,
        "intermediate_size": 64,
        "moe_intermediate_size": 64,
        "layer_norm_eps": 1e-5,
        "layer_switch": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "logit_scale": 1,
        "max_position_embeddings": 200000,
        "norm_topk_prob": True,
        "num_attention_heads": 16,
        "num_experts": held,
        "num_experts_per_tok": 4,
        "num_hidden_layers": 4,
        "num_key_value_heads": 4,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000,
        "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": window,
        "tie_word_embeddings": True,
        "use_embedding_sharing": True,
        "use_gated_activation": True,
        "use_parallel_block": True,
        "use_parallel_embedding": False,
        "use_qk_norm": False,
        "vocab_size": vocab,
        "assumed": {"router_experts": experts, "expert_first": first, "initializer_range": 0.1},
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
    from benchlib import cohere2_moe_weights

    return cohere2_moe_weights.make_params(cfg, seed, jnp.dtype(dtype))


def reference():
    load_run()
    from benchlib import cohere2_moe_reference

    return cohere2_moe_reference


def program_logits(cfg: dict, params: dict, ids):
    """The program's logits [len(ids), V] float32 of one sequence through
    ``forward_prefill`` (the XLA forms: the CPU path)."""
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import hybrid

    mcfg = model_config(cfg)
    ids = jnp.asarray(np.asarray(ids, np.int32))[None]
    hidden, *_ = hybrid.forward_prefill(params, mcfg, ids, jnp.ones_like(ids))
    return np.asarray(hybrid.compute_logits(params, mcfg, hidden)[0], np.float32)


def rehearsal(limit: float = 2e-5) -> dict:
    return {
        "model": tiny_model(),
        "traffic": TINY_MIX,  # prompts of 8-60 tokens, contexts to 120: up to seven windows of 16, so every ring wraps
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 8, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 1.0,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 3.0,
            "check": {"sample": 4, "limit": limit},
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, **limits) -> dict:
    reh = {**rehearsal(**limits), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 51, 3.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
