"""Tiny sizes of the ``solar_open2`` family (a delta rule with a decay of its
own every key channel beside gated attention without a positional embedding,
3:1; sigmoid-routed experts beside a shared one in EVERY layer, a chip's
share of them) for the CPU tests: a configuration of the shape the
benchmark's ``solar-open2-250b-ep16-d8`` has with every width's ratio kept
(two whole periods G K K K; 8 query heads over 1 KV head of 16 and 8 kda
heads of 16 x 16, both twice the hidden size as published; a low rank of the
head size; experts of 5/16 of the hidden size, the router scoring 16 of which
the stack holds 4, top-4), the test-only rehearsal override of the cell, and
the program's own pieces in float32. Used by ``tests/test_solar_open2_*.py``
too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-solar-open2-ep16-d8-longctx-grpo"
CONFIG = "solar-open2-250b-ep16-d8"


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(held: int = 4, first: int = 0, experts: int = 16, periods: int = 2, vocab: int = 500) -> dict:
    """``held`` of the router's ``experts`` experts from id ``first``; held ==
    experts is the uncut model."""
    return {
        "model_type": "solar_open2",
        "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 8, "num_kv_heads": None},
        "hidden_size": 64,
        "num_hidden_layers": 4 * periods,
        "num_attention_heads": 8,
        "head_dim": 16,
        "num_key_value_heads": 1,
        "vocab_size": vocab,
        "intermediate_size": 160,
        "moe_intermediate_size": 20,
        "rms_norm_eps": 1e-5,
        "rope_theta": 10000,
        "tie_word_embeddings": False,
        "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0,
        "use_rope": False,
        "gqa_interval": 3,
        "gqa_layers": [4 * p for p in range(periods)],
        "use_gqa_gate": True,
        "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True,
        "n_routed_experts": held,
        "n_shared_experts": 1,
        "norm_topk_prob": True,
        "routed_scaling_factor": 1,
        "num_experts_per_tok": 4,
        "assumed": {
            "router_experts": experts,
            "expert_first": first,
            "kda_state_dtype": "float32",
            "conv_state_dtype": "float32",
            "initializer_range": 0.1,
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
    from benchlib import solar_open2_weights

    return solar_open2_weights.make_params(cfg, seed, jnp.dtype(dtype))


def reference():
    load_run()
    from benchlib import solar_open2_reference

    return solar_open2_reference


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


def rehearsal(limit: float = 2e-5, limit_state: float = 1e-5) -> dict:
    return {
        # ONE period here (two scan bodies to compile where two periods have four) and a second of warm traffic: under
        # six test workers a two-row prefill program first met by the traffic compiled for 9 s, and nothing was sent in
        # a window of 2 s
        "model": tiny_model(periods=1),
        "traffic": TINY_MIX,
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 1.0,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 3.0,  # eight layers and a chunked scan a prompt: under six test workers a first token can take a second
            "check": {
                "sample": 4,
                "limit": limit,
                "state_probe": {"requests": 2, "prompt_len": 12, "new_tokens": 20},
                "limit_state_rel": limit_state,
            },
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, **limits) -> dict:
    reh = {**rehearsal(**limits), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 47, 3.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
