"""Tiny sizes of the ``olmo_hybrid`` family (gated-delta-rule layers beside
full attention without a rotary embedding, post-sublayer norms) for the CPU
tests: a configuration of the shape the benchmark's ``olmo-hybrid-7b-d16``
has (periods of three linear-attention layers and one full-attention layer;
4 delta-rule heads of 24 x 64, so that two heads share a state tile as at the
published 192), the test-only rehearsal override of the cell, and the
program's own pieces in float32. Used by ``tests/test_olmo_hybrid_*.py``
too."""

from __future__ import annotations

import json
import os

from chipbench_util import CHIP, TINY_MIX

CELL = "rollout-olmo-hybrid-7b-d16-grpo"
CONFIG = "olmo-hybrid-7b-d16"
KINDS = ("linear_attention", "linear_attention", "linear_attention", "full_attention") * 2


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(layer_types=KINDS) -> dict:
    return {
        "model_type": "olmo_hybrid",
        "vocab_size": 512,
        "hidden_size": 128,
        "intermediate_size": 192,
        "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types),
        "num_attention_heads": 2,
        "num_key_value_heads": 2,
        "hidden_act": "silu",
        "attention_bias": False,
        "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False,
        "linear_num_key_heads": 4,
        "linear_num_value_heads": 4,
        "linear_key_head_dim": 24,
        "linear_value_head_dim": 64,
        "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
        "assumed": {
            "head_dim": 64,
            "rope_theta": None,
            "norm_placement": "post",
            "qk_norm_over": "whole",
            "linear_attention_form": "fla.GatedDeltaNet",
            "gdn_state_dtype": "float32",
            "conv_state_dtype": "float32",
            "initializer_range": 0.02,
        },
    }


def model_config(cfg: dict, dtype: str = "float32", **over):
    """The program's configuration of a tiny model, as the cell builds it."""
    from benchlib.cells import rollout_family

    hf = {**cfg, "assumed": {**cfg["assumed"], **over}}
    return rollout_family.model_config(hf, family(), dtype)


def make_params(cfg: dict, seed: int = 5, dtype="float32"):
    import jax.numpy as jnp

    from benchlib import olmo_hybrid_weights

    return olmo_hybrid_weights.make_params(cfg, seed, jnp.dtype(dtype))


def first_state(mcfg, cache, slot):
    """The first delta-rule layer's state of ``slot`` as float64 [H, K, V]."""
    import numpy as np

    from areal_tpu.ops.gdn_state_update import unpack_state

    return np.asarray(unpack_state(cache["gdn"][0, slot], mcfg.gdn_head_pack), np.float64)


def rel(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rehearsal(limit: float = 2e-5, limit_state: float = 1e-5) -> dict:
    return {
        "model": tiny_model(),
        "traffic": TINY_MIX,
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.2,
            "trace_seconds": 0.5,
            # eight layers and a chunked scan a prompt: under six test workers a first token can take a second
            "ttft_grace_seconds": 3.0,
            "check": {
                "sample": 4,
                "limit": limit,
                "state_probe": {"requests": 2, "prompt_len": 12, "new_tokens": 20},
                "limit_state_rel": limit_state,
            },
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, **limits) -> dict:
    import time

    from chipbench_util import bench, load_run

    reh = {**rehearsal(**limits), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 29, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
