"""Tiny sizes of the ``phi4flash`` family (a decoder-hybrid-decoder: Mamba-1
layers beside window attention, ONE full-attention layer whose keys and
values the cross-attention layers read, gated memory units, differential
attention, LayerNorm with a bias) for the CPU tests: a configuration of the
shape the benchmark's ``phi-4-mini-flash-reasoning`` has (12 layers in the
same five kinds: an 8-layer self-decoder whose last layer is the full one,
two memory-unit / cross pairs; a window of 8 tokens against pages of 4, so
that rings wrap many times and a window crosses page edges), the test-only
rehearsal override of the cell, and the program's own pieces in float32.
Used by ``tests/test_phi4flash_*.py`` too."""

from __future__ import annotations

import json
import os

from chipbench_util import CHIP, TINY_MIX

CELL = "rollout-phi-4-mini-flash-longctx-grpo"
CONFIG = "phi-4-mini-flash-reasoning"
KINDS = ("s6", "swa") * 3 + ("s6", "attention") + ("gmu", "cross") * 2


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(layers: int = 12, window: int = 8, vocab: int = 500) -> dict:
    return {
        "model_type": "phi4flash",
        "vocab_size": vocab,
        "hidden_size": 64,
        "intermediate_size": 96,
        "num_hidden_layers": layers,
        "num_attention_heads": 8,
        "num_key_value_heads": 4,
        "hidden_act": "silu",
        "layer_norm_eps": 1e-5,
        "mb_per_layer": 2,
        "sliding_window": window,
        "tie_word_embeddings": True,
        "mlp_bias": False,
        "lm_head_bias": False,
        "max_position_embeddings": 262144,
        "assumed": {
            "head_dim": 8,
            "mamba_d_state": 4,
            "mamba_d_conv": 4,
            "mamba_expand": 2,
            "mamba_dt_rank": 4,
            "mamba_conv_bias": True,
            "mamba_proj_bias": False,
            "attn_bias": True,
            "ssm_state_dtype": "float32",
            "conv_state_dtype": "float32",
            "initializer_range": 0.1,
            "lambda_init_std": 0.1,
        },
    }


def model_config(cfg: dict, dtype: str = "float32", **over):
    """The program's configuration of a tiny model, as the cell builds it."""
    from chipbench_util import load_run

    load_run()
    from benchlib.cells import rollout_family

    hf = {**cfg, "assumed": {**cfg["assumed"], **over}}
    return rollout_family.model_config(hf, family(), dtype)


def make_params(cfg: dict, seed: int = 5, dtype="float32"):
    import jax.numpy as jnp
    from chipbench_util import load_run

    load_run()
    from benchlib import phi4flash_weights

    return phi4flash_weights.make_params(cfg, seed, jnp.dtype(dtype))


def reference():
    from chipbench_util import load_run

    load_run()
    from benchlib import phi4flash_reference

    return phi4flash_reference


def rehearsal(limit: float = 2e-5, limit_state: float = 1e-5) -> dict:
    return {
        "model": tiny_model(),
        "traffic": TINY_MIX,
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 4, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.2,
            "trace_seconds": 0.5,
            # twelve layers and a token-by-token scan a prompt: under six test workers a first token can take a second
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
    return load_run().run_cell(bench(), CELL, 2**31 + 43, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
