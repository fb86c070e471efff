"""Tiny sizes of the ``xing4_0`` family (a residual path of FOUR streams mixed
before and after every sublayer; latent attention through a low-rank query
under a YaRN-scaled rotary key; sigmoid-routed experts beside a shared one, a
chip's share of them) for the CPU tests: a configuration of the shape the
benchmark's ``xing4.0-29b-a4b-ep4-d10`` has with every width's ratio kept (2
dense + 4 expert layers; hidden 56 = 3,584 / 64, a dense FFN of 144 = 9,216 /
64, experts and the shared one of 16 = 1,024 / 64; 4 heads of 16 + 8 and a
low-rank query of 12, over a latent of 128 in rows of 256 lanes: the one width
the latent launch holds to whole lane tiles; the router scoring 16 experts of
which the stack holds 4, top-4; YaRN with an original length of 32 that the
test prompts pass, factor 64, the same betas: pairs 0 keeps its frequency, 3
takes a 64th), the test-only rehearsal override of the cell, and the
program's own pieces in float32. Used by ``tests/test_xing4_*.py`` too."""

from __future__ import annotations

import json
import os
import time

from chipbench_util import CHIP, TINY_MIX, bench, load_run

CELL = "rollout-xing4.0-29b-a4b-ep4-d10-longctx-grpo"
CONFIG = "xing4.0-29b-a4b-ep4-d10"
ORIGINAL = 32  # YaRN's original length at the tiny size


def family() -> dict:
    """The cell's ``family`` block: what names the model's pieces."""
    with open(os.path.join(CHIP, "workloads", CELL + ".json")) as f:
        return json.load(f)["family"]


def tiny_model(held: int = 4, first: int = 0, experts: int = 16, vocab: int = 500, layers: int = 6, rounds: int = 20) -> dict:
    """``held`` of the router's ``experts`` experts from id ``first``; held ==
    experts is the uncut model."""
    return {
        "model_type": "xing4_0",
        "attention_bias": False,
        "ep_size": 1,
        "first_k_dense_replace": 2,
        "hidden_act": "silu",
        "hidden_size": 56,
        "intermediate_size": 144,
        "kv_lora_rank": 128,
        "max_position_embeddings": 2048,
        "moe_intermediate_size": 16,
        "moe_layer_freq": 1,
        "n_group": 1,
        "n_routed_experts": held,
        "n_shared_experts": 1,
        "norm_topk_prob": True,
        "num_attention_heads": 4,
        "num_experts_per_tok": 4,
        "num_hidden_layers": layers,
        "num_key_value_heads": 4,
        "num_nextn_predict_layers": 0,
        "hc_mult": 4,
        "hc_sinkhorn_iters": rounds,
        "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 12,
        "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": ORIGINAL, "type": "yarn",
        },
        "routed_scaling_factor": 2,
        "scoring_func": "sigmoid",
        "tie_word_embeddings": False,
        "topk_group": 1,
        "topk_method": "noaux_tc",
        "v_head_dim": 16,
        "vocab_size": vocab,
        "assumed": {
            "router_experts": experts, "expert_first": first, "latent_row_lanes": 256, "rope_interleave": True,
            "stream_init": "embedding_copied", "stream_merge": "sum", "hc_per_sublayer": True, "hc_norm_weight": "none",
            "hc_eps_in": "both_denominators", "hc_coeff_dtype": "float32", "yarn_form": "deepseek_v3",
            "initializer_range": 0.1, "hc_seeded": True,
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
    from benchlib import xing4_weights

    return xing4_weights.make_params(cfg, seed, jnp.dtype(dtype))


def reference():
    load_run()
    from benchlib import xing4_reference

    return xing4_reference


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
    model = tiny_model()
    model["assumed"]["router_balance_tokens"] = 64  # as the cell's file: the router's bias settled on seeded tokens
    return {
        "model": model,
        "traffic": TINY_MIX,  # prompts of 8-60 tokens, contexts to 120: most of them past the original length of 32
        "params": {
            "dtype": "float32",
            "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
            "clients": 2,
            "warm_seconds": 0.5,
            "trace_seconds": 0.5,
            "ttft_grace_seconds": 2.0,
            "check": {"sample": 4, "limit": limit},
        },
    }


def rehearse(trace: bool, tmp, control: bool = False, **limits) -> dict:
    reh = {**rehearsal(**limits), "tmp": str(tmp)}
    return load_run().run_cell(bench(), CELL, 2**31 + 53, 3.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)
