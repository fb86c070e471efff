"""Shared helpers for the math example entries (gsm8k_rl / gsm8k_sft /
gsm8k_eval) — one copy so tokenizer loading, reward selection, and the
single-host server spin-up cannot drift between entries."""

from __future__ import annotations

import sys

from areal_tpu.reward.gsm8k import gsm8k_reward_fn


def load_tokenizer(path: str):
    """Forgiving tokenizer load: weights-only smoke dirs have no tokenizer
    files; entries fall back to char-level/prompt_ids rows."""
    if not path:
        return None
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(path)
    except Exception as e:  # noqa: BLE001
        print(
            f"warning: no tokenizer at {path} ({e}); continuing without one",
            file=sys.stderr,
        )
        return None


VISION_DATASETS = ("clevr_count_70k", "geometry3k", "virl39k")


def reward_for(dataset_type: str):
    if dataset_type == "synthetic_arith":
        from areal_tpu.reward.synthetic import arith_char_reward_fn

        return arith_char_reward_fn
    if dataset_type == "countdown":
        from areal_tpu.reward.countdown import countdown_reward_fn

        return countdown_reward_fn
    if dataset_type == "clevr_count_70k":
        from areal_tpu.reward.clevr_count import clevr_count_reward_fn

        return clevr_count_reward_fn
    if dataset_type in ("geometry3k", "virl39k"):
        from areal_tpu.reward.math_verify import math_verify_reward_fn

        return math_verify_reward_fn
    return gsm8k_reward_fn


def make_workflow(dataset_type: str, gconfig, tokenizer, processor=None):
    """RLVR for text tasks; VisionRLVRWorkflow (pixel patches through the
    request path) for image datasets — the entry stays task-agnostic."""
    reward_fn = reward_for(dataset_type)
    if dataset_type in VISION_DATASETS:
        from areal_tpu.workflow.vision_rlvr import VisionRLVRWorkflow

        if processor is None:  # operator-facing: must survive python -O
            raise ValueError(
                f"{dataset_type} needs an image processor (AutoProcessor of "
                "the VLM checkpoint)"
            )
        return VisionRLVRWorkflow(reward_fn, gconfig, tokenizer, processor)
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    return RLVRWorkflow(reward_fn, gconfig, tokenizer=tokenizer)


def load_processor(path: str, dataset_type: str = ""):
    """AutoProcessor for VLM checkpoints; None for text models. Only loads
    when the dataset actually needs images (AutoProcessor on a text
    checkpoint degenerates into a second full tokenizer load)."""
    if not path or dataset_type not in VISION_DATASETS:
        return None
    try:
        from transformers import AutoProcessor

        return AutoProcessor.from_pretrained(path)
    except Exception as e:  # noqa: BLE001 — surface the root cause; the
        # vision workflow will refuse to build without a processor
        print(f"warning: AutoProcessor load failed at {path}: {e}")
        return None


def start_single_host_stack(config, dataset_size: int):
    """Single-host RL bootstrap shared by the RL entries: ONE process holds
    this host's chips, with the trainer engine and an in-process server
    side by side. The trainer engine is built first and the server starts
    from a device-to-device copy of its weights (no second checkpoint
    load, no host round trip). Returns (actor_engine, server).

    The copy is needed: the trainer's step DONATES its parameter buffers
    (engine/train_engine.py), so an aliased tree would be deleted under
    the server at the first train step — and an asynchronous server keeps
    decoding under the old policy while the trainer moves on, so two
    parameter generations are live by design. Later "mem" updates travel
    the normal client path (stage over loopback HTTP, fenced pointer-swap
    commit), which carries the version tags and the commit fence."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine

    config.weight_update_mode = "mem"
    config.actor.temperature = config.gconfig.temperature
    actor_engine = JaxTrainEngine(config.actor)
    actor_engine.initialize(
        FinetuneSpec(
            total_train_epochs=config.total_train_epochs,
            dataset_size=dataset_size,
            train_batch_size=config.train_dataset.batch_size,
        )
    )
    scfg = config.server
    scfg.model_path = scfg.model_path or config.actor.path
    serve_dtype = jnp.dtype(scfg.dtype)
    server = start_local_server(
        scfg,
        # cast-and-copy on the device: always a fresh buffer the server owns
        params=jax.tree.map(
            lambda x: jnp.array(x, dtype=serve_dtype, copy=True),
            actor_engine.params,
        ),
        model_cfg=actor_engine.model_cfg,
    )
    return actor_engine, server


def start_local_server(server_cfg, params=None, model_cfg=None):
    """Single-host mode: in-process DecodeEngine + HTTP server on this
    host's chips. With ``params`` the server serves the caller's tree as
    placed (device arrays stay on the device); otherwise it loads
    ``server_cfg.model_path``."""
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread

    engine = DecodeEngine(server_cfg, params=params, model_cfg=model_cfg)
    engine.initialize()
    server = ServerThread(server_cfg, engine)
    server.start()
    return server
