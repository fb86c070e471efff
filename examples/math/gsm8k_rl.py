"""GSM8K GRPO training entry (parity: reference examples/math/gsm8k_rl.py).

Two deployment shapes:
- **fleet mode**: inference servers already running (launched via
  ``python -m areal_tpu.inference.server --config ...`` or a scheduler);
  their addresses arrive through ``AREAL_TPU_SERVER_ADDRS`` or name_resolve.
- **single-host mode** (default when no addresses are found): spin an
  in-process DecodeEngine+ServerThread in THIS process — one process holds
  the host's TPU chips, rollout and training time-share them, and weight
  updates use the "mem" mode through the normal client path.

Usage:
    python examples/math/gsm8k_rl.py --config examples/math/gsm8k_grpo.yaml \
        [train_dataset.path=/data/gsm8k] [key=value ...]
"""

import os
import sys

from areal_tpu.api.config import GRPOConfig, load_expr_config
from areal_tpu.dataset import get_custom_dataset
from areal_tpu.inference.client import RemoteJaxEngine
from areal_tpu.trainer import PPOTrainer


from common import (
    load_processor,
    load_tokenizer,
    make_workflow,
    start_single_host_stack,
)


def main(argv):
    config, _ = load_expr_config(argv, GRPOConfig)
    tokenizer = load_tokenizer(config.tokenizer_path or config.actor.path)

    ds_type = config.train_dataset.type or "gsm8k"
    train_dataset = get_custom_dataset(
        ds_type, split="train", path=config.train_dataset.path
    )
    valid_dataset = None
    if config.valid_dataset is not None:
        valid_dataset = get_custom_dataset(
            config.valid_dataset.type or ds_type,
            split="test",
            path=config.valid_dataset.path,
        )

    server = None
    actor_engine = None
    addrs = [a for a in os.environ.get("AREAL_TPU_SERVER_ADDRS", "").split(",") if a]
    if not addrs:
        # single-host: build the trainer engine first; the server starts
        # from a device copy of its weights (no double HF load)
        actor_engine, server = start_single_host_stack(config, len(train_dataset))
        addrs = [server.address]
    rollout = RemoteJaxEngine(config.rollout, addresses=addrs)
    rollout.initialize()

    # image datasets route through VisionRLVRWorkflow (pixel patches ride
    # the request path); text datasets through RLVR — same entry either way.
    # The eval split may declare its OWN type; each workflow follows its
    # dataset's modality.
    valid_ds_type = (
        (config.valid_dataset.type or ds_type)
        if config.valid_dataset is not None
        else ds_type
    )
    proc_path = config.tokenizer_path or config.actor.path
    workflow = make_workflow(
        ds_type, config.gconfig, tokenizer, load_processor(proc_path, ds_type)
    )
    eval_workflow = make_workflow(
        valid_ds_type,
        config.gconfig.new(temperature=0.6),
        tokenizer,
        load_processor(proc_path, valid_ds_type),
    )

    trainer = PPOTrainer(
        config,
        train_dataset,
        valid_dataset=valid_dataset,
        rollout=rollout,
        tokenizer=tokenizer,
        actor_engine=actor_engine,
    )
    try:
        trainer.train(workflow=workflow, eval_workflow=eval_workflow)
    finally:
        trainer.close()
        if server is not None:
            server.stop()


if __name__ == "__main__":
    main(sys.argv[1:])
