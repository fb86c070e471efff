"""The GSPMD training engine — one engine instead of FSDP/Megatron/Archon.

Implements the reference TrainEngine contract (areal/api/engine_api.py:30-528)
on a single jax mesh ``(data, fsdp, seq, model, expert)``: DP/ZeRO-3, TP, SP
and EP are sharding rules, not codepaths — XLA inserts the collectives
the reference gets from FSDP2/DTensor/Megatron/NCCL
(areal/engine/fsdp_engine.py, megatron_engine.py). Pipeline parallelism is
deliberately not an engine mode (GSPMD covers the reference's PP use cases
within a pod, SURVEY §7.1); the GPipe mechanism itself lives in
``parallel/pipeline.py`` (fill-drain schedule over a stage axis, backward
via AD through the collectives) for deployments that want stage
partitioning across DCN-connected slices.

Design notes:
- A microbatch is a fixed-shape [G, L] grid of FFD-packed rows
  (utils/grid.py); L comes from a small bucket set and G is padded to the DP
  degree, so XLA compiles a handful of programs total (SURVEY §7.3.4 —
  replaces the reference's ragged varlen batches).
- ``train_batch(input_, loss_fn, loss_weight_fn)`` keeps the reference's
  packed-loss protocol: grads accumulate over microbatch grids scaled by
  ``loss_weight_fn(mb)/total_weight`` (the reference's loss-weight all-reduce,
  areal/engine/core/train_engine.py:28-140, is just a host sum here), then one
  donated optimizer step.
- Master params fp32, compute bf16 (cast per-step), AdamW + warmup-cosine via
  optax (reference fsdp_utils/optimizer.py).
- ``loss_fn(outputs, grid_data) -> (scalar_loss, {stat: scalar})``; outputs
  has label-aligned ``logprobs``/``entropy`` grids (or ``values`` for the
  critic). Callers pre-shift per-token data to label alignment (the
  reference's roll(-1), trainer/ppo/actor.py).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.api.config import MicroBatchSpec, OptimizerConfig, TrainEngineConfig
from areal_tpu.api.engine_api import InferenceEngine, TrainEngine
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta, WeightUpdateMeta
from areal_tpu.models import qwen
from areal_tpu.models.hf import load_params_from_hf, save_params_to_hf
from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability.step_timeline import engine_phase
from areal_tpu.parallel import mesh as mesh_lib
from jax import set_mesh, shard_map
from areal_tpu.utils import logging as alog
from areal_tpu.utils import perf_tracer
from areal_tpu.utils import compile_cache
from areal_tpu.utils.data import TensorDict, seqlens_of
from areal_tpu.utils.grid import Grid, pack_grid
from areal_tpu.utils.data import round_up_to_bucket

logger = alog.getLogger("jax_engine")

def _np_device_dtype(v: np.ndarray) -> np.ndarray:
    """Host arrays ship to device in 32-bit: f64/i64 are loader artifacts,
    never intentional precision."""
    if v.dtype == np.float64:
        return v.astype(np.float32)
    if v.dtype == np.int64:
        return v.astype(np.int32)
    return v


def _shape_key(batch) -> tuple:
    """jit-cache shape key: grid shape + pixel shapes when the trainable
    vision tower rides in the batch (their padded sizes change the traced
    program)."""
    s = tuple(batch["segment_ids"].shape)
    if "pixel_values" in batch:
        s = s + tuple(batch["pixel_values"].shape)
    return s


# per-token keys that ship to device grids (everything else stays on host)
_GRID_KEYS = (
    "input_ids",
    "loss_mask",
    "advantages",
    "old_logprobs",
    "prox_logprobs",
    "prox_alpha",
    "ref_logprobs",
    "logprobs",
    "versions",
    "version_lag",
    "values",
    "target_values",
    "old_values",
    "labels",
    "label_valid",
    "rw_pair_id",
    "rw_sign",
    "rw_last_mask",
    "image_embeds",
)


def _fold_weighted_stats(
    agg: dict[str, float], mb_host: list[dict], weights: list[float], total_w: float
) -> None:
    """Fold per-microbatch stat dicts (host values from the one boundary
    pull) into the step aggregate, weighted by each microbatch's loss
    weight — the reference's loss-weight all-reduce as a host sum.
    Array-valued stats (per-sequence attribution) are split off before
    this runs; skip any stragglers rather than crash on float()."""
    for s, w in zip(mb_host, weights):
        for k, v in s.items():
            if getattr(v, "ndim", 0):
                continue
            agg[k] = agg.get(k, 0.0) + float(v) * (w / total_w)


def _split_seq_stats(host: dict) -> dict[str, np.ndarray]:
    """Pop array-valued (per-sequence) stats out of one microbatch's host
    stat dict, leaving only scalars for the weighted fold."""
    arrays = {
        k: np.asarray(v) for k, v in host.items() if getattr(v, "ndim", 0)
    }
    for k in arrays:
        host.pop(k)
    return arrays


def make_lr_schedule(cfg: OptimizerConfig, total_steps: int):
    warmup = max(1, int(cfg.warmup_steps_proportion * total_steps))
    peak, floor = cfg.lr, cfg.lr * cfg.min_lr_ratio
    if cfg.lr_scheduler_type == "constant":
        main = optax.constant_schedule(peak)
    elif cfg.lr_scheduler_type == "linear":
        main = optax.linear_schedule(peak, floor, max(1, total_steps - warmup))
    elif cfg.lr_scheduler_type == "cosine":
        main = optax.cosine_decay_schedule(
            peak, max(1, total_steps - warmup), alpha=cfg.min_lr_ratio
        )
    else:
        raise ValueError(cfg.lr_scheduler_type)
    return optax.join_schedules(
        [optax.linear_schedule(0.0, peak, warmup), main], [warmup]
    )


class JaxTrainEngine(TrainEngine):
    """TrainEngine over one GSPMD mesh. One instance per model role."""

    def __init__(
        self,
        config: TrainEngineConfig,
        value_head: bool = False,
        model_config: qwen.ModelConfig | None = None,
        need_optimizer: bool = True,
        distributed: dict | None = None,
    ):
        self.config = config
        # logit temperature for the logprob/entropy heads: declared on
        # PPOActorConfig; plain TrainEngineConfig (SFT/RW/critic/ref)
        # defaults to 1.0. Read ONCE here on the host: the value is baked
        # into every traced forward and the jit cache key does not include
        # it, so a getattr inside the traced body would freeze a silent
        # fallback into the compiled program.
        # arealint: disable-next=CFG003 polymorphic read: PPOActorConfig declares temperature; base engines default to 1.0
        self._logit_temperature = float(getattr(config, "temperature", 1.0))
        # {"coordinator_address", "num_processes", "process_id"} — supplied
        # by TrainController for multi-host worker meshes
        self._distributed_kwargs = distributed
        self.value_head = value_head
        self.need_optimizer = need_optimizer  # False for frozen ref models
        self._model_config = model_config
        self._version = 0
        self._version_lock = threading.Lock()
        # host mirror of the optimizer step count (None = re-read from
        # opt_state on next use; see _opt_step_count)
        self._step_count: int | None = None
        self.mesh = None
        self.params = None
        self.opt_state = None
        self._param_labels = None  # "train"/"freeze" tree when LoRA is on
        self.model_cfg: qwen.ModelConfig | None = None
        self._tx = None
        self._fn_cache: dict[tuple, Callable] = {}
        self._inference_engine: InferenceEngine | None = None
        self._weight_update_meta: WeightUpdateMeta | None = None
        self._rollout_coord = None
        self.ft_spec: FinetuneSpec | None = None
        # per-sequence loss attribution from the LAST train_batch call
        # (key -> [B_input] array, input order), or None when the loss
        # emitted no seq__* stats. Read by PPOActor.ppo_update to join
        # loss stats onto the trajectory lineage ring.
        self.last_seq_stats: dict[str, np.ndarray] | None = None
        self._obs = obs_catalog.train_obs_metrics()
        # one WARNING line for a train step over 3 x the median of the last
        # 64, with what the span record holds of it
        self._step_watch = perf_tracer.SlowSpanWatch("areal.train.step")

    # -- lifecycle --------------------------------------------------------
    def initialize(self, ft_spec: FinetuneSpec | None = None, **kwargs) -> None:
        from areal_tpu.observability import hw_accounting as hw

        with perf_tracer.trace_scope(
            "areal.setup.engine_init", args={"engine": "train"}
        ) as span:
            self._initialize(ft_spec, **kwargs)
            span.set(
                param_bytes=hw.tree_bytes(self.params),
                opt_state_bytes=hw.tree_bytes(self.opt_state),
            )

    def _initialize(self, ft_spec: FinetuneSpec | None = None, **kwargs) -> None:
        cfg = self.config
        self.ft_spec = ft_spec
        # re-read the logit temperature: trainers sync config.actor fields
        # (rl_trainer sets actor.temperature from gconfig) after an
        # injectable engine may already have been constructed, and every
        # path calls initialize() before the first trace bakes the value in
        # arealint: disable-next=CFG003 polymorphic read: PPOActorConfig declares temperature; base engines default to 1.0
        self._logit_temperature = float(getattr(cfg, "temperature", 1.0))
        dist = kwargs.get("distributed") or self._distributed_kwargs
        if dist and int(dist.get("num_processes", 1)) > 1:
            # multi-host mesh: every worker process joins the same XLA world
            # before any device enumeration (reference role: torch
            # dist.init_process_group, fsdp_engine.py:208; here the
            # collectives ride ICI/DCN chosen by XLA)
            jax.distributed.initialize(
                coordinator_address=dist["coordinator_address"],
                num_processes=int(dist["num_processes"]),
                process_id=int(dist["process_id"]),
            )
            logger.info(
                f"jax.distributed up: process {dist['process_id']}/"
                f"{dist['num_processes']} @ {dist['coordinator_address']}"
            )
        # before the first compile, in the process that owns the devices
        # (a trainer driving remote workers never touches jax): the
        # persistent compile cache, TPU-only, and the listener that counts
        # compilations and writes them into the span record
        # (utils/compile_cache.py)
        compile_cache.install_compile_counters()
        compile_cache.enable_persistent_cache()
        self.mesh = kwargs.get("mesh") or mesh_lib.make_mesh(cfg.mesh)
        mcfg = self._model_config
        if mcfg is None:
            assert cfg.path, "TrainEngineConfig.path or model_config required"
            mcfg = qwen.ModelConfig.from_hf_path(cfg.path)
        mcfg = qwen.ModelConfig(
            **{
                **mcfg.__dict__,
                "dtype": cfg.dtype,
                "remat": cfg.gradient_checkpointing,
                "remat_policy": cfg.remat_policy,
                "attn_impl": cfg.attn_impl,
                "lora_rank": cfg.lora_rank,
                "lora_alpha": cfg.lora_alpha,
                "lora_targets": tuple(cfg.lora_targets),
            }
        )
        self.model_cfg = mcfg

        specs = qwen.param_partition_specs(mcfg)
        if self.mesh.shape.get("pipe", 1) > 1:
            # PP (AllocationMode pN): the stacked [n_layers, ...] leaves
            # shard their LEADING dim over the pipe axis — each stage owns a
            # contiguous layer slice, and _pp_hidden runs the GPipe schedule
            # over exactly that slicing (parallel/pipeline.py)
            assert mcfg.num_layers % self.mesh.shape["pipe"] == 0, (
                f"num_layers={mcfg.num_layers} must divide over "
                f"pipe={self.mesh.shape['pipe']} stages"
            )
            assert mcfg.num_experts == 0 and mcfg.vision is None, (
                "pipeline parallelism currently supports dense text models"
            )
            specs["layers"] = {
                k: P(*(("pipe",) + tuple(s)[1:]))
                for k, s in specs["layers"].items()
            }
        if self.value_head:
            specs["value_head"] = P(None)
        self.param_shardings = mesh_lib.param_sharding(self.mesh, specs)
        pdtype = jnp.dtype(cfg.param_dtype)

        if cfg.init_from_scratch or not cfg.path:
            init = jax.jit(
                lambda key: qwen.init_params(key, mcfg, dtype=pdtype),
                out_shardings={
                    k: v for k, v in self.param_shardings.items() if k != "value_head"
                },
            )
            with set_mesh(self.mesh):
                self.params = init(jax.random.PRNGKey(kwargs.get("seed", 0)))
        else:
            t0 = time.monotonic()

            def put(path, arr):
                shard = mesh_lib.shard_for_path(self.param_shardings, path)
                return jax.device_put(jnp.asarray(arr, dtype=pdtype), shard)

            self.params, _ = load_params_from_hf(cfg.path, mcfg, dtype=pdtype, put=put)
            logger.info(f"loaded HF weights from {cfg.path} in {time.monotonic()-t0:.1f}s")
            # fresh adapters over the loaded base (reference
            # fsdp_engine.py:833-860 get_peft_model role)
            self._add_lora_adapters(seed=kwargs.get("seed", 0))
            self._ensure_vision_tower(seed=kwargs.get("seed", 0))
        if self.value_head:
            self.params["value_head"] = jax.device_put(
                jnp.zeros((mcfg.hidden_size,), pdtype),
                self.param_shardings["value_head"],
            )

        if not self.need_optimizer:
            return
        total_steps = ft_spec.total_train_steps if ft_spec else 10_000
        ocfg = cfg.optimizer
        self._lr_schedule = make_lr_schedule(ocfg, total_steps)
        inner = optax.chain(
            optax.clip_by_global_norm(ocfg.gradient_clipping),
            optax.adamw(
                self._lr_schedule,
                b1=ocfg.beta1,
                b2=ocfg.beta2,
                eps=ocfg.eps,
                weight_decay=ocfg.weight_decay,
            ),
        )
        train_vit = cfg.train_vision_tower
        if train_vit:
            assert mcfg.vision is not None, (
                "train_vision_tower set but the model has no vision tower"
            )
            assert mcfg.lora_rank == 0, (
                "train_vision_tower with LoRA is unsupported: LoRA freezes "
                "every non-adapter leaf by design"
            )
        if mcfg.lora_rank > 0 or (mcfg.vision is not None and not train_vit):
            # freeze branches never READ their grads (set_to_zero) and the
            # grad-norm is masked below, so inside the fused jit XLA's DCE
            # prunes their dW matmuls from the backward.
            # - LoRA: only adapter (+value head) leaves train
            # - VLM: the vision tower is frozen by DEFAULT (embeds are
            #   precomputed outside the loss — its grads are structurally
            #   zero, and plain AdamW's decoupled weight decay would still
            #   shrink it every step); config.train_vision_tower runs the
            #   tower inside the grad jit instead and trains it jointly
            def label(p, _):
                ks = jax.tree_util.keystr(p)
                if ks.startswith("['vision']"):
                    return "freeze"
                if mcfg.lora_rank > 0:
                    return (
                        "train"
                        if "_lora_" in ks or ks.endswith("['value_head']")
                        else "freeze"
                    )
                return "train"

            self._param_labels = jax.tree_util.tree_map_with_path(
                label, self.params
            )
            self._tx = optax.multi_transform(
                {"train": inner, "freeze": optax.set_to_zero()},
                self._param_labels,
            )
        else:
            self._param_labels = None
            self._tx = inner
        state_shapes = jax.eval_shape(self._tx.init, self.params)
        self.opt_state_shardings = self._opt_state_shardings(state_shapes)
        with set_mesh(self.mesh):
            self.opt_state = jax.jit(
                self._tx.init, out_shardings=self.opt_state_shardings
            )(self.params)
        self._step_count = None  # fresh opt_state: re-sync the host mirror

    def _add_lora_adapters(self, seed: int = 0) -> None:
        """Insert freshly-initialized adapter leaves into an adapter-less
        param tree (HF checkpoints never carry them — they are merged away
        on export)."""
        mcfg = self.model_cfg
        if mcfg.lora_rank <= 0:
            return
        pdtype = jnp.dtype(self.config.param_dtype)
        lora_shardings = mesh_lib.param_sharding(
            self.mesh, qwen.lora_partition_specs(mcfg)
        )
        with set_mesh(self.mesh):
            lora = jax.jit(
                lambda key: qwen.init_lora_params(key, mcfg, dtype=pdtype),
                out_shardings=lora_shardings,
            )(jax.random.PRNGKey(seed))
        self.params["layers"].update(lora)

    def _ensure_vision_tower(self, seed: int = 0) -> None:
        """VLM: guarantee a ``vision`` subtree exists after any param-tree
        replacement. HF checkpoints with a ``visual.*`` tower load it via
        models/hf.py:_load_vision_params; this path only fires for
        checkpoints WITHOUT tower weights (e.g. text-only exports run as a
        VLM), which initialize from scratch."""
        mcfg = self.model_cfg
        if mcfg.vision is None or "vision" in self.params:
            return
        logger.warning(
            "VLM: checkpoint has no visual.* weights; vision tower "
            "initializes from scratch"
        )
        from areal_tpu.models.vision import init_vision_params, vision_partition_specs

        pdtype = jnp.dtype(self.config.param_dtype)
        vshard = mesh_lib.param_sharding(self.mesh, vision_partition_specs())
        with set_mesh(self.mesh):
            self.params["vision"] = jax.jit(
                lambda k: init_vision_params(k, mcfg.vision, dtype=pdtype),
                out_shardings=vshard,
            )(jax.random.PRNGKey(seed))

    def _grad_norm(self, grads):
        """Global norm over TRAINABLE grads only — reading frozen grads here
        would keep their backward computation alive under LoRA."""
        if self._param_labels is None:
            return optax.global_norm(grads)
        labels = jax.tree.leaves(self._param_labels)
        return optax.global_norm(
            [g for g, l in zip(jax.tree.leaves(grads), labels) if l == "train"]
        )

    def _opt_state_shardings(self, state_shapes):
        """Match mu/nu subtrees to param shardings by path suffix; scalars and
        unknown leaves are replicated."""
        param_flat = {
            jax.tree_util.keystr(path): s
            for path, s in jax.tree_util.tree_flatten_with_path(self.param_shardings)[0]
        }
        repl = NamedSharding(self.mesh, P())

        def assign(path, leaf):
            ks = jax.tree_util.keystr(path)
            if getattr(leaf, "ndim", 0) == 0:
                return repl
            for pks, shard in param_flat.items():
                if ks.endswith(pks) and shard.spec != P():
                    return shard
            return repl

        return jax.tree_util.tree_map_with_path(assign, state_shapes)

    def destroy(self) -> None:
        self.wait_for_save()
        self.params = None
        self.opt_state = None
        self._step_count = None
        self._fn_cache.clear()

    # -- offload / onload -------------------------------------------------
    # Colocated gen+train time-shares one chip's HBM: the trainer offloads
    # params+optimizer state during rollout and onloads before the update
    # (reference torch_memory_saver role, fsdp_engine.py:691-722).
    def offload(self) -> None:
        from areal_tpu.utils.offload import offload_tree

        if self.params is None or getattr(self, "_offload_mode", None):
            return
        t0 = time.monotonic()
        self._offload_shardings = jax.tree.map(
            lambda x: x.sharding if isinstance(x, jax.Array) else None,
            (self.params, self.opt_state),
        )
        self.params, mode_p = offload_tree(self.params)
        self.opt_state, mode_o = offload_tree(self.opt_state)
        self._offload_mode = (mode_p, mode_o)
        logger.info(
            f"offloaded params+opt ({mode_p}) in {time.monotonic()-t0:.2f}s"
        )

    def onload(self) -> None:
        from areal_tpu.utils.offload import onload_tree

        mode = getattr(self, "_offload_mode", None)
        if not mode:
            return
        t0 = time.monotonic()
        sp, so = self._offload_shardings
        with set_mesh(self.mesh):
            self.params = onload_tree(
                self.params, None if mode[0] == "pinned_host" else sp, mode[0]
            )
            self.opt_state = onload_tree(
                self.opt_state, None if mode[1] == "pinned_host" else so, mode[1]
            )
        self._offload_mode = None
        self._offload_shardings = None
        logger.info(f"onloaded params+opt in {time.monotonic()-t0:.2f}s")

    # -- versioning -------------------------------------------------------
    def set_version(self, version: int) -> None:
        with self._version_lock:
            self._version = version

    def get_version(self) -> int:
        with self._version_lock:
            return self._version

    # -- grid construction ------------------------------------------------
    def _dp(self) -> int:
        return self.mesh.shape["data"] * self.mesh.shape["fsdp"]

    def _attach_image_embeds(self, input_: TensorDict) -> TensorDict:
        """VLM data boundary. Frozen tower (default): run the vision tower
        once over the batch's pixel patches and materialize a per-token
        [B, L, D] ``image_embeds`` key aligned to <|image_pad|> positions —
        packed grids then never carry pixel data (models/vision.py design
        note). With ``train_vision_tower`` the tower must run INSIDE the
        grad jit instead, so this keeps the (padded) pixel tensors as
        per-seq keys plus a per-token ``image_k`` (ordinal of each image-pad
        token) that the grid packer redistributes with the tokens; the
        gather map is finalized per grid in _grid_to_device."""
        if "pixel_values" not in input_:
            return input_
        mcfg = self.model_cfg
        assert mcfg.vision is not None and mcfg.image_token_id >= 0, (
            "batch has pixel_values but the model is not a VLM"
        )
        from areal_tpu.models import vision as vis

        input_ = dict(input_)
        pv_obj = input_.pop("pixel_values")
        counts_obj = input_.pop("pixel_counts", None)
        ids_obj = input_["input_ids"]
        pv = np.asarray(pv_obj, np.float32)  # [B, P, pd]
        B, P_raw, pd = pv.shape
        counts = np.asarray(
            np.full(B, P_raw) if counts_obj is None else counts_obj, np.int32
        )
        if "pixel_pos_ids" not in input_:
            logger.warning(
                "VLM batch has pixel_values but no pixel_pos_ids; vision "
                "rope positions default to (0,0) per patch (real Qwen2-VL "
                "weights will mis-embed)"
            )
        pos_ids = np.asarray(
            input_.pop("pixel_pos_ids", np.zeros((B, P_raw, 2))), np.int32
        )
        ids = np.asarray(input_["input_ids"])
        trainable = self.config.train_vision_tower
        if not trainable:
            # one PPO step calls forward_batch (logprob recompute) and
            # train_batch on the SAME batch; memoize the tower output so the
            # frozen ViT truly runs once per batch — checked FIRST so a hit
            # pays none of the padding/alignment host work below. Keyed by
            # the IDENTITY of the caller's batch arrays, not content —
            # hashing the full pixel buffer cost O(batch bytes) of host time
            # on every forward/train call. The memo pins the keyed objects
            # so their ids can't be recycled while the entry is alive;
            # callers that mutate a pixel buffer in place must pass a fresh
            # array (the trainer never does).
            memo_key = (
                id(pv_obj),
                None if counts_obj is None else id(counts_obj),
                id(ids_obj),
                pv.shape,
                self.get_version(),
            )
            cached = getattr(self, "_image_embed_memo", None)
            if cached is not None and cached[0] == memo_key:
                input_["image_embeds"] = cached[1]
                return input_
        # shared alignment pass (both paths): patch-bucket padding, image-pad
        # ordinals, and the loud mismatch check — extras (k >= n_emb) get
        # zero embeddings either way
        merge2 = mcfg.vision.spatial_merge**2
        Ppad = vis.pad_patch_bucket(P_raw, merge2)
        if Ppad != P_raw:
            pv = np.pad(pv, ((0, 0), (0, Ppad - P_raw), (0, 0)))
            pos_ids = np.pad(pos_ids, ((0, 0), (0, Ppad - P_raw), (0, 0)))
        pad_mask = ids == mcfg.image_token_id  # [B, L]
        n_emb = counts // merge2  # [B]
        n_pos = pad_mask.sum(axis=1)
        for b in np.nonzero(n_pos != n_emb)[0]:
            # silent truncation here means training on corrupted inputs
            # (wrong spatial_merge, processor/tokenizer skew, truncated
            # image-pad runs) — make the misconfiguration loud
            logger.warning(
                f"VLM mismatch row {b}: {int(n_pos[b])} image-pad tokens vs "
                f"{int(n_emb[b])} merged patch embeddings; extra positions "
                "get zero embeddings"
            )
        k = np.cumsum(pad_mask, axis=1) - 1  # ordinal of each pad token
        take = pad_mask & (k < n_emb[:, None])

        if trainable:
            input_["image_k"] = np.where(take, k, -1).astype(np.int32)
            input_["pixel_values"] = pv
            input_["pixel_counts"] = counts
            input_["pixel_pos_ids"] = pos_ids
            return input_
        key = ("vision", Ppad)
        if key not in self._fn_cache:
            vcfg = mcfg.vision
            self._fn_cache[key] = jax.jit(
                lambda vp, px, c, pid: vis.vision_forward_batch(vp, vcfg, px, c, pid)
            )
        with set_mesh(self.mesh):
            # arealint: disable-next=PRF002 designed batch-boundary sync: the frozen ViT runs ONCE per batch (memoized across forward/train) and its embeds are scattered host-side into the packed grids
            out = np.asarray(
                self._fn_cache[key](
                    self.params["vision"],
                    jnp.asarray(pv),
                    jnp.asarray(counts),
                    jnp.asarray(pos_ids),
                ),
                np.float32,
            )  # [B, Ppad/merge2, D]
        embeds = np.zeros((B, ids.shape[1], mcfg.hidden_size), np.float32)
        # vectorized scatter: for each row, the k-th image-pad token gets the
        # k-th merged patch embedding (k < counts[b]//merge2)
        rows, cols = np.nonzero(take)
        embeds[rows, cols] = out[rows, k[rows, cols]]
        input_["image_embeds"] = embeds
        self._image_embed_memo = (memo_key, embeds, (pv_obj, counts_obj, ids_obj))
        return input_

    def _make_grids(
        self, input_: TensorDict, mb_spec: MicroBatchSpec | None = None
    ) -> list[Grid]:
        """Padded batch -> list of microbatch grids (FFD rows, bucketed L,
        G padded to the DP degree). ``mb_spec`` overrides the engine config
        for this call only (e.g. RWEngine's pair-preserving split)."""
        cfg = self.config
        input_ = self._attach_image_embeds(input_)
        lens = seqlens_of(input_)
        row_len = round_up_to_bucket(int(lens.max()), cfg.bucket_step)
        grid = pack_grid(input_, row_len=row_len, pad_rows_to=1)
        max_tok = (mb_spec or cfg.mb_spec).max_tokens_per_mb
        dp = self._dp()
        rows_per_mb = grid.n_rows
        if max_tok:
            rows_per_mb = max(1, max_tok // row_len)
        rows_per_mb = max(dp, -(-rows_per_mb // dp) * dp) if dp > 1 else rows_per_mb
        if rows_per_mb >= grid.n_rows and grid.n_rows % max(dp, 1) == 0:
            # source_index: grid-local sequence order -> index in input_
            # (per-seq loss attribution maps device outputs back through it)
            grid.source_index = list(grid.seq_index)
            return [grid]
        # re-pack per microbatch: chunk sequences by their assigned row
        n_mbs = -(-grid.n_rows // rows_per_mb)
        row_to_mb = [r // rows_per_mb for r in range(grid.n_rows)]
        mb_seqs: list[list[int]] = [[] for _ in range(n_mbs)]
        for local, r in enumerate(grid.row_of_seq):
            mb_seqs[row_to_mb[r]].append(grid.seq_index[local])
        out = []
        for seqs in mb_seqs:
            if not seqs:
                continue
            sub = {k: np.asarray(v)[seqs] for k, v in input_.items()}
            g = pack_grid(sub, row_len=row_len, pad_rows_to=max(dp, 1))
            # compose the sub-batch indirection: g.seq_index points into
            # ``sub``; the attribution needs indices into ``input_``
            g.source_index = [seqs[i] for i in g.seq_index]
            out.append(g)
        return out

    def _count_attn_tiles(self, grids: list[Grid]) -> None:
        """Credit ``areal_train_attn_tiles_{run,causal}_total`` with a step's
        grids: how far ``flash_train``'s segment skip engages. Numpy over
        [G, L] ids; nothing where the rows' attention is not the flash
        kernel (``resolve_impl``: off a TPU, short or odd rows)."""
        from areal_tpu.ops import attention

        mcfg = self.model_cfg
        for grid in grids:
            seg = np.asarray(grid.data["segment_ids"])
            L = seg.shape[-1]
            if attention.resolve_impl(mcfg.attn_impl, L, mcfg.head_dim_) != "pallas":
                continue
            blocks = attention.flash_block_sizes(attention.flash_tiles(L, mcfg.head_dim_))
            for kernel, (run, causal) in attention.flash_tile_counts(seg, blocks).items():
                self._obs.attn_tiles_run.labels(kernel=kernel).inc(run)
                self._obs.attn_tiles_causal.labels(kernel=kernel).inc(causal)

    def _grid_to_device(
        self, grid: Grid, seq_attribution: bool = False
    ) -> dict[str, jax.Array]:
        """Ship per-token grid arrays to the mesh with batch sharding.

        ``seq_attribution`` additionally builds the packed-batch segment
        map (``seq_slot``/``seq_slots``) for per-trajectory loss stats —
        only the train_batch loss path consumes it, so forward_batch /
        eval_batch skip the host loop and the two extra transfers."""
        seg = grid.data["segment_ids"]
        labels, label_valid = qwen.make_causal_inputs(grid.data["input_ids"], seg)
        batch: dict[str, np.ndarray] = {
            "segment_ids": seg,
            "positions": grid.data["positions"],
            "labels": labels,
            "label_valid": label_valid,
        }
        for k in _GRID_KEYS:
            if k in grid.data and k not in batch:
                batch[k] = grid.data[k]
        sharding = mesh_lib.batch_sharding(self.mesh)
        dev = {}
        for k, v in batch.items():
            dev[k] = jax.device_put(_np_device_dtype(np.asarray(v)), sharding)
        if seq_attribution and "lineage_id" in grid.data:
            # learning-health observatory: the packed-batch segment map for
            # per-trajectory loss attribution (trainer/ppo.py
            # _per_sequence_stats). ``seq_slot`` tags each cell with its
            # grid-local sequence slot; ``seq_slots`` is a dummy whose
            # bucketed SHAPE gives the traced reduction its static slot
            # count (n_seqs varies per batch — unbucketed it would recompile
            # the fwd/bwd per distinct count).
            n_local = len(grid.seq_index)
            n_slots = round_up_to_bucket(max(n_local, 1), 8)
            slot = np.full((grid.data["segment_ids"].shape), -1, np.int32)
            for local, (r, c, n) in enumerate(
                zip(grid.row_of_seq, grid.col_of_seq, grid.seq_lens)
            ):
                slot[r, c : c + n] = local
            dev["seq_slot"] = jax.device_put(slot, sharding)
            dev["seq_slots"] = jax.device_put(
                np.zeros(n_slots, np.int32), mesh_lib.replicated(self.mesh)
            )
        if "pixel_values" in grid.data and "image_k" in grid.data:
            # trainable-tower path: pixel tensors ride to the jit (replicated
            # — n_seqs is not dp-divisible in general and the tower is small
            # relative to the LM), and the per-token image_k ordinals become
            # a flat gather map into the [n_seqs * Pm, D] tower output
            merge2 = self.model_cfg.vision.spatial_merge**2
            pv = np.asarray(grid.data["pixel_values"], np.float32)
            counts = np.asarray(grid.data["pixel_counts"], np.int32)
            pos_ids = np.asarray(grid.data["pixel_pos_ids"], np.int32)
            # bucket n_seqs too: ragged rollouts vary the per-microbatch
            # sequence count, and an unbucketed jit operand dim would
            # recompile the whole train program per count. Padded rows have
            # count 0 (fully masked tower) and no slot references them.
            n_pad = round_up_to_bucket(pv.shape[0], 8)
            if n_pad > pv.shape[0]:
                extra = n_pad - pv.shape[0]
                pv = np.pad(pv, ((0, extra), (0, 0), (0, 0)))
                counts = np.pad(counts, (0, extra))
                pos_ids = np.pad(pos_ids, ((0, extra), (0, 0), (0, 0)))
            Pm = pv.shape[1] // merge2
            ik = np.asarray(grid.data["image_k"])
            slot = np.full_like(ik, -1)
            for local, (r, c, n) in enumerate(
                zip(grid.row_of_seq, grid.col_of_seq, grid.seq_lens)
            ):
                seg = ik[r, c : c + n]
                slot[r, c : c + n] = np.where(seg >= 0, local * Pm + seg, -1)
            rep = mesh_lib.replicated(self.mesh)
            dev["image_slot"] = jax.device_put(slot, sharding)
            dev["pixel_values"] = jax.device_put(pv, rep)
            dev["pixel_counts"] = jax.device_put(counts, rep)
            dev["pixel_pos_ids"] = jax.device_put(pos_ids, rep)
        return dev

    # -- jitted kernels ---------------------------------------------------
    def _outputs_fn(self, params, batch, no_grad: bool = False):
        mcfg = self.model_cfg
        cparams = jax.tree.map(
            lambda x: x.astype(mcfg.jax_dtype)
            if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            params,
        )
        moe = mcfg.num_experts > 0
        image_embeds = batch.get("image_embeds")
        if "pixel_values" in batch:
            # trainable tower (train_vision_tower): the ViT runs INSIDE this
            # traced fn on cparams["vision"], so the LM loss differentiates
            # through it; image_slot gathers merged patch embeddings into
            # the packed grid layout
            from areal_tpu.models import vision as vis

            emb = vis.vision_forward_batch(
                cparams["vision"],
                mcfg.vision,
                batch["pixel_values"],
                batch["pixel_counts"],
                batch["pixel_pos_ids"],
            )  # [n_seqs, Pm, D]
            flat = emb.reshape(-1, emb.shape[-1])
            slot = batch["image_slot"]
            image_embeds = jnp.where(
                (slot >= 0)[..., None], flat[jnp.maximum(slot, 0)], 0.0
            )
        if self.mesh.shape.get("pipe", 1) > 1:
            hidden, moe_aux = self._pp_hidden(cparams, batch), None
        else:
            fwd = qwen.forward(
                cparams,
                mcfg,
                batch["input_ids"],
                batch["segment_ids"],
                batch["positions"],
                with_aux=moe,
                no_grad=no_grad,
                image_embeds=image_embeds,
            )
            hidden, moe_aux = fwd if moe else (fwd, None)
        outputs: dict[str, jax.Array] = {}
        if moe_aux is not None:
            # router load-balance aux: loss fns add
            # cfg.router_aux_coef * outputs["moe_aux"]
            outputs["moe_aux"] = moe_aux
        if self.value_head:
            outputs["values"] = jnp.einsum(
                "gld,d->gl", hidden.astype(jnp.float32), cparams["value_head"].astype(jnp.float32)
            )
        else:
            with jax.named_scope("loss"):
                logp, ent = qwen.chunked_logprobs_entropy(
                    cparams,
                    mcfg,
                    hidden,
                    batch["labels"],
                    chunk_size=self.config.logprob_chunk_size,
                    temperature=self._logit_temperature,
                )
            outputs["logprobs"] = logp
            outputs["entropy"] = ent
        return outputs

    def _pp_hidden(self, cparams, batch) -> jax.Array:
        """Transformer hidden states through the GPipe schedule (AllocationMode
        pN -> mesh.pipe; reference megatron_engine.py:561-637 schedules).

        Embed and the logprob head stay in plain GSPMD outside the pipeline;
        only the layer stack runs inside shard_map over the ``pipe`` axis,
        each stage holding its [L/S, ...] slice (sharded that way at init).
        Every grid row is one microbatch; batch rows stay sharded over
        (data, fsdp) inside the shard_map, so DP still divides the work.
        Backward is jax.grad THROUGH the collectives — no handwritten
        schedule (parallel/pipeline.py design note)."""
        from areal_tpu.parallel.pipeline import gpipe

        mcfg = self.model_cfg
        mesh = self.mesh
        S = mesh.shape["pipe"]
        ids, seg, pos = batch["input_ids"], batch["segment_ids"], batch["positions"]
        G, L = ids.shape
        dp = mesh.shape["data"] * mesh.shape["fsdp"]
        assert G % dp == 0, (G, dp)  # _make_grids pads rows to the DP degree
        M = G // dp
        x = qwen._embed_lookup(cparams["embed"], ids, mcfg.jax_dtype)

        # microbatch m = one row per DP shard: device d's contiguous row
        # block [d*M, (d+1)*M) becomes x_micro[:, d] — the reshard is local
        def to_micro(a):
            a = a.reshape(dp, M, *a.shape[1:])
            return jnp.swapaxes(a, 0, 1)

        x_micro = (to_micro(x), to_micro(seg), to_micro(pos))

        # honor the configured attention impl like qwen.forward does; ring
        # attention needs the seq axis (excluded by the PP-path mesh assert)
        from areal_tpu.ops.attention import flash_mask, resolve_impl

        impl = resolve_impl(mcfg.attn_impl, L, mcfg.head_dim_)
        if impl == "ring":
            impl = "xla"

        def layer_fn(carry, layer):
            h, sg, ps = carry
            mask = flash_mask(sg, mcfg.head_dim_) if impl == "pallas" else qwen._attention_mask(sg)
            h, _ = qwen._decoder_layer(mcfg, h, layer, mask, ps, impl=impl)
            return h, sg, ps

        if mcfg.remat:
            policies = {
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "dots_nobatch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                "everything": jax.checkpoint_policies.everything_saveable,
            }
            layer_fn = jax.checkpoint(
                layer_fn, policy=policies[mcfg.remat_policy]
            )
        fn = gpipe(layer_fn, n_stages=S, n_microbatches=M, axis_name="pipe")
        row = P(None, ("data", "fsdp"), None)
        data_specs = (P(None, ("data", "fsdp"), None, None), row, row)
        layer_specs = jax.tree.map(lambda _: P("pipe"), cparams["layers"])
        mapped = shard_map(
            fn,
            mesh=mesh,
            in_specs=(layer_specs, data_specs),
            out_specs=data_specs,
            check_vma=False,
        )
        y, _, _ = mapped(cparams["layers"], x_micro)
        hidden = jnp.swapaxes(y, 0, 1).reshape(G, L, -1)
        return qwen._rms_norm(hidden, cparams["final_norm"], mcfg.rms_norm_eps)

    def _tree_outputs_fn(self, params, batch):
        """Tree-training outputs (reference models/tree_attn/module_fsdp.py
        :1-185 role): the transformer fwd/bwd runs once per unique trie NODE
        through the block-sparse ancestor kernel; per-sequence label-aligned
        logprobs/entropy are then GATHERED from the edges, so the loss zoo
        sees the same [B, T] contract as the packed path — exact parity,
        FLOPs scale with unique nodes."""
        mcfg = self.model_cfg
        cparams = jax.tree.map(
            lambda x: x.astype(mcfg.jax_dtype)
            if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            params,
        )
        from areal_tpu.ops.tree_attention import forest_hidden

        moe = mcfg.num_experts > 0
        fwd = forest_hidden(
            cparams,
            mcfg,
            batch["node_ids"],
            batch["node_pos"],
            batch["mask_words"],
            batch["block_any"],
            with_aux=moe,
        )
        hidden, moe_aux = fwd if moe else (fwd, None)
        # one chunked-vocab pass, EDGE-aligned: row parent(j) scored against
        # token(j) gives log p(node j | ancestors); the entropy from the
        # same row is exactly the label-aligned entropy convention
        with jax.named_scope("loss"):
            edge_hidden = jnp.take(hidden, batch["edge_rows"], axis=0)
            logp, ent = qwen.chunked_logprobs_entropy(
                cparams,
                mcfg,
                edge_hidden[None],
                batch["edge_labels"][None],
                chunk_size=self.config.logprob_chunk_size,
                temperature=self._logit_temperature,
            )
        gather = batch["gather_idx"]  # [B, T] -> edge index of token t+1
        outputs = {
            "logprobs": logp[0][gather],
            "entropy": ent[0][gather],
        }
        if moe_aux is not None:
            # router load-balance aux over UNIQUE nodes (the packed path's
            # statistic covers duplicated tokens; same contract, slightly
            # different and arguably better-behaved estimator)
            outputs["moe_aux"] = moe_aux
        return outputs

    def _first_call(self, key: tuple, named: tuple) -> compile_cache.FirstCall:
        """The program just cached under ``key`` for its first call, with what
        the program store names it by: everything the step programs close over
        (the model's and the engine's configuration, the mesh, the schedule's
        length, the logit temperature, the value head) and ``named``, the key with the loss
        function or hook ITSELF where the key has its ``id()``: described by
        module, name, closure and source (``compile_cache.describe``), or the
        store leaves the program alone. Parameters, optimizer state and the
        batch are arguments of every program."""
        closed_over = (
            self.model_cfg, self.config, self.mesh, self.ft_spec, self._logit_temperature, self.value_head, named,
        )
        return compile_cache.FirstCall(
            self._fn_cache, key, compile_cache.default_store(), compile_cache.describe(closed_over)
        )

    def _get_grad_fn(self, loss_fn: Callable, shape: tuple, kind: str = "packed"):
        key = ("grad", kind, shape, id(loss_fn))
        if key not in self._fn_cache:
            ofn = self._outputs_fn if kind == "packed" else self._tree_outputs_fn

            def compute(params, batch, scale):
                def lf(p):
                    outputs = ofn(p, batch)
                    with jax.named_scope("loss"):
                        loss, stats = loss_fn(outputs, batch)
                        return loss * scale, stats

                (loss, stats), grads = jax.value_and_grad(lf, has_aux=True)(params)
                return grads, loss, stats

            # the microbatch grid is consumed by this one call (every
            # iteration device_puts a fresh one), so donate it — its pages
            # free as the forward consumes them instead of surviving the
            # whole fwd/bwd
            self._fn_cache[key] = jax.jit(compute, donate_argnums=(1,))
            return self._first_call(key, ("grad", kind, shape, loss_fn))
        return self._fn_cache[key]

    def _get_forward_fn(self, shape: tuple, post_hook: Callable | None = None):
        key = ("fwd", shape, id(post_hook))
        if key not in self._fn_cache:

            def compute(params, batch):
                outputs = self._outputs_fn(params, batch, no_grad=True)
                if post_hook is not None:
                    outputs = post_hook(outputs, batch)
                return outputs

            self._fn_cache[key] = jax.jit(compute)
            return self._first_call(key, ("fwd", shape, post_hook))
        return self._fn_cache[key]

    def _get_accum_fn(self):
        key = ("accum",)
        if key not in self._fn_cache:
            # BOTH operands are dead after the add (the caller rebinds the
            # accumulator and drops the fresh grads), so donating both lets
            # XLA reuse one of them as the output — the accumulate path
            # carries two grad trees transiently instead of three
            self._fn_cache[key] = jax.jit(
                lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0, 1)
            )
            return self._first_call(key, key)
        return self._fn_cache[key]

    def _get_fused_step_fn(
        self, loss_fn: Callable, shape: tuple, kind: str = "packed"
    ):
        """Single-microbatch fast path: grad + optimizer apply in ONE jit with
        donated params/opt_state — XLA frees each grad buffer as soon as its
        param update consumes it, cutting peak HBM vs the accumulate path."""
        key = ("fused", kind, shape, id(loss_fn))
        if key not in self._fn_cache:
            ofn = self._outputs_fn if kind == "packed" else self._tree_outputs_fn

            def step(params, opt_state, batch, scale):
                def lf(p):
                    outputs = ofn(p, batch)
                    with jax.named_scope("loss"):
                        loss, stats = loss_fn(outputs, batch)
                        return loss * scale, stats


                (loss, stats), grads = jax.value_and_grad(lf, has_aux=True)(params)
                with jax.named_scope("optimizer"):
                    gnorm = self._grad_norm(grads)
                    updates, opt_state = self._tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, gnorm, loss, stats

            # params/opt_state are rebound by every caller (DON001 contract)
            # and the batch is single-use — donate all three
            self._fn_cache[key] = jax.jit(step, donate_argnums=(0, 1, 2))
            return self._first_call(key, ("fused", kind, shape, loss_fn))
        return self._fn_cache[key]

    def _get_apply_fn(self):
        key = ("apply",)
        if key not in self._fn_cache:

            def apply(params, opt_state, grads):
                with jax.named_scope("optimizer"):
                    gnorm = self._grad_norm(grads)
                    updates, opt_state = self._tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, gnorm

            # grads are dead after the apply (the accumulate loop rebinds
            # them next step) — donating them lets XLA write the optax
            # update tree into the grad buffers instead of allocating a
            # third params-sized transient (DON burn-down; the HBM ledger's
            # step_transient component accounts for exactly this)
            self._fn_cache[key] = jax.jit(apply, donate_argnums=(0, 1, 2))
            return self._first_call(key, key)
        return self._fn_cache[key]

    # -- tree training ----------------------------------------------------
    def _make_tree_batches(
        self, input_: TensorDict
    ) -> tuple[list[dict], dict[str, float]]:
        """Padded [B, T] batch -> host forest microbatches + dedup stats.

        Each microbatch is one fixed-shape forest forward: sequences are
        chunked under ``tree_node_budget`` unique nodes (GRPO groups kept
        whole — models/tree.py pack_forest), the trie's ancestor relation
        packed to bitmask words, and every label-aligned loss key sliced to
        the chunk's rows. Shapes are bucketed (node axis: tree_node_bucket;
        time axis: bucket_step) to bound XLA recompiles."""
        from areal_tpu.models import tree as tree_lib
        from areal_tpu.ops.tree_attention import BLOCK, pack_ancestor_bits

        cfg = self.config
        attn = np.asarray(input_["attention_mask"], bool)
        lens = attn.sum(-1).astype(int)
        ids = np.asarray(input_["input_ids"])
        T_orig = ids.shape[1]
        seqs = [ids[b, : lens[b]] for b in range(len(lens))]
        packs = tree_lib.pack_forest(
            # arealint: disable-next=CFG003 polymorphic read: PPOActorConfig declares group_size; SFT/ref trees have no sample groups
            seqs, cfg.tree_node_budget, getattr(cfg, "group_size", 1)
        )
        batches: list[dict] = []
        for pack, rows in packs:
            N = pack.n_nodes
            n_pad = round_up_to_bucket(N, max(cfg.tree_node_bucket, BLOCK))
            n_pad = -(-n_pad // BLOCK) * BLOCK
            words, block_any = pack_ancestor_bits(pack.parent, n_pad)
            node_ids = np.zeros(n_pad, np.int32)
            node_ids[:N] = pack.tokens
            node_pos = np.zeros(n_pad, np.int32)
            node_pos[:N] = pack.depth
            # edge j (every non-root node is one edge): score row parent(j)
            # against token(j); roots clamp to row 0 and are never gathered
            edge_rows = np.zeros(n_pad, np.int32)
            edge_rows[:N] = np.maximum(pack.parent, 0)
            edge_labels = np.zeros(n_pad, np.int32)
            edge_labels[:N] = pack.tokens
            Tp = min(
                T_orig,
                round_up_to_bucket(
                    int(max(lens[r] for r in rows)), cfg.bucket_step
                ),
            )
            B = len(rows)
            # bucket the row axis too: how many groups fit a node budget
            # shifts step to step, and an unbucketed B would recompile the
            # full fwd/bwd per distinct pack size. Dummy rows carry
            # label_valid=False and zeroed loss keys — inert in every loss.
            B_pad = round_up_to_bucket(B, 8)
            gather = np.zeros((B_pad, Tp), np.int32)
            label_valid = np.zeros((B_pad, Tp), bool)
            for i in range(B):
                nodes = pack.seq_nodes[i]
                L = len(nodes)
                gather[i, : L - 1] = nodes[1:]
                label_valid[i, : L - 1] = True
            batch = {
                "node_ids": node_ids,
                "node_pos": node_pos,
                "mask_words": words,
                "block_any": block_any,
                "edge_rows": edge_rows,
                "edge_labels": edge_labels,
                "gather_idx": gather,
                "label_valid": label_valid,
            }
            for k in _GRID_KEYS:
                if k in ("labels", "label_valid", "image_embeds"):
                    continue
                if k not in input_:
                    continue
                v = np.asarray(input_[k])[rows]
                if v.ndim >= 2 and v.shape[1] == T_orig:
                    v = v[:, :Tp]
                if B_pad > B:
                    pad = np.zeros((B_pad - B, *v.shape[1:]), v.dtype)
                    v = np.concatenate([v, pad], axis=0)
                batch[k] = v
            batches.append(batch)
        total_tokens = int(lens.sum())
        total_nodes = sum(p.n_nodes for p, _ in packs)
        stats = {
            "tree_tokens": float(total_tokens),
            "tree_nodes": float(total_nodes),
            # fwd/bwd FLOPs scale with nodes: this ratio IS the measured
            # FLOP reduction vs padded training (reference claims up to 10x,
            # docs/en/reference/tree_training.md:19-21)
            "tree_dedup_ratio": float(total_tokens) / max(total_nodes, 1),
        }
        return batches, stats

    def _tree_batch_to_device(self, batch: dict) -> dict[str, jax.Array]:
        """Tree microbatches ship replicated: the node axis is one fused
        kernel sequence (not row-shardable like grids), and params keep
        their GSPMD shardings regardless."""
        rep = mesh_lib.replicated(self.mesh)
        return {
            k: jax.device_put(_np_device_dtype(np.asarray(v)), rep)
            for k, v in batch.items()
        }

    def _train_batch_tree(
        self,
        input_: TensorDict,
        loss_fn: Callable,
        loss_weight_fn: Callable[[TensorDict], float],
    ) -> dict[str, float]:
        t0 = time.monotonic()
        with engine_phase("host_prep"):
            batches, tstats = self._make_tree_batches(input_)
            weights = [float(loss_weight_fn(b)) for b in batches]
        total_w = sum(weights) or 1.0
        agg: dict[str, float] = {}
        if len(batches) == 1:
            with set_mesh(self.mesh):
                with engine_phase("host_prep"):
                    batch = self._tree_batch_to_device(batches[0])
                shape = batch["node_ids"].shape + batch["gather_idx"].shape
                step_before = self._opt_step_count()
                fn = self._get_fused_step_fn(loss_fn, shape, kind="tree")
                with engine_phase("forward_backward"):
                    self.params, self.opt_state, gnorm, loss, stats = fn(
                        self.params,
                        self.opt_state,
                        batch,
                        jnp.float32(weights[0] / total_w),
                    )
                    # arealint: disable-next=PRF001 designed step-boundary sync: single batched pull, nothing left to overlap
                    host = jax.device_get(
                        {**stats, "loss": loss, "grad_norm": gnorm}
                    )
            agg = {k: float(v) for k, v in host.items()}
            agg["n_microbatches"] = 1.0
        else:
            grads = None
            accum = self._get_accum_fn()
            pending_stats: list[dict] = []  # per-microbatch DEVICE trees
            with set_mesh(self.mesh):
                for b, w in zip(batches, weights):
                    with engine_phase("host_prep"):
                        batch = self._tree_batch_to_device(b)
                    shape = batch["node_ids"].shape + batch["gather_idx"].shape
                    gfn = self._get_grad_fn(loss_fn, shape, kind="tree")
                    with engine_phase("forward_backward"):
                        new_grads, loss, stats = gfn(
                            self.params, batch, jnp.float32(w / total_w)
                        )
                        grads = new_grads if grads is None else accum(grads, new_grads)
                    # stats stay on device until the step boundary (one
                    # batched pull below, not one sync per microbatch)
                    pending_stats.append({**stats, "loss": loss})
                step_before = self._opt_step_count()
                with engine_phase("optimizer"):
                    self.params, self.opt_state, gnorm = self._get_apply_fn()(
                        self.params, self.opt_state, grads
                    )
                    # arealint: disable-next=PRF001 designed step-boundary sync: single batched pull, nothing left to overlap
                    gnorm_h, mb_host = jax.device_get((gnorm, pending_stats))
            _fold_weighted_stats(agg, mb_host, weights, total_w)
            agg["grad_norm"] = float(gnorm_h)
            agg["n_microbatches"] = float(len(batches))
        agg["lr"] = float(self._lr_schedule(step_before))
        self._count_opt_step()
        agg.update(tstats)
        agg["train_batch_secs"] = time.monotonic() - t0
        return agg

    # -- TrainEngine API --------------------------------------------------
    def train_batch(
        self,
        input_: TensorDict,
        loss_fn: Callable,
        loss_weight_fn: Callable[[TensorDict], float],
        mb_spec: MicroBatchSpec | None = None,
    ) -> dict[str, float]:
        """One optimizer step: the span ``areal.train.step``, whose children
        are the step's ``engine_phase`` spans."""
        with perf_tracer.trace_scope("areal.train.step", cpu=True) as span:
            out = self._train_batch(input_, loss_fn, loss_weight_fn, mb_spec)
        self._step_watch.observe(span)
        return out

    def _train_batch(
        self,
        input_: TensorDict,
        loss_fn: Callable,
        loss_weight_fn: Callable[[TensorDict], float],
        mb_spec: MicroBatchSpec | None,
    ) -> dict[str, float]:
        assert self.params is not None, "engine not initialized"
        self.last_seq_stats = None
        if self.config.tree_training:
            assert not self.value_head, "tree training is a policy-only path"
            assert "pixel_values" not in input_ and "image_embeds" not in input_, (
                "tree training does not support vision inputs"
            )
            return self._train_batch_tree(input_, loss_fn, loss_weight_fn)
        t0 = time.monotonic()
        with engine_phase("host_prep"):
            grids = self._make_grids(input_, mb_spec=mb_spec)
            weights = [float(loss_weight_fn(g.data)) for g in grids]
            self._count_attn_tiles(grids)
        total_w = sum(weights) or 1.0

        grads = None
        agg: dict[str, float] = {}
        accum = self._get_accum_fn()
        if len(grids) == 1:
            with set_mesh(self.mesh):
                with engine_phase("host_prep"):
                    batch = self._grid_to_device(grids[0], seq_attribution=True)
                step_before = self._opt_step_count()
                fn = self._get_fused_step_fn(loss_fn, _shape_key(batch))
                # the fused jit folds the optimizer apply into the same
                # program, so this span carries BOTH fwd/bwd and the
                # update (docs/observability.md phase vocabulary note)
                with engine_phase("forward_backward"):
                    self.params, self.opt_state, gnorm, loss, stats = fn(
                        self.params, self.opt_state, batch, jnp.float32(weights[0] / total_w)
                    )
                    # ONE batched transfer fetches every stat and fences the
                    # step (replaces block_until_ready + one blocking float()
                    # per stat — PRF burn-down, docs/static_analysis.md)
                    # arealint: disable-next=PRF001 designed step-boundary sync: single batched pull, nothing left to overlap
                    host = jax.device_get({**stats, "loss": loss, "grad_norm": gnorm})
            # the step's stats are host work too (the schedule's value is a
            # handful of tiny device programs and a pull): the device idles
            # through it, so it carries a phase and not the residual
            with engine_phase("host_prep"):
                seq_arrays = _split_seq_stats(host)
                if seq_arrays:
                    self._collect_seq_stats(
                        [(grids[0], seq_arrays)],
                        int(np.asarray(input_["attention_mask"]).shape[0]),
                    )
                agg = {k: float(v) for k, v in host.items()}
                agg["lr"] = float(self._lr_schedule(step_before))
                agg["n_microbatches"] = 1.0
                agg["train_batch_secs"] = time.monotonic() - t0
                self._count_opt_step()
            return agg
        pending_stats: list[dict] = []  # per-microbatch DEVICE stat trees
        with set_mesh(self.mesh):
            for g, w in zip(grids, weights):
                with engine_phase("host_prep"):
                    batch = self._grid_to_device(g, seq_attribution=True)
                shape = _shape_key(batch)
                gfn = self._get_grad_fn(loss_fn, shape)
                with engine_phase("forward_backward"):
                    new_grads, loss, stats = gfn(
                        self.params, batch, jnp.float32(w / total_w)
                    )
                    grads = new_grads if grads is None else accum(grads, new_grads)
                # stats stay on device: a float()/block here would stall
                # host dispatch once per microbatch, serializing the queue
                # XLA could otherwise run ahead on
                pending_stats.append({**stats, "loss": loss})
            step_before = self._opt_step_count()
            with engine_phase("optimizer"):
                self.params, self.opt_state, gnorm = self._get_apply_fn()(
                    self.params, self.opt_state, grads
                )
                # single step-boundary fence + batched pull of every
                # microbatch's stats (was: one sync per microbatch)
                # arealint: disable-next=PRF001 designed step-boundary sync: single batched pull, nothing left to overlap
                gnorm_h, mb_host = jax.device_get((gnorm, pending_stats))
        with engine_phase("host_prep"):  # the step's stats, as above
            seq_pairs = [
                (g, _split_seq_stats(s)) for g, s in zip(grids, mb_host)
            ]
            if any(arrs for _, arrs in seq_pairs):
                self._collect_seq_stats(
                    seq_pairs, int(np.asarray(input_["attention_mask"]).shape[0])
                )
            _fold_weighted_stats(agg, mb_host, weights, total_w)
            agg["grad_norm"] = float(gnorm_h)
            agg["lr"] = float(self._lr_schedule(step_before))
            agg["n_microbatches"] = float(len(grids))
            agg["train_batch_secs"] = time.monotonic() - t0
            self._count_opt_step()
        return agg

    def _collect_seq_stats(
        self, pairs: list[tuple[Grid, dict[str, np.ndarray]]], n_input: int
    ) -> None:
        """Map per-slot ``seq__*`` loss stats back to INPUT sequence order
        through each grid's source_index (bucket-padding slots drop).
        Host-side bookkeeping only — the arrays already arrived in the one
        step-boundary pull."""
        out: dict[str, np.ndarray] = {}
        for g, arrs in pairs:
            src = g.source_index if g.source_index is not None else g.seq_index
            for k, arr in arrs.items():
                dest = out.setdefault(k, np.zeros(n_input, np.float64))
                for local, s in enumerate(src):
                    if local < len(arr) and 0 <= s < n_input:
                        dest[s] = arr[local]
        self.last_seq_stats = out or None

    # -- RPC-friendly dispatch (single-controller mode) -------------------
    # Closures don't cross the RPC boundary; the controller ships loss /
    # weight functions as import-path strings resolved worker-side
    # (reference pattern: rpc_server.py create_engine dynamic import).
    def train_batch_serialized(
        self, input_: TensorDict, loss_fn: str, loss_weight_fn: str, **kw
    ) -> dict[str, float]:
        from areal_tpu.utils.dynamic_import import import_from_string

        return self.train_batch(
            input_, import_from_string(loss_fn), import_from_string(loss_weight_fn), **kw
        )

    def eval_batch_serialized(
        self, input_: TensorDict, loss_fn: str, loss_weight_fn: str, **kw
    ) -> dict[str, float]:
        from areal_tpu.utils.dynamic_import import import_from_string

        return self.eval_batch(
            input_, import_from_string(loss_fn), import_from_string(loss_weight_fn), **kw
        )

    def _opt_step_count(self) -> int:
        """Host-mirrored optimizer step count. The count leaf lives in
        ``opt_state`` on device; pulling it every step is a blocking
        scalar read in the step path (PRF burn-down). The mirror does one
        device read whenever opt_state was replaced wholesale (init /
        load) and host-increments per applied step after that."""
        if self._step_count is None:
            # arealint: disable-next=PRF002 one-time re-sync after init/load, not a per-step read
            self._step_count = self._read_opt_step_count()
        return self._step_count

    def _read_opt_step_count(self) -> int:
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.opt_state)[0]:
            if "count" in jax.tree_util.keystr(path):
                return int(leaf)
        return 0

    def _count_opt_step(self) -> None:
        if self._step_count is not None:
            self._step_count += 1

    def eval_batch(
        self,
        input_: TensorDict,
        loss_fn: Callable,
        loss_weight_fn: Callable[[TensorDict], float],
    ) -> dict[str, float]:
        with engine_phase("host_prep"):
            grids = self._make_grids(input_)
            weights = [float(loss_weight_fn(g.data)) for g in grids]
        total_w = sum(weights) or 1.0
        agg: dict[str, float] = {}
        pending_stats: list[dict] = []  # per-microbatch DEVICE stat trees
        with set_mesh(self.mesh):
            for g, w in zip(grids, weights):
                with engine_phase("host_prep"):
                    batch = self._grid_to_device(g)
                shape = _shape_key(batch)
                key = ("eval", shape, id(loss_fn))
                if key not in self._fn_cache:

                    def compute(params, batch):
                        outputs = self._outputs_fn(params, batch, no_grad=True)
                        return loss_fn(outputs, batch)

                    self._fn_cache[key] = jax.jit(compute)
                with engine_phase("forward_backward"):
                    loss, stats = self._fn_cache[key](self.params, batch)
                # stats stay on device; every microbatch is fetched in one
                # batched pull at the boundary below
                pending_stats.append({**stats, "loss": loss})
            # arealint: disable-next=PRF001 designed batch-boundary sync: single batched pull, nothing left to overlap
            mb_host = jax.device_get(pending_stats)
        _fold_weighted_stats(agg, mb_host, weights, total_w)
        return agg

    def forward_batch(
        self,
        input_: TensorDict,
        output_key: str = "logprobs",
        post_hook: Callable | None = None,
    ) -> np.ndarray:
        """Forward-only. Returns [B, L] fp32 aligned with the *input* padded
        batch: out[b, t] = log p(token t | prefix), out[b, 0] = 0 (the
        reference's gather_logprobs alignment). For values: out[b, t] =
        V(prefix incl. t)."""
        B, L = np.asarray(input_["attention_mask"]).shape
        out = np.zeros((B, L), dtype=np.float32)
        with engine_phase("host_prep"):
            grids = self._make_grids(input_)
        pending: list = []  # per-grid DEVICE outputs, pulled once below
        with set_mesh(self.mesh):
            for g in grids:
                with engine_phase("host_prep"):
                    batch = self._grid_to_device(g)
                shape = _shape_key(batch)
                fn = self._get_forward_fn(shape, post_hook)
                with engine_phase("forward_backward"):
                    outputs = fn(self.params, batch)
                # keep the result on device: pulling here would stall
                # dispatch of the NEXT grid behind this grid's compute
                pending.append(outputs[output_key])
            with engine_phase("forward_backward"):
                # arealint: disable-next=PRF001 designed batch-boundary sync: single batched pull after every grid is dispatched
                fetched = jax.device_get(pending)
        for vals, g in zip(fetched, grids):
            vals = np.asarray(vals, np.float32)
            # vectorized grid->batch scatter (one fancy-indexed copy
            # instead of a per-sequence Python loop). For logprobs the
            # label-aligned output shifts right one: token t's logp was
            # computed at position t-1, so out[src, 1:n] = row[:n-1].
            lens = np.asarray(g.seq_lens, np.int64)
            n_eff = lens if output_key == "values" else np.maximum(lens - 1, 0)
            seq_of = np.repeat(np.arange(len(lens)), n_eff)
            within = np.arange(n_eff.sum()) - np.repeat(
                np.cumsum(n_eff) - n_eff, n_eff
            )
            src_r = np.asarray(g.row_of_seq)[seq_of]
            src_c = np.asarray(g.col_of_seq)[seq_of] + within
            dst_r = np.asarray(g.seq_index)[seq_of]
            dst_c = within if output_key == "values" else within + 1
            out[dst_r, dst_c] = vals[src_r, src_c]
        return out

    # -- rollout plumbing -------------------------------------------------
    def connect_engine(
        self, engine: InferenceEngine, meta: WeightUpdateMeta | None = None
    ) -> None:
        self._inference_engine = engine
        self._weight_update_meta = meta
        # multi-host worlds route rollout pulls through the coordinator:
        # process 0 consumes from the fleet, DCN-broadcasts, every process
        # takes a seqlen-balanced shard (reference dist_rollout.py:22-272)
        from areal_tpu.infra.dist_rollout import DistRolloutCoordinator

        self._rollout_coord = DistRolloutCoordinator(engine, mesh=self.mesh)

    def prepare_batch(self, *args, **kwargs) -> TensorDict:
        assert self._inference_engine is not None
        if jax.process_count() > 1:
            return self._rollout_coord.prepare_batch(*args, **kwargs)
        return self._inference_engine.prepare_batch(*args, **kwargs)

    def rollout_batch(self, *args, **kwargs) -> TensorDict:
        assert self._inference_engine is not None
        if jax.process_count() > 1:
            return self._rollout_coord.rollout_batch(*args, **kwargs)
        return self._inference_engine.rollout_batch(*args, **kwargs)

    # -- weights ----------------------------------------------------------
    def update_weights(self, meta: WeightUpdateMeta | None = None) -> None:
        """Push current weights to the connected inference fleet.

        disk mode: export HF safetensors then notify servers (reference
        fsdp_engine.py:1139-1163). mem mode is implemented by the inference
        client pulling from a shared in-process weight store (see
        inference/client.py)."""
        meta = meta or self._weight_update_meta
        assert meta is not None, "no WeightUpdateMeta configured"
        mcfg = self.model_cfg
        if meta.lora_only and (mcfg is None or mcfg.lora_rank <= 0):
            # a lora_only meta on a non-LoRA model must not leak into the
            # client's lora branch (it would encode the full merged tree
            # against /update_weights_lora) — fall back to a full update
            import dataclasses as _dc

            logger.warning("lora_only weight update on a non-LoRA model; using full update")
            meta = _dc.replace(meta, lora_only=False)
        if meta.type == "mem" and meta.lora_only:
            # LoRA fast path: ship only the adapter leaves; servers fold the
            # delta into their base weights (decode_engine.update_weights_lora)
            assert self._inference_engine is not None
            import dataclasses as _dc

            lora = {
                f"layers/{t}_lora_{s}": self.params["layers"][f"{t}_lora_{s}"]
                for t in mcfg.lora_targets
                for s in ("a", "b")
            }
            self._inference_engine.update_weights(
                _dc.replace(meta, lora_scale=mcfg.lora_alpha / mcfg.lora_rank),
                params=lora,
            )
            return
        # inference serves the merged tree — LoRA deltas fold into the base
        # (the reference instead ships a PEFT config to SGLang; on TPU the
        # merged weights ARE the serving format)
        export = self._export_params()
        if meta.type == "disk":
            path = meta.path
            if meta.with_version:
                path = os.path.join(path, f"v{self.get_version()}")
            save_params_to_hf(
                export, self.model_cfg, path, base_model_path=self.config.path
            )
            if self._inference_engine is not None:
                import dataclasses as _dc

                self._inference_engine.update_weights(_dc.replace(meta, path=path))
        elif meta.type == "mem":
            assert self._inference_engine is not None
            self._inference_engine.update_weights(meta, params=export)
        else:
            raise NotImplementedError(meta.type)

    def _export_params(self) -> dict:
        if self.model_cfg is not None and self.model_cfg.lora_rank > 0:
            with set_mesh(self.mesh):
                return qwen.merge_lora(self.params, self.model_cfg)
        return self.params

    def save(self, meta: SaveLoadMeta) -> None:
        if meta.weight_format == "hf":
            save_params_to_hf(
                self._export_params(),
                self.model_cfg,
                meta.path,
                base_model_path=meta.base_model_path or self.config.path,
            )
        elif meta.weight_format == "orbax":
            # async save (reference utils/async_checkpoint.py:27-208 role):
            # orbax stages device arrays then writes in the background; the
            # next train_batch blocks on wait_for_save() before mutating
            # params (reference saver.py:176 maybe_wait_for_staging)
            ckptr = self._get_async_checkpointer()
            ckptr.wait_until_finished()  # one in-flight save at a time
            ckpt = {"params": self.params}
            if meta.with_optim:
                ckpt["opt_state"] = self.opt_state
            ckptr.save(os.path.join(meta.path, "state"), ckpt, force=True)
        else:
            raise NotImplementedError(meta.weight_format)

    def _get_async_checkpointer(self):
        import orbax.checkpoint as ocp

        if getattr(self, "_async_ckptr", None) is None:
            self._async_ckptr = ocp.AsyncCheckpointer(
                ocp.StandardCheckpointHandler()
            )
        return self._async_ckptr

    # -- async recover dumps (utils/saver.py Saver.save_async) -------------
    # Orbax's AsyncCheckpointer still BLOCKS the caller for device->host
    # staging plus any previous save; the step loop's pause should be the
    # host snapshot alone. Split the save so Saver can run the Orbax write
    # on its own background thread against an immutable numpy tree.
    def snapshot_for_save(self, with_optim: bool = True) -> dict:
        """Host (numpy) snapshot of params (+ optimizer state): the ONLY
        step-loop-blocking part of an async checkpoint. jax arrays are
        immutable, so the copy is consistent without pausing anything."""
        self.wait_for_save()  # order after any in-flight orbax async save
        ckpt = {"params": jax.tree.map(np.asarray, self.params)}
        if with_optim:
            ckpt["opt_state"] = jax.tree.map(np.asarray, self.opt_state)
        return ckpt

    def write_snapshot(self, snapshot: dict, path: str) -> None:
        """Write a :meth:`snapshot_for_save` tree as the same Orbax layout
        :meth:`load` restores. Runs on the saver's background thread —
        touches no engine state."""
        import orbax.checkpoint as ocp

        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(os.path.join(path, "state"), snapshot, force=True)

    def wait_for_save(self) -> None:
        """Block until any in-flight async checkpoint finished staging+write
        (must run before params/opt_state mutate)."""
        ckptr = getattr(self, "_async_ckptr", None)
        if ckptr is not None:
            ckptr.wait_until_finished()

    def load(self, meta: SaveLoadMeta) -> None:
        self.wait_for_save()
        if meta.weight_format == "hf":
            pdtype = jnp.dtype(self.config.param_dtype)

            def put(path, arr):
                shard = mesh_lib.shard_for_path(self.param_shardings, path)
                return jax.device_put(jnp.asarray(arr, dtype=pdtype), shard)

            vh = self.params.get("value_head") if self.value_head else None
            self.params, _ = load_params_from_hf(
                meta.path, self.model_cfg, dtype=pdtype, put=put
            )
            # HF checkpoints are merged trees without adapters or the vision
            # tower: restore those subtrees so params stay congruent with
            # _param_labels/_tx
            self._add_lora_adapters()
            self._ensure_vision_tower()
            if vh is not None:
                self.params["value_head"] = vh
        elif meta.weight_format == "orbax":
            import orbax.checkpoint as ocp

            tgt = {"params": self.params}
            if meta.with_optim:
                tgt["opt_state"] = self.opt_state
            with ocp.StandardCheckpointer() as ckptr:
                restored = ckptr.restore(
                    os.path.join(meta.path, "state"), jax.tree.map(lambda x: x, tgt)
                )
            self.params = restored["params"]
            if meta.with_optim:
                self.opt_state = restored["opt_state"]
                self._step_count = None  # restored count: re-sync the mirror
        else:
            raise NotImplementedError(meta.weight_format)

    def export_stats(self) -> dict[str, float]:
        return {"version": float(self.get_version())}

    # Whether the optimizer-step jits donate params/opt_state/grads. The
    # constant documents (and the HBM ledger + its test assert) the
    # donation contract of _get_fused_step_fn/_get_apply_fn: flipping a
    # donate_argnums there without updating this shows up as a ledger
    # regression, not a silent HBM doubling.
    STEP_DONATES_STATE = True

    def hbm_ledger(self, override_hbm_gb: float | None = None) -> dict:
        """Itemized device-memory account of this engine (params +
        optimizer state vs the device limit; analytic byte sums when the
        backend has no memory_stats — docs/observability.md "HBM ledger").

        ``step_transient`` is the analytic peak of extra bytes the
        optimizer step holds beyond the standing params/opt_state: one
        grads tree, plus — only when the step jits do NOT donate — a
        second params+opt_state generation (the donated buffers would
        otherwise stay live until the new trees materialize)."""
        from areal_tpu.observability import hw_accounting as hw

        components = {
            "params": hw.tree_bytes(self.params),
            "opt_state": hw.tree_bytes(self.opt_state),
        }
        components["step_transient"] = hw.step_transient_bytes(
            components["params"],
            components["opt_state"],
            donate=self.STEP_DONATES_STATE,
        )
        return hw.build_hbm_ledger(
            components,
            override_hbm_gb=override_hbm_gb,
            # a peak-of-step estimate, not standing allocation: itemize it
            # (the OOM margin the step needs) without counting it in_use
            exclude_from_total=("step_transient",),
        )
