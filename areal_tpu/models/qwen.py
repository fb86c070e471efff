"""Qwen2/Qwen2.5/Qwen3 decoder — a ground-up TPU-native implementation.

Replaces the reference's HF-runtime models and Archon's native torch Qwen
(reference areal/experimental/models/archon/qwen3/model/model.py) with a pure
functional JAX model designed for GSPMD:

- params are a plain pytree with **stacked layers** (leading ``n_layers`` dim)
  so the decoder body is one ``lax.scan`` — fast compiles, uniform shardings.
- sequence packing is first-class: a microbatch is a ``[G, L]`` grid of packed
  rows; ``segment_ids`` (0 = padding) drive both the attention mask and the
  loss mask. This replaces the reference's flash-attn varlen cu_seqlens path
  (areal/utils/data.py:273-324) with the TPU-idiomatic equivalent.
- sharding is expressed as `PartitionSpec` trees over mesh axes
  ``(data, seq, model, expert)`` — XLA inserts the collectives (TP all-reduce,
  Ulysses all-to-all between seq- and head-sharded layouts), replacing the
  reference's DTensor TP plan (areal/engine/fsdp_utils/parallel.py:217-365)
  and Ulysses monkey-patches (areal/models/fsdp/ulysses.py).
- logprob/entropy are computed **chunked over tokens** so the ``[T, vocab]``
  logits never fully materialize (the reference's vocab-parallel logprob role,
  areal/utils/functional/vocab_parallel.py).

Covers Qwen2 (attention bias, no qk-norm) and Qwen3 (qk-norm, no bias) via
config flags, with GQA and optional tied embeddings.
"""

from __future__ import annotations

import dataclasses
import json
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.sharding import get_abstract_mesh

# mesh axes over which the microbatch rows (G dim) shard
BATCH_AXES = ("data", "fsdp")


# ``jax.named_scope`` names every forward below gives its ops, so that a
# device trace reads in the model's own terms (docs/observability.md "Spans
# and scopes"). Norms go with the block they feed. The engines add
# ``sampler`` (decode chunk), ``loss`` and ``optimizer`` (train step).
SCOPES = ("embed", "attn_proj", "kv_write", "attn", "mlp", "lm_head")

# ``model_type``s of a published config.json that this module implements
MODEL_TYPES = ("qwen2", "qwen3", "qwen3_moe", "qwen2_vl", "qwen2_5_vl", "llama", "sdar_moe")

# how a denoise pass of a block picks the masked positions it commits (``ModelConfig.remasking_strategy``; the decode
# chunk's ``block_select``): the first k in sequence order, the k whose candidates are the most probable, or every one
# whose candidate is over ``confidence_threshold`` and at least the best one
REMASKING_RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 151936
    hidden_size: int = 896
    intermediate_size: int = 4864
    num_layers: int = 24
    num_heads: int = 14
    num_kv_heads: int = 2
    head_dim: int | None = None  # default hidden_size // num_heads
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    qk_norm: bool = False  # Qwen3
    attention_bias: bool = True  # Qwen2 has q/k/v bias
    dtype: str = "bfloat16"
    remat: bool = True
    # checkpoint policy under remat: "nothing" (recompute all — min HBM),
    # "dots_nobatch" (save non-batch matmul outputs — fewer recomputed
    # FLOPs when HBM allows), "everything" (no recompute)
    remat_policy: str = "nothing"
    # training attention: "xla" (masked sdpa, Ulysses via GSPMD a2a),
    # "ring" (shard_map ring attention over the mesh "seq" axis),
    # "pallas" (fused flash kernel; falls back to xla off-TPU)
    attn_impl: str = "xla"
    # MoE (qwen3-moe family; 0 experts = dense FFN). Experts shard over the
    # mesh "expert" axis; dispatch is dropless unless moe_dropless is off,
    # then a capacity-bounded einsum (models/moe.py)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int | None = None
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True
    # sort-based grouped dispatch (megablox gmm) computing EVERY routed
    # token; False = capacity-bounded einsum dispatch (drops overflow)
    moe_dropless: bool = True
    # LoRA (reference fsdp_engine.py:833-860 PEFT wrapper). rank 0 = off.
    # Adapters live as extra stacked-layer leaves ("wq_lora_a"/"wq_lora_b");
    # the base stays frozen and exports merge the deltas back in.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ("wq", "wk", "wv", "wo")
    # VLM (reference VLM path fsdp_utils/parallel.py:217-365): when set, the
    # params tree carries a "vision" subtree (models/vision.py tower) and
    # forward() scatters image embeddings into <|image_pad|> positions
    image_token_id: int = -1
    vision: Any = None  # vision.VisionConfig | None
    router_aux_coef: float = 0.0  # load-balance aux loss weight
    # generation by diffusion over blocks (``sdar_moe``): positions go in blocks of ``block_length`` by ABSOLUTE
    # position (position j is in block j // block_length) and a query sees every key of the blocks up to its own, its
    # own both ways. 1 = an autoregressive model: every mask, program and count below is what it was. Above 1 a decode
    # step is a PASS over a slot's current block (``forward_block_paged``; inference/decode_programs.py): a denoise
    # pass scores the block with its uncommitted positions holding ``mask_token_id``'s embedding and commits some of
    # them, a commit pass runs the clean block again and writes its keys and values. The other three are a request's
    # defaults: passes a block (``block_length / denoising_steps`` positions a pass under the counted rules), the rule
    # (``REMASKING_RULES``) and the dynamic rule's threshold
    block_length: int = 1
    mask_token_id: int = -1
    denoising_steps: int = 1
    remasking_strategy: str = "sequential"
    confidence_threshold: float = 0.9

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim_

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim_

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    # -- what the serving cache holds for this family (inference/paged_kv.py):
    # K and V pages for every layer, as wide as a head; no recurrent state
    @property
    def num_kv_layers(self) -> int:
        return self.num_layers

    @property
    def kv_head_dim(self) -> int:
        return self.head_dim_

    @property
    def kv_pools(self) -> dict[str, tuple[int, int]]:
        """{page pool: (heads, lanes)} of what a token leaves behind in a
        layer (inference/paged_kv.py): a K and a V row a KV head."""
        return {"k": (self.num_kv_heads, self.kv_head_dim), "v": (self.num_kv_heads, self.kv_head_dim)}

    has_recurrent_state = False
    has_slot_tenant = False  # nothing of a slot's but its pages: no state, no window ring (models/hybrid.py)
    expert_first = 0  # every expert the router scores is held here (models/hybrid.py holds a share of them)

    @property
    def moe_count_shapes(self) -> dict[str, tuple[int, ...]]:
        """{leaf: shape} of the expert-load counts a decode chunk hands back (models/hybrid.py has what each counts):
        a block model's with experts; none for a token-a-step model of this module, whose chunk never carried them."""
        if self.block_length == 1 or not self.num_experts:
            return {}
        n = self.num_layers
        return {"moe_load": (n, self.num_experts), "moe_touched": (n,), "moe_streamed": (n,)}

    @property
    def count_shapes(self) -> dict[str, tuple[int, ...]]:
        """{leaf: shape} of every int32 count a decode chunk hands back beside its tokens: the blocks of pages a
        token step's attention launches listed and fetched; for a block model the slot-passes that denoised and that
        committed, the blocks emitted and the cached tokens the in-block attention launches fetched (a pass, not a
        layer), and its expert loads."""
        if self.block_length == 1:
            return {"attn_blocks_listed": (1,), "attn_blocks_fetched": (1,)}
        blk = dict.fromkeys(("blk_denoise_passes", "blk_commit_passes", "blk_blocks", "blk_attn_tokens_read"), (1,))
        return {**blk, **self.moe_count_shapes}

    def state_shapes(self, slots: int) -> dict:
        return {}

    def ring_shapes(self, slots: int, page_size: int) -> dict:
        """No layer of this family keeps a window (models/hybrid.py)."""
        return {}

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "ModelConfig":
        """Build from an HF ``config.json`` dict (qwen2 / qwen3 model types,
        plus qwen2-vl-style VLMs whose text fields may nest under
        ``text_config``, and llama, which is the same decoder). Any other
        ``model_type`` is refused: the keys this reads are common enough
        that another architecture's config would build a dense Qwen under
        its name."""
        mt = d.get("model_type", "qwen2")
        if mt not in MODEL_TYPES and mt != "qwen2_moe":
            raise ValueError(
                f"model_type {mt!r} is not implemented by models/qwen.py "
                f"({', '.join(MODEL_TYPES)}); see models.config_from_hf_dict"
            )
        if mt == "qwen2_moe":
            raise ValueError(
                "qwen2_moe checkpoints use always-active SHARED experts, "
                "which this model family does not implement — loading one "
                "would silently drop those weights. Supported MoE family: "
                "qwen3_moe."
            )
        td = {**d, **d.get("text_config", {})}
        vision = None
        image_token_id = d.get("image_token_id", -1)
        if "vision_config" in d:
            from areal_tpu.models.vision import VisionConfig

            vd = d["vision_config"]
            patch = vd.get("patch_size", 14)
            vision = VisionConfig(
                patch_dim=vd.get("in_channels", 3)
                * vd.get("temporal_patch_size", 2)
                * patch
                * patch,
                hidden_size=vd.get("embed_dim", vd.get("hidden_size", 1280)),
                intermediate_size=vd.get(
                    "intermediate_size", 4 * vd.get("embed_dim", 1280)
                ),
                num_layers=vd.get("depth", vd.get("num_hidden_layers", 32)),
                num_heads=vd.get("num_heads", vd.get("num_attention_heads", 16)),
                out_hidden_size=td["hidden_size"],
                spatial_merge=vd.get("spatial_merge_size", 2),
            )
        return cls(
            vocab_size=td["vocab_size"],
            hidden_size=td["hidden_size"],
            intermediate_size=td["intermediate_size"],
            num_layers=td["num_hidden_layers"],
            num_heads=td["num_attention_heads"],
            num_kv_heads=td.get("num_key_value_heads", td["num_attention_heads"]),
            head_dim=td.get("head_dim"),
            rope_theta=td.get("rope_theta", 1e6),
            rms_norm_eps=td.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=td.get("tie_word_embeddings", False),
            # explicit key wins (our own from-scratch exports carry it);
            # else the qwen3-family heuristic
            qk_norm=d.get("qk_norm", mt.startswith("qwen3") or mt == "sdar_moe"),
            attention_bias=td.get("attention_bias", mt.startswith("qwen2")),
            # qwen2_moe / qwen3_moe checkpoints (HF key names)
            num_experts=td.get("num_experts", 0),
            num_experts_per_tok=td.get("num_experts_per_tok", 2),
            moe_intermediate_size=td.get("moe_intermediate_size"),
            norm_topk_prob=td.get("norm_topk_prob", True),
            image_token_id=image_token_id,
            vision=vision,
            **_block_fields(td, mt),
        )

    @classmethod
    def from_hf_path(cls, path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))


def _block_fields(td: dict, mt: str) -> dict:
    """The block-diffusion fields of a ``sdar_moe`` configuration (none for any other type). The published
    config.json has none of them: the family's generation script takes them as arguments, so a caller that serves the
    model states them beside the published keys (benchmarks/chip/configs: ``assumed``)."""
    if mt != "sdar_moe":
        return {}
    out = {
        "block_length": int(td.get("block_length", 4)),
        "mask_token_id": int(td["mask_token_id"]),
        "denoising_steps": int(td.get("denoising_steps", td.get("block_length", 4))),
        "remasking_strategy": str(td.get("remasking_strategy", "low_confidence_dynamic")),
        "confidence_threshold": float(td.get("confidence_threshold", 0.9)),
        **({"dtype": str(td["dtype"])} if "dtype" in td else {}),  # the type it is served in, where the caller states one
    }
    B, steps = out["block_length"], out["denoising_steps"]
    if B < 1 or not 1 <= steps <= B or B % steps:
        raise ValueError(f"denoising_steps {steps} does not divide block_length {B}")
    if out["remasking_strategy"] not in REMASKING_RULES:
        raise ValueError(f"remasking_strategy {out['remasking_strategy']!r} is none of {REMASKING_RULES}")
    if not 0 <= out["mask_token_id"] < td["vocab_size"]:
        raise ValueError(f"mask_token_id {out['mask_token_id']} outside the vocabulary")
    return out


def serving_config(cfg: ModelConfig, dtype: str) -> ModelConfig:
    """``cfg`` as a decode engine serves it."""
    return dataclasses.replace(cfg, dtype=dtype, remat=False)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    D, Q, KV, F, hd = (
        cfg.hidden_size,
        cfg.q_dim,
        cfg.kv_dim,
        cfg.intermediate_size,
        cfg.head_dim_,
    )
    shapes = {
        "wq": (D, Q),
        "wk": (D, KV),
        "wv": (D, KV),
        "wo": (Q, D),
        "input_norm": (D,),
        "post_attn_norm": (D,),
    }
    if cfg.num_experts > 0:
        E = cfg.num_experts
        Fm = cfg.moe_intermediate_size or F
        shapes.update(
            w_router=(D, E),
            we_gate=(E, D, Fm),
            we_up=(E, D, Fm),
            we_down=(E, Fm, D),
        )
    else:
        shapes.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    if cfg.attention_bias:
        shapes.update(bq=(Q,), bk=(KV,), bv=(KV,))
    if cfg.qk_norm:
        shapes.update(q_norm=(hd,), k_norm=(hd,))
    return shapes


def _lora_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """a: [in, r], b: [r, out] per target projection, from the base shapes."""
    base = _layer_shapes(cfg)
    r = cfg.lora_rank
    out = {}
    for t in cfg.lora_targets:
        if t not in base or len(base[t]) != 2:
            raise ValueError(f"LoRA target {t!r} is not a 2-D layer projection")
        d_in, d_out = base[t]
        out[f"{t}_lora_a"] = (d_in, r)
        out[f"{t}_lora_b"] = (r, d_out)
    return out


def init_lora_params(rng: jax.Array, cfg: ModelConfig, dtype=None) -> dict:
    """Stacked-layer LoRA leaves. Standard init: A ~ N(0, 0.02), B = 0 so the
    adapted model starts exactly at the base model."""
    assert cfg.lora_rank > 0
    dtype = dtype or cfg.jax_dtype
    n = cfg.num_layers
    keys = iter(jax.random.split(rng, 2 * len(cfg.lora_targets) + 1))
    out = {}
    for name, shape in _lora_shapes(cfg).items():
        full = (n, *shape)
        if name.endswith("_a"):
            out[name] = (
                0.02 * jax.random.truncated_normal(next(keys), -2, 2, full, jnp.float32)
            ).astype(dtype)
        else:
            out[name] = jnp.zeros(full, dtype)
    return out


def lora_partition_specs(cfg: ModelConfig, fsdp_axis: str | None = "fsdp") -> dict:
    """a keeps the base weight's input-dim sharding, b its output-dim
    sharding; the tiny rank dim is replicated."""
    base = param_partition_specs(
        ModelConfig(**{**cfg.__dict__, "lora_rank": 0}), fsdp_axis
    )["layers"]
    out = {}
    for t in cfg.lora_targets:
        spec = base[t]  # P(None, in_shard, out_shard)
        out[f"{t}_lora_a"] = P(None, spec[1], None)
        out[f"{t}_lora_b"] = P(None, None, spec[2])
    return out


def merge_lora(params: dict, cfg: ModelConfig) -> dict:
    """W' = W + (alpha/r)·A@B per target; drops the adapter leaves. Used for
    HF export and weight updates to inference (the reference ships the PEFT
    config to SGLang instead; on TPU the merged tree IS the serving format)."""
    if cfg.lora_rank <= 0:
        return params
    scale = cfg.lora_alpha / cfg.lora_rank
    layers = dict(params["layers"])
    for t in cfg.lora_targets:
        a = layers.pop(f"{t}_lora_a")
        b = layers.pop(f"{t}_lora_b")
        delta = jnp.einsum("nir,nro->nio", a.astype(jnp.float32), b.astype(jnp.float32))
        layers[t] = (layers[t].astype(jnp.float32) + scale * delta).astype(
            layers[t].dtype
        )
    return {**params, "layers": layers}


def _proj(cfg: ModelConfig, layer: dict, name: str, x: jax.Array) -> jax.Array:
    """x @ W with the LoRA delta when this layer carries adapters.

    When the layer carries an int8-quantized weight (``name_q8`` +
    ``name_scale``, see ``quantize_params_int8``) the matmul reads the int8
    table and applies the per-output-channel scale to the PRODUCT — scaling
    commutes through the contraction, so the dequantized [in, out] matrix is
    never materialized and HBM streams half the bytes. Serving (decode) is
    weight-bandwidth-bound, so this is a throughput lever, not just memory.
    """
    q8 = layer.get(f"{name}_q8")
    if q8 is not None:
        y = x @ q8.astype(x.dtype)
        out = (y.astype(jnp.float32) * layer[f"{name}_scale"]).astype(x.dtype)
    else:
        out = x @ layer[name]
    a = layer.get(f"{name}_lora_a")
    if a is not None:
        scale = cfg.lora_alpha / cfg.lora_rank
        out = out + ((x @ a) @ layer[f"{name}_lora_b"]) * scale
    return out


def serving_limits(cfg) -> dict[str, str]:
    """What this module does not implement for ``cfg``, for the decode engine
    to refuse when it is configured ({feature: why}, ``models/hybrid.py``):
    nothing. Radix reuse with suffix prefill, speculative verification, int8
    weights and pages and tensor parallelism all serve this family. A block
    model's step is no token step: the speculative round (one accepted path
    of single tokens) and the frequency penalty's per-token counts have no
    form for it."""
    if getattr(cfg, "block_length", 1) == 1:
        return {}
    return {
        "reason": "block_diffusion",
        "speculative": "speculative decoding drafts single next tokens; a block-diffusion model commits positions of a block in any order",
        "frequency_penalty": "the frequency penalty counts tokens as they are sampled one a step; a block pass samples candidates it may discard",
    }


def prefill_row_bytes(cfg, bucket: int) -> int:
    """Bytes of the widest activations ONE row of a prefill program of
    ``bucket`` tokens holds through the layers: the residual stream. The
    engine sizes a prefill group by it (``DecodePrograms.prefill_sizes``)."""
    return bucket * cfg.hidden_size * jnp.dtype(cfg.jax_dtype).itemsize


def prefill_attn_launch(cfg, L: int) -> bool:
    """Whether the prefill program of bucket ``L`` attends under the
    latent-attention prompt launch (``models/hybrid.py``): this family has
    no latent attention."""
    del cfg, L
    return False


def kda_prefill_launch(cfg, L: int) -> bool:
    """Whether the prefill program of bucket ``L`` scans ``kda`` layers under
    the prompt-scan launch (``models/hybrid.py``): this family has none."""
    del cfg, L
    return False


# int8 weight-only serving quantization. The reference reaches serving
# quantization through SGLang/vLLM deployment options; the TPU-native engine
# provides it as a first-class transform. Dense projection weights only —
# embed/lm_head stay bf16 (tied-table gather + fp32-sensitive logits), as do
# norms/biases (tiny) and MoE experts (megablox gmm path; follow-up).
QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_dense_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """W[..., in, out] -> (q8 int8, scale fp32[..., 1, out]) with
    W ≈ q8 * scale — the ONE transform shared by server-side quantization
    and the client's q8 weight-update wire format (identical results by
    construction)."""
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    return jnp.round(w32 / s).clip(-127, 127).astype(jnp.int8), s


def quantize_params_int8(params: dict) -> dict:
    """Per-output-channel symmetric int8 quantization of the dense
    projection weights via ``quantize_dense_int8``. Jit-friendly (pure
    jnp); leaves every other weight untouched and drops the bf16
    originals."""
    layers = dict(params["layers"])
    for name in QUANT_TARGETS:
        w = layers.get(name)
        if w is None:
            continue
        layers[f"{name}_q8"], layers[f"{name}_scale"] = quantize_dense_int8(w)
        del layers[name]
    return {**params, "layers": layers}


def quant_partition_specs(cfg: ModelConfig, fsdp_axis: str | None = "fsdp") -> dict:
    """Partition specs matching ``quantize_params_int8`` output: q8 inherits
    the base weight's spec; the per-out-channel scale keeps only the output
    dim's sharding."""
    specs = param_partition_specs(cfg, fsdp_axis)
    layers = dict(specs["layers"])
    for name in QUANT_TARGETS:
        spec = layers.pop(name, None)
        if spec is None:
            continue
        layers[f"{name}_q8"] = spec
        layers[f"{name}_scale"] = P(spec[0], None, spec[2])
    return {**specs, "layers": layers}


def init_params(rng: jax.Array, cfg: ModelConfig, dtype=None) -> dict:
    """Random init (truncated-normal 0.02), stacked-layer layout."""
    dtype = dtype or cfg.jax_dtype
    n = cfg.num_layers
    keys = iter(jax.random.split(rng, 64))

    def dense(key, shape):
        return (0.02 * jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)).astype(dtype)

    layers = {}
    for name, shape in _layer_shapes(cfg).items():
        full = (n, *shape)
        if name.endswith("norm"):
            layers[name] = jnp.ones(full, dtype)
        elif name.startswith("b"):
            layers[name] = jnp.zeros(full, dtype)
        else:
            layers[name] = dense(next(keys), full)
    if cfg.lora_rank > 0:
        layers.update(init_lora_params(next(keys), cfg, dtype))
    params = {
        "embed": dense(next(keys), (cfg.vocab_size, cfg.hidden_size)),
        "layers": layers,
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (cfg.vocab_size, cfg.hidden_size))
    if cfg.vision is not None:
        from areal_tpu.models.vision import init_vision_params

        params["vision"] = init_vision_params(next(keys), cfg.vision, dtype)
    return params


def param_partition_specs(cfg: ModelConfig, fsdp_axis: str | None = "fsdp") -> dict:
    """PartitionSpec tree matching ``init_params`` structure.

    TP ("model" axis) shards head/ffn/vocab dims — the same plan as the
    reference's DTensor colwise/rowwise parallel
    (areal/engine/fsdp_utils/parallel.py:217-365). ZeRO-3-style FSDP shards the
    complementary dim over ``fsdp_axis`` (reference FSDP2 fully_shard role).
    """
    f = fsdp_axis
    layer_specs = {
        "wq": P(None, f, "model"),
        "wk": P(None, f, "model"),
        "wv": P(None, f, "model"),
        "wo": P(None, "model", f),
        "input_norm": P(None, None),
        "post_attn_norm": P(None, None),
    }
    if cfg.num_experts > 0:
        # EP: experts shard over the "expert" mesh axis; inside each expert
        # the ffn dims shard over model/fsdp like the dense plan
        layer_specs.update(
            w_router=P(None, None, None),
            we_gate=P(None, "expert", f, "model"),
            we_up=P(None, "expert", f, "model"),
            we_down=P(None, "expert", "model", f),
        )
    else:
        layer_specs.update(
            w_gate=P(None, f, "model"),
            w_up=P(None, f, "model"),
            w_down=P(None, "model", f),
        )
    if cfg.attention_bias:
        layer_specs.update(bq=P(None, "model"), bk=P(None, "model"), bv=P(None, "model"))
    if cfg.qk_norm:
        layer_specs.update(q_norm=P(None, None), k_norm=P(None, None))
    if cfg.lora_rank > 0:
        layer_specs.update(lora_partition_specs(cfg, fsdp_axis))
    # vocab-sharded over (fsdp, model), D replicated: the distributed lookup
    # in _embed_lookup (zero-3 all_gather over fsdp + masked psum over
    # model) and the vocab-parallel logprob reduction both key off this
    # layout; sharding D instead made XLA replicate the whole table per
    # step (MULTICHIP_r02 involuntary-remat warning)
    vocab_spec = P((f, "model") if f else "model", None)
    specs = {
        "embed": vocab_spec,
        "layers": layer_specs,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = vocab_spec
    if cfg.vision is not None:
        from areal_tpu.models.vision import vision_partition_specs

        specs["vision"] = vision_partition_specs()
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed_lookup(
    embed: jax.Array, ids: jax.Array, dtype, batch_sharded: bool = True
) -> jax.Array:
    """Vocab-parallel embedding lookup.

    ``embed`` is vocab-sharded over ("fsdp", "model") — see
    ``param_partition_specs``. A plain ``jnp.take`` from a sharded table
    makes XLA SPMD replicate the whole [V, D] table on every step
    ("Involuntary full rematerialization", MULTICHIP_r02 — a step-time cliff
    at 151k x D). Instead we express the distributed lookup explicitly:

    - zero-3 leg: ``all_gather`` the local rows over "fsdp" (the same
      per-use param gather FSDP does for every other weight),
    - TP leg: masked local take + ``psum`` over "model" (each rank resolves
      only the ids in its vocab shard; out-of-shard rows contribute zeros).

    Batch dims of ``ids`` stay sharded over ("data","fsdp")/"seq" throughout
    — no replication anywhere. Falls back to ``jnp.take`` when no mesh is
    active (single-chip serving, CPU tests)."""
    axes = dict(get_abstract_mesh().shape)  # empty outside a mesh context
    f_sz, m_sz = axes.get("fsdp", 1), axes.get("model", 1)
    if f_sz * m_sz == 1 or embed.shape[0] % (f_sz * m_sz):
        return jnp.take(embed, ids, axis=0).astype(dtype)
    vloc = embed.shape[0] // (f_sz * m_sz)

    def local_grid(emb, ids_l):
        # ids vary over (data, fsdp, seq): zero-3 leg first — all_gather the
        # fsdp vocab blocks so each rank holds the rows of its "model" index
        # (global row (b*m_sz + m_idx)*vloc + r sits at gathered row
        # b*vloc + r; vocab order is fsdp-major, model-minor) — then masked
        # local take + psum over "model" only.
        emb = jax.lax.all_gather(emb, "fsdp", axis=0, tiled=True)
        m_idx = jax.lax.axis_index("model")
        blk = ids_l // vloc
        ok = (blk % m_sz) == m_idx
        pos = (blk // m_sz) * vloc + ids_l % vloc
        rows = jnp.take(emb, jnp.clip(pos, 0, emb.shape[0] - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0).astype(dtype)
        return jax.lax.psum(rows, "model")

    def local_flat(emb, ids_l):
        # ids replicated (decode steps / serving prefill, where the engine
        # replicates work across spare mesh axes): no gather needed — each
        # rank resolves ids inside its own (fsdp x model) vocab block and
        # one psum over both axes assembles the rows (replicated output).
        f_idx = jax.lax.axis_index("fsdp")
        m_idx = jax.lax.axis_index("model")
        mine = f_idx * m_sz + m_idx
        blk = ids_l // vloc
        ok = blk == mine
        rows = jnp.take(emb, jnp.clip(ids_l % vloc, 0, vloc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0).astype(dtype)
        return jax.lax.psum(rows, ("fsdp", "model"))

    if batch_sharded and ids.ndim == 2:
        # [G, L] training grids — engine-built grids pad G to the DP degree
        # and bucket L; ad-hoc forward() calls (tests, tiny probes) may
        # not divide, and then take the replicated variant below
        d_sz = axes.get("data", 1) * f_sz
        s_sz = axes.get("seq", 1)
        if ids.shape[0] % d_sz == 0 and ids.shape[1] % s_sz == 0:
            return shard_map(
                local_grid,
                in_specs=(P(("fsdp", "model"), None), P(BATCH_AXES, "seq")),
                out_specs=P(BATCH_AXES, "seq", None),
            )(embed, ids)
        # a non-dividing TRAINING grid means the caller skipped the engine's
        # G/L padding — the replicated fallback below works but replicates
        # ids + [G, L, D] output on every rank (the very cliff this function
        # exists to avoid); make that loud
        import warnings

        warnings.warn(
            f"_embed_lookup: grid {ids.shape} not divisible by mesh "
            f"(dp={d_sz}, seq={s_sz}); taking the replicated fallback",
            stacklevel=2,
        )
    reps = (None,) * ids.ndim
    return shard_map(  # replicated ids: decode steps, serving prefill
        local_flat,
        in_specs=(P(("fsdp", "model"), None), P(*reps)),
        out_specs=P(*reps, None),
    )(embed, ids)


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Neox-style rotary embedding. x: [..., L, n_heads, head_dim]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [..., L, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _attention_mask(segment_ids: jax.Array, block: int = 1) -> jax.Array:
    """[G, L] segment ids (0 = pad) -> [G, 1, L, L] bool mask.

    Causality is by *row position* (packed rows concatenate sequences, each
    with its own restarting rope positions), matching the reference's varlen
    flash-attn semantics. ``block`` > 1 (a serving prompt pass of a block
    model, whose row starts on a block boundary): causal over blocks of that
    many positions, both ways inside one.
    """
    L = segment_ids.shape[-1]
    idx = jnp.arange(L)
    if block > 1:
        idx = idx // block
    causal = idx[:, None] >= idx[None, :]
    same_seg = segment_ids[:, :, None] == segment_ids[:, None, :]
    not_pad = (segment_ids != 0)[:, :, None]
    return (causal[None] & same_seg & not_pad)[:, None]


def _sdpa(q, k, v, mask, head_dim: int):
    """XLA attention — single source of truth in ops/attention.py."""
    from areal_tpu.ops.attention import sdpa_xla

    return sdpa_xla(q, k, v, mask, head_dim)


def _ffn(cfg: ModelConfig, h: jax.Array, layer: dict, live: jax.Array | None = None):
    """Feed-forward for the cache paths (prefill/decode): dense SwiGLU or
    MoE. Accepts [..., D]; MoE internally needs [G, L, D]. With ``live``
    (bool, h's shape less D: the rows that hold a request) the experts'
    load comes back too, (out, load [E] int32), counted over those rows."""
    if cfg.num_experts > 0:
        from areal_tpu.models.moe import moe_ffn, moe_ffn_dropless

        squeeze = h.ndim == 2
        h3 = h[:, None] if squeeze else h
        if live is not None:
            out, _, load = moe_ffn_dropless(h3, layer, cfg, live=live[:, None] if squeeze else live)
            return (out[:, 0] if squeeze else out), load
        out, _ = moe_ffn(h3, layer, cfg)
        return out[:, 0] if squeeze else out
    return _proj(
        cfg,
        layer,
        "w_down",
        jax.nn.silu(_proj(cfg, layer, "w_gate", h)) * _proj(cfg, layer, "w_up", h),
    )


def _decoder_layer(cfg: ModelConfig, x, layer, mask, positions, impl=None):
    """One transformer block. x: [G, L, D]. ``impl`` overrides the attention
    dispatch (forward() resolves it once; explicit masks force 'xla')."""
    G, L, D = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

    with jax.named_scope("attn_proj"):
        h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        q = _proj(cfg, layer, "wq", h)
        k = _proj(cfg, layer, "wk", h)
        v = _proj(cfg, layer, "wv", h)
        if cfg.attention_bias:
            q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
        q = q.reshape(G, L, H, hd)
        k = k.reshape(G, L, KH, hd)
        v = v.reshape(G, L, KH, hd)
        if cfg.qk_norm:
            q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
            k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attn"):
        if KH != H:
            k = jnp.repeat(k, H // KH, axis=2)
            v = jnp.repeat(v, H // KH, axis=2)
        if impl is None:
            from areal_tpu.ops.attention import resolve_impl

            impl = resolve_impl(cfg.attn_impl, L, hd)
        if impl == "ring":
            # context parallelism: q/k/v stay seq-sharded; K/V rotate the ring
            # (parallel/ring_attention.py). mask here is (segment_ids, col_index).
            from areal_tpu.parallel.ring_attention import ring_attention

            seg, col = mask
            q = _shard(q, P(BATCH_AXES, "seq", "model", None))
            k = _shard(k, P(BATCH_AXES, "seq", "model", None))
            v = _shard(v, P(BATCH_AXES, "seq", "model", None))
            attn = ring_attention(q, k, v, seg, col)
        else:
            # Ulysses region (reference models/fsdp/ulysses.py:44-202): outside
            # attention, activations are seq-sharded; inside, heads are sharded
            # over model×seq and the sequence is whole. GSPMD lowers the
            # [L/sp, H] -> [L, H/sp] reshard to the head<->seq all-to-all — the
            # a2a moves 1/sp of the activation vs. a full all-gather. kv heads
            # were already replicated to H above (the GQA sp>kv_heads case,
            # ulyssess_patch.py:43-47).
            q = _shard(q, P(BATCH_AXES, None, ("model", "seq"), None))
            k = _shard(k, P(BATCH_AXES, None, ("model", "seq"), None))
            v = _shard(v, P(BATCH_AXES, None, ("model", "seq"), None))
            if impl == "pallas":
                from areal_tpu.ops.attention import flash_train

                attn = flash_train(q, k, v, mask)  # mask is a FlashMask here
            elif impl == "pallas_fwd":
                # leaner forward-only kernel (no VJP residuals) for the no-grad
                # hot paths: logprob recompute, ref/prox forward, eval
                from areal_tpu.ops.attention import flash_fwd_pallas

                attn = flash_fwd_pallas(q, k, v, mask)  # mask is segment_ids
            else:
                attn = _sdpa(q, k, v, mask, hd)
    with jax.named_scope("attn_proj"):
        attn = attn.reshape(G, L, H * hd)
        x = x + _shard(_proj(cfg, layer, "wo", attn), P(BATCH_AXES, "seq", None))

    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
        if cfg.num_experts > 0:
            from areal_tpu.models.moe import moe_ffn

            ff_out, aux = moe_ffn(h, layer, cfg)
            return x + ff_out, aux
        ff = jax.nn.silu(_proj(cfg, layer, "w_gate", h)) * _proj(cfg, layer, "w_up", h)
        x = x + _shard(_proj(cfg, layer, "w_down", ff), P(BATCH_AXES, "seq", None))
        return x, jnp.float32(0.0)


def _shard(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint that is a no-op outside a mesh context and
    drops manual axes inside shard_map regions (the PP path wraps the
    layer stack in shard_map over ``pipe``; a raw constraint naming a
    Manual axis dies at lowering)."""
    from areal_tpu.utils.jax_compat import with_sharding_constraint

    return with_sharding_constraint(x, spec)


def forward(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [G, L] int32
    segment_ids: jax.Array,  # [G, L] int32, 0 = padding
    positions: jax.Array,  # [G, L] int32, restart per segment
    attn_mask: jax.Array | None = None,  # [G, 1, L, L] override (tree training)
    with_aux: bool = False,  # also return the summed MoE router aux loss
    no_grad: bool = False,  # forward-only: use the leaner fwd flash kernel
    image_embeds: jax.Array | None = None,  # [G, L, D] precomputed vision embeds
) -> jax.Array:
    """Decoder body -> final hidden states [G, L, D] (+ aux when asked)."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], input_ids, cfg.jax_dtype)
        if image_embeds is not None and cfg.image_token_id >= 0:
            # VLM: <|image_pad|> positions take the vision tower's output
            # (precomputed and positioned by the caller; models/vision.py)
            img_pos = (input_ids == cfg.image_token_id)[..., None]
            x = jnp.where(img_pos, image_embeds.astype(cfg.jax_dtype), x)
    x = _shard(x, P(BATCH_AXES, "seq", None))
    from areal_tpu.ops.attention import resolve_impl

    if attn_mask is not None:
        # explicit mask (e.g. ancestor masks from models/tree.py) forces the
        # dense-mask XLA path; the flash/ring kernels only know causal+segment
        impl = "xla"
        mask = attn_mask
    else:
        impl = resolve_impl(cfg.attn_impl, segment_ids.shape[-1], cfg.head_dim_)
        if impl == "ring":
            # ring attention masks from per-token metadata, not an [L, L] matrix
            L = segment_ids.shape[-1]
            col = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), segment_ids.shape)
            mask = (segment_ids, col)
        elif impl == "pallas":
            if no_grad:
                impl = "pallas_fwd"
                mask = segment_ids  # the forward kernel masks from segment ids alone
            else:
                from areal_tpu.ops.attention import flash_mask

                # once a forward pass, not once a layer inside the scan below
                mask = flash_mask(segment_ids, cfg.head_dim_)
        else:
            mask = _attention_mask(segment_ids)

    layer_fn = partial(_decoder_layer, cfg, impl=impl)
    if cfg.remat:
        policies = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots_nobatch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "everything": jax.checkpoint_policies.everything_saveable,
        }
        if cfg.remat_policy not in policies:
            raise ValueError(
                f"remat_policy={cfg.remat_policy!r}; valid: {sorted(policies)}"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policies[cfg.remat_policy])

    def body(x, layer):
        x, aux = layer_fn(x, layer, mask, positions)
        return x, aux

    x, aux = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if with_aux:
        return hidden, aux.sum()
    return hidden


def _lm_head_weight(params: dict) -> jax.Array:
    return params.get("lm_head", params["embed"])  # [V, D]


def compute_logits(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """[..., D] -> [..., V] logits in fp32 (small decodes only — for training
    use chunked_logprobs_entropy). The matmul runs in the weight dtype with
    fp32 ACCUMULATION — casting the [V, D] table to fp32 first would either
    materialize a second full-size copy per step or push the matmul off the
    bf16 MXU path (decode-step hot path)."""
    w = _lm_head_weight(params)
    return jax.lax.dot_general(
        hidden.astype(w.dtype),
        w,
        (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def chunked_logprobs_entropy(
    params: dict,
    cfg: ModelConfig,
    hidden: jax.Array,  # [G, L, D]
    labels: jax.Array,  # [G, L] int32 (next-token ids)
    chunk_size: int = 1024,
    temperature: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """log p(label) and entropy per position, without materializing [T, V].

    Tokens are processed in chunks under ``lax.map`` + remat: each chunk
    computes its logits, logsumexp, label logprob and entropy, then the logits
    are discarded (recomputed in backward). This is the TPU replacement for
    the reference's vocab-parallel logprob path
    (areal/utils/functional/vocab_parallel.py) — with a "model"-sharded vocab
    dim, XLA additionally distributes each chunk's reduction.
    """
    G, L, D = hidden.shape
    w = _lm_head_weight(params)
    T = G * L
    pad = (-T) % chunk_size
    flat_h = hidden.reshape(T, D)
    flat_y = labels.reshape(T)
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_y = jnp.pad(flat_y, (0, pad))
    n_chunks = (T + pad) // chunk_size
    flat_h = flat_h.reshape(n_chunks, chunk_size, D)
    flat_y = flat_y.reshape(n_chunks, chunk_size)

    @jax.checkpoint
    def one_chunk(args):
        h, y = args
        logits = jnp.einsum("td,vd->tv", h, w).astype(jnp.float32)
        if temperature != 1.0:
            logits = logits / temperature
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        probs = jax.nn.softmax(logits, axis=-1)
        ent = lse - jnp.sum(probs * logits, axis=-1)
        return label_logit - lse, ent

    logp, ent = jax.lax.map(one_chunk, (flat_h, flat_y))
    logp = logp.reshape(-1)[:T].reshape(G, L)
    ent = ent.reshape(-1)[:T].reshape(G, L)
    return logp, ent


# ---------------------------------------------------------------------------
# HF name mapping (for the safetensors loader/saver, models/hf.py)
# ---------------------------------------------------------------------------

# our layer param -> (HF suffix, needs_transpose)
_HF_LAYER_MAP = {
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "bq": ("self_attn.q_proj.bias", False),
    "bk": ("self_attn.k_proj.bias", False),
    "bv": ("self_attn.v_proj.bias", False),
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
    "input_norm": ("input_layernorm.weight", False),
    "post_attn_norm": ("post_attention_layernorm.weight", False),
}


def hf_name_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    """Flat map: our param path -> (HF name, transpose). Dense leaves map as
    "layers/<l>/<name>"; MoE expert leaves (stacked [L, E, ...] here, one
    tensor per (layer, expert) in HF qwen2/3_moe checkpoints) map as
    "layers/<l>/<name>/<e>"."""
    out: dict[str, tuple[str, bool]] = {
        "embed": ("model.embed_tokens.weight", False),
        "final_norm": ("model.norm.weight", False),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ("lm_head.weight", False)
    moe_map = {
        "w_router": ("mlp.gate.weight", True),
        "we_gate": ("mlp.experts.{e}.gate_proj.weight", True),
        "we_up": ("mlp.experts.{e}.up_proj.weight", True),
        "we_down": ("mlp.experts.{e}.down_proj.weight", True),
    }
    for name in _layer_shapes(cfg):
        if name in ("we_gate", "we_up", "we_down"):
            suffix, transpose = moe_map[name]
            for i in range(cfg.num_layers):
                for e in range(cfg.num_experts):
                    out[f"layers/{i}/{name}/{e}"] = (
                        f"model.layers.{i}.{suffix.format(e=e)}",
                        transpose,
                    )
            continue
        hf_suffix, transpose = moe_map.get(name) or _HF_LAYER_MAP[name]
        for i in range(cfg.num_layers):
            out[f"layers/{i}/{name}"] = (f"model.layers.{i}.{hf_suffix}", transpose)
    return out


def make_causal_inputs(
    input_ids: np.ndarray, segment_ids: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """labels + label validity mask for next-token prediction on packed rows.

    Position t predicts token t+1 *within the same segment*; the last token of
    each segment (and padding) is masked out.
    """
    labels = np.roll(input_ids, -1, axis=-1)
    next_seg = np.roll(segment_ids, -1, axis=-1)
    next_seg[..., -1] = 0
    valid = (segment_ids != 0) & (segment_ids == next_seg)
    return labels, valid



# ---------------------------------------------------------------------------
# incremental decoding (inference server path)
# ---------------------------------------------------------------------------


def forward_prefill(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [A, P]
    positions: jax.Array,  # [A, P]
    seg: jax.Array | None = None,  # [A, P] 1=valid 0=pad; default all-valid
    image_embeds: jax.Array | None = None,  # [A, P, D] VLM vision embeds
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched prompt pass: returns (hidden [A, P, D], k, v) where k/v are
    [n_layers, A, P, KH, hd] (post-rope, pre-GQA-repeat) for cache fill.

    Batching prompts into one pass amortises the full-parameter HBM read
    across A admits — the round-1 serial batch-1 prefill paid that read per
    request (VERDICT "What's weak" #2).
    """
    if seg is None:
        seg = jnp.ones_like(input_ids)
    # serving prefill runs replicated over any spare mesh axes (the decode
    # engine's data axis absorbs leftover devices) — ids are not sharded
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], input_ids, cfg.jax_dtype, batch_sharded=False)
        if image_embeds is not None and cfg.image_token_id >= 0:
            img_pos = (input_ids == cfg.image_token_id)[..., None]
            x = jnp.where(img_pos, image_embeds.astype(cfg.jax_dtype), x)
    mask = _attention_mask(seg, cfg.block_length)
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

    def body(x, layer):
        G, L, D = x.shape
        with jax.named_scope("attn_proj"):
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q = _proj(cfg, layer, "wq", h)
            k = _proj(cfg, layer, "wk", h)
            v = _proj(cfg, layer, "wv", h)
            if cfg.attention_bias:
                q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
            q = q.reshape(G, L, H, hd)
            k = k.reshape(G, L, KH, hd)
            v = v.reshape(G, L, KH, hd)
            if cfg.qk_norm:
                q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        k_cache, v_cache = k, v
        with jax.named_scope("attn"):
            if KH != H:
                k = jnp.repeat(k, H // KH, axis=2)
                v = jnp.repeat(v, H // KH, axis=2)
            attn = _sdpa(q, k, v, mask, hd).reshape(G, L, H * hd)
        with jax.named_scope("attn_proj"):
            x = x + _proj(cfg, layer, "wo", attn)
        with jax.named_scope("mlp"):
            h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
            x = x + _ffn(cfg, h, layer)
        return x, (k_cache, v_cache)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return hidden, ks, vs


def prefill_into_cache(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    ids: jax.Array,  # [A, bucket]
    plens: jax.Array,  # [A]
    flat_pages: jax.Array,  # [A * bucket/psz]
    slots: jax.Array,  # [A] target slots: nothing of this family is slot-indexed
    *,
    page_size: int,
    image_embeds: jax.Array | None = None,
) -> dict:
    """What the engine's prefill program does for this family: the K and V
    of every prompt token, padding included, scattered into the rows' pages.
    No gather or merge: rows at and after each prompt's last token are
    overwritten by decode before they become readable."""
    from areal_tpu.inference import paged_kv

    del slots
    bucket = ids.shape[1]
    positions = jnp.broadcast_to(jnp.arange(bucket, dtype=jnp.int32)[None], ids.shape)
    seg = (jnp.arange(bucket, dtype=jnp.int32)[None] < plens[:, None]).astype(jnp.int32)
    _, ks, vs = forward_prefill(params, cfg, ids, positions, seg, image_embeds=image_embeds)
    # ks/vs: [n_layers, A, bucket, KH, hd] -> page scatter
    with jax.named_scope("kv_write"):
        return paged_kv.scatter_prefill(cache, ks, vs, flat_pages, page_size)


def _gather_window(cache: dict, name: str, li, page_table: jax.Array) -> jax.Array:
    """Layer ``li``'s window pages of ``cache[name]``, gathered dense:
    [A, wp] page ids -> [A, wp * psz, KH, d] (d = head_dim for pages, 1 for
    scales, which the pool stores lane-major, [.., 1, psz])."""
    lay = jax.lax.dynamic_index_in_dim(cache[name], li, 0, keepdims=False)
    g = lay[:, page_table]  # [KH, A, wp, psz, d]
    if name.endswith("_scale"):
        g = jnp.swapaxes(g, -1, -2)
    A, wp = page_table.shape
    KH, psz, d = g.shape[0], g.shape[3], g.shape[4]
    return jnp.transpose(g, (1, 2, 3, 0, 4)).reshape(A, wp * psz, KH, d)


def forward_prefill_paged(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [A, B] suffix tokens (page-aligned start)
    positions: jax.Array,  # [A, B] ABSOLUTE rope positions (prefix_len + i)
    seg: jax.Array,  # [A, B] 1=valid 0=pad
    cache: dict,  # k/v [n_layers, KH, n_pages, psz, hd] (+ scales under quant)
    page_table: jax.Array,  # [A, wp] int32 pages holding the cached prefix
    prefix_lens: jax.Array,  # [A] int32 tokens cached (page-aligned; 0 = none)
    use_kernel: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Suffix-only prefill over a radix-cached prefix: like
    ``forward_prefill`` but each row's queries additionally attend over its
    cached prefix pages, so only the NON-cached suffix pays prefill FLOPs.
    Returns (hidden, ks, vs) for the suffix positions only — the caller
    scatters them into fresh pages; the prefix pages are read, never
    written (aliased, possibly shared).

    ``use_kernel=False`` (the default and the reference): gather + grouped
    einsum, the same numerics as ``paged_attention_xla`` — one extra HBM
    read+write of the gathered prefix per layer. ``use_kernel=True`` runs
    the Pallas suffix-prefill kernel (ops/paged_suffix_attention.py,
    chain-mask launch): the prefix streams page-by-page through VMEM and
    never materializes; padded rows output zeros instead of the dense
    path's discarded garbage (their KV lands in trash page 0 either way).
    """
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], input_ids, cfg.jax_dtype, batch_sharded=False)
    suf_mask = _attention_mask(seg, cfg.block_length)  # [A, 1, B, B] causal-within-suffix (the suffix starts on a page, so on a block)
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    G = H // KH
    A, B = input_ids.shape
    wp = page_table.shape[1]
    psz = cache["k"].shape[3]
    W = wp * psz
    kv_quant = "k_scale" in cache
    # prefix columns valid below each row's cached length; padded suffix
    # rows (seg == 0) attend nowhere in the prefix block
    pre_valid = (
        (jnp.arange(W)[None, :] < prefix_lens[:, None])[:, None, :]
        & (seg != 0)[:, :, None]
    )  # [A, B, W]

    def gather(name, li):
        return _gather_window(cache, name, li, page_table)

    def body(x, scanned):
        layer, li = scanned
        with jax.named_scope("attn_proj"):
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q = _proj(cfg, layer, "wq", h)
            k = _proj(cfg, layer, "wk", h)
            v = _proj(cfg, layer, "wv", h)
            if cfg.attention_bias:
                q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
            q = q.reshape(A, B, H, hd)
            k = k.reshape(A, B, KH, hd)
            v = v.reshape(A, B, KH, hd)
            if cfg.qk_norm:
                q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        k_cache, v_cache = k, v
        if use_kernel:
            with jax.named_scope("attn"):
                # Pallas chain-mask launch: the prefix streams through VMEM
                # (double-buffered page DMA + online softmax), quantized pages
                # dequantize in-kernel with narrow scales
                from areal_tpu.ops.paged_suffix_attention import (
                    paged_suffix_attention,
                )

                attn = paged_suffix_attention(
                    q,
                    k,
                    v,
                    cache["k"],
                    cache["v"],
                    li,
                    prefix_lens,
                    page_table,
                    suf_mask[:, 0],  # [A, B, B] causal & row/col validity
                    k_scales=cache.get("k_scale"),
                    v_scales=cache.get("v_scale"),
                ).reshape(A, B, H * hd)
            with jax.named_scope("attn_proj"):
                x = x + _proj(cfg, layer, "wo", attn.astype(x.dtype))
            with jax.named_scope("mlp"):
                h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
                x = x + _ffn(cfg, h, layer)
            return x, (k_cache, v_cache)
        with jax.named_scope("attn"):
            kp = gather("k", li)  # [A, W, KH, hd]
            vp = gather("v", li)
            if kv_quant:
                from areal_tpu.inference.paged_kv import dequantize_kv

                kp = dequantize_kv(kp, gather("k_scale", li), q.dtype)
                vp = dequantize_kv(vp, gather("v_scale", li), q.dtype)
            # GQA repeat + concat(prefix, suffix) along the KV length, then the
            # same batched-matmul einsum layout as sdpa_xla — grouped 5D
            # einsums with split batch axes lower an order of magnitude slower
            if KH != H:
                kp = jnp.repeat(kp, G, axis=2)
                vp = jnp.repeat(vp, G, axis=2)
                k_r = jnp.repeat(k, G, axis=2)
                v_r = jnp.repeat(v, G, axis=2)
            else:
                k_r, v_r = k, v
            k_full = jnp.concatenate([kp, k_r], axis=1)  # [A, W + B, H, hd]
            v_full = jnp.concatenate([vp, v_r], axis=1)
            mask = jnp.concatenate(
                [pre_valid[:, None], suf_mask], axis=-1
            )  # [A, 1, B, W + B]
            attn = _sdpa(q, k_full, v_full, mask, hd).reshape(A, B, H * hd)
        with jax.named_scope("attn_proj"):
            x = x + _proj(cfg, layer, "wo", attn)
        with jax.named_scope("mlp"):
            h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
            x = x + _ffn(cfg, h, layer)
        return x, (k_cache, v_cache)

    n_layers = cfg.num_layers
    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(n_layers))
    )
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return hidden, ks, vs


def forward_verify_paged(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [S, B] pending token (root) + draft tree nodes
    positions: jax.Array,  # [S, B] ABSOLUTE rope positions (root pos + depth)
    tree_mask: jax.Array,  # [S, B, B] bool: node row attends node col
    cache: dict,  # k/v [n_layers, KH, n_pages, psz, hd] (+ scales under quant)
    page_table: jax.Array,  # [S, wp] int32 pages holding the cached context
    prefix_lens: jax.Array,  # [S] int32 tokens already in pages (= root pos)
    use_kernel: bool = False,
    live: jax.Array | None = None,  # [S] bool: with it, a model with experts also returns their loads
) -> tuple[jax.Array, ...]:
    """Speculative-verify forward: score every slot's draft token tree in
    ONE pass over the paged KV pool — the step that used to produce one
    token per slot produces logits for B tree nodes per slot.

    Structurally ``forward_prefill_paged`` with two twists: the in-flight
    suffix mask is the draft tree's ancestor-or-self mask (a chain draft
    degenerates to plain causal), and ``prefix_lens`` is the slot's live
    decode position rather than a page-aligned radix prefix. Returns
    (hidden [S, B, D], ks, vs [L, S, B, KH, hd]) — KV is NOT written here;
    the caller routes only accepted-path rows into real pages
    (paged_kv.scatter_token_rows) so rejected drafts never land.

    ``use_kernel=True`` runs the Pallas tree-verify launch
    (ops/paged_suffix_attention.py, the same kernel body as suffix-prefill
    with the ancestor tree mask as the suffix-mask operand) — the drafter
    sets every node's self bit (inference/speculative.py), so the kernel's
    diagonal row-validity rule admits every row to the committed prefix,
    matching this function's broadcast ``pre_valid`` exactly.

    A block pass of a block-diffusion model is this forward with the mask
    all ones (``forward_block_paged``). Given ``live``, the rows of the
    slots it marks count as the experts' load and a fourth value comes
    back, the loads [n_layers, E] int32 (nothing of a slot ``live`` leaves
    out is read: its rows are computed and discarded, as an ended slot's
    are in a decode step).
    """
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], input_ids, cfg.jax_dtype, batch_sharded=False)
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    G = H // KH
    S, B = input_ids.shape
    with_load = live is not None and cfg.num_experts > 0
    row_live = jnp.broadcast_to(live[:, None], (S, B)) if with_load else None

    def ffn(h, layer):
        if with_load:
            return _ffn(cfg, h, layer, row_live)
        return _ffn(cfg, h, layer), None

    wp = page_table.shape[1]
    psz = cache["k"].shape[3]
    W = wp * psz
    kv_quant = "k_scale" in cache
    # every node attends the whole committed context; tree structure only
    # constrains attention among the in-flight nodes themselves
    pre_valid = jnp.broadcast_to(
        (jnp.arange(W)[None, :] < prefix_lens[:, None])[:, None, None, :],
        (S, 1, B, W),
    )
    suf_mask = tree_mask[:, None]  # [S, 1, B, B]

    def gather(name, li):
        return _gather_window(cache, name, li, page_table)

    def body(x, scanned):
        layer, li = scanned
        with jax.named_scope("attn_proj"):
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q = _proj(cfg, layer, "wq", h)
            k = _proj(cfg, layer, "wk", h)
            v = _proj(cfg, layer, "wv", h)
            if cfg.attention_bias:
                q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
            q = q.reshape(S, B, H, hd)
            k = k.reshape(S, B, KH, hd)
            v = v.reshape(S, B, KH, hd)
            if cfg.qk_norm:
                q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        k_cache, v_cache = k, v
        if use_kernel:
            with jax.named_scope("attn"):
                from areal_tpu.ops.paged_suffix_attention import (
                    paged_suffix_attention,
                )

                attn = paged_suffix_attention(
                    q,
                    k,
                    v,
                    cache["k"],
                    cache["v"],
                    li,
                    prefix_lens,
                    page_table,
                    tree_mask,  # [S, B, B] ancestor-or-self
                    k_scales=cache.get("k_scale"),
                    v_scales=cache.get("v_scale"),
                ).reshape(S, B, H * hd)
            with jax.named_scope("attn_proj"):
                x = x + _proj(cfg, layer, "wo", attn.astype(x.dtype))
            with jax.named_scope("mlp"):
                h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
                out, load = ffn(h, layer)
                x = x + out
            return x, (k_cache, v_cache, load)
        with jax.named_scope("attn"):
            kp = gather("k", li)  # [S, W, KH, hd]
            vp = gather("v", li)
            if kv_quant:
                from areal_tpu.inference.paged_kv import dequantize_kv

                kp = dequantize_kv(kp, gather("k_scale", li), q.dtype)
                vp = dequantize_kv(vp, gather("v_scale", li), q.dtype)
            if KH != H:
                kp = jnp.repeat(kp, G, axis=2)
                vp = jnp.repeat(vp, G, axis=2)
                k_r = jnp.repeat(k, G, axis=2)
                v_r = jnp.repeat(v, G, axis=2)
            else:
                k_r, v_r = k, v
            k_full = jnp.concatenate([kp, k_r], axis=1)  # [S, W + B, H, hd]
            v_full = jnp.concatenate([vp, v_r], axis=1)
            mask = jnp.concatenate([pre_valid, suf_mask], axis=-1)  # [S,1,B,W+B]
            attn = _sdpa(q, k_full, v_full, mask, hd).reshape(S, B, H * hd)
        with jax.named_scope("attn_proj"):
            x = x + _proj(cfg, layer, "wo", attn)
        with jax.named_scope("mlp"):
            h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
            out, load = ffn(h, layer)
            x = x + out
        return x, (k_cache, v_cache, load)

    x, (ks, vs, loads) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.num_layers))
    )
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if with_load:
        return hidden, ks, vs, loads
    return hidden, ks, vs


def block_attn_tokens_fetched(prefix_lens: jax.Array, wp: int, page_size: int, use_kernel: bool) -> jax.Array:
    """Cached tokens the in-block attention of one block pass fetches, a layer
    and KV head, summed over the slots (int32): the Pallas launch reads whole
    blocks of ``default_ppcb(wp)`` pages up to each slot's ``prefix_lens``
    (ops/paged_suffix_attention.py); the gather path reads every slot's whole
    window of ``wp`` pages."""
    from areal_tpu.ops.paged_suffix_attention import default_ppcb

    if not use_kernel:
        return jnp.int32(prefix_lens.shape[0] * wp * page_size)
    bs = default_ppcb(wp) * page_size
    return jnp.sum(-(-prefix_lens // bs) * bs, dtype=jnp.int32)


def forward_block_paged(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,  # [S, B] the block's clean ids, ``mask_token_id`` where a position is not committed
    start: jax.Array,  # [S] absolute position of the block's first token (a multiple of B)
    live: jax.Array,  # [S] bool slots that hold a request
    cache: dict,
    page_table: jax.Array,  # [S, wp]
    use_kernel: bool = False,
) -> tuple[jax.Array, ...]:
    """One pass of a block-diffusion model over every slot's current block:
    its B rows attend the committed blocks before it (``start`` tokens, from
    the slot's pages) and each other, every pair allowed. It is the verify
    forward under an all-ones in-flight mask, one body: (hidden [S, B, D],
    the rows' keys and values [n_layers, S, B, KH, hd], and the experts'
    loads over the live slots' rows). Nothing is written: the caller puts a
    block's keys and values into its pages on the pass that finds it clean.
    A slot ``live`` leaves out reads no page (its prefix counts 0 tokens)."""
    S, B = input_ids.shape
    positions = start[:, None] + jnp.arange(B, dtype=jnp.int32)[None]
    return forward_verify_paged(
        params, cfg, input_ids, positions, jnp.ones((S, B, B), bool), cache, page_table,
        jnp.where(live, start, 0), use_kernel=use_kernel, live=live,
    )



def forward_decode_paged(
    params: dict,
    cfg: ModelConfig,
    ids: jax.Array,  # [S] current tokens
    positions: jax.Array,  # [S] rope positions of these tokens
    cache: dict,  # k/v [n_layers, KH, n_pages, page_size, hd]
    page_table: jax.Array,  # [S, wp] int32 page ids covering the window
    *,
    page_size: int,
    active: jax.Array | None = None,  # [S] live slots: not needed, see below
    use_kernel: bool = True,
) -> tuple[jax.Array, dict]:
    """One incremental step for all S slots over the *paged* KV cache.

    Every slot is stepped, ended ones too: what they write lands in the
    trash page (gather path), nowhere (kernel path: a slot whose table row
    starts at the trash page is in neither kernel's work list) or in a row
    decode rewrites before reading, so ``active`` (which a family with
    recurrent state needs, models/hybrid.py) changes nothing here.

    The current token's k/v lands at page ``table[s, pos//psz]`` row
    ``pos % psz`` (``paged_kv.write_decode_rows``: one Pallas launch a layer
    over the live slots under ``use_kernel``, per-head XLA scatters
    otherwise); attention reads each slot's pages via the TPU
    paged-attention kernel (ops/paged_attention_q8.py), or a gather + grouped
    einsum off-TPU. This is the serving design SURVEY §7.1 specifies in
    place of the reference's SGLang paged/radix attention
    (reference blog/AReaL_v0_3.md:266): KV HBM ∝ used tokens, so 4K–32K
    contexts fit at real concurrency (VERDICT r02 missing #1).
    """
    from areal_tpu.inference import paged_kv

    del active
    S = ids.shape[0]
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    # a decode chunk's counts (``count_shapes``) ride with the cache and are no pool: out of the layers' way
    counts = {name: cache[name] for name in cfg.count_shapes if name in cache}
    cache = {name: leaf for name, leaf in cache.items() if name not in counts}
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], ids, cfg.jax_dtype)  # [S, D]
    pos1 = positions[:, None]
    lengths = (positions + 1).astype(jnp.int32)
    slot = jnp.arange(S)
    write_page = page_table[slot, positions // page_size]  # [S]
    write_off = positions % page_size  # [S]
    kv_quant = "k_scale" in cache  # int8 pages + per-vector scales
    if use_kernel:
        from areal_tpu.ops.paged_attention_q8 import (
            live_order,
            paged_attention_stacked,
            shared_decode_schedule,
        )

        # a slot whose request has ended keeps its last position, and the
        # engine points its whole table row at the pool's trash page 0 (never
        # allocated): it has nothing to attend over, and its row of the
        # output is not read. The kernel's work list is the same for every
        # layer, so it is made here, once a step: each distinct block of
        # pages once, with the slots whose rows name it (a group's siblings
        # hold the first one's prompt pages, a prefix-cache hit the cached).
        attn_lengths = jnp.where(page_table[:, 0] == 0, 0, lengths)
        ppcb = paged_kv.choose_ppcb(page_table.shape[1])
        with jax.named_scope("attn"):
            schedule, fetch = shared_decode_schedule(attn_lengths, page_table, page_size, ppcb)
            counts = fetch.counted(counts)
        # the same slots are the ones whose row is written (one Pallas launch
        # a layer, ops/paged_kv_write.py): an ended slot's row, which the
        # scatters send to the trash page, is not written at all
        with jax.named_scope("kv_write"):
            kv_live = live_order(page_table[:, 0] != 0)
    else:
        kv_live = None

    def body(carry, scanned):
        x, c = carry
        layer, li = scanned
        with jax.named_scope("attn_proj"):
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q = _proj(cfg, layer, "wq", h)
            k = _proj(cfg, layer, "wk", h)
            v = _proj(cfg, layer, "wv", h)
            if cfg.attention_bias:
                q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
            q = q.reshape(S, 1, H, hd)
            k = k.reshape(S, 1, KH, hd)
            v = v.reshape(S, 1, KH, hd)
            if cfg.qk_norm:
                q = _rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
            q = _rope(q, pos1, cfg.rope_theta)[:, 0]  # [S, H, hd]
            k = _rope(k, pos1, cfg.rope_theta)[:, 0]  # [S, KH, hd]
            v = v[:, 0]
        with jax.named_scope("kv_write"):
            c = paged_kv.write_decode_rows(c, li, k, v, write_page, write_off, kv_live)
        with jax.named_scope("attn"):
            if use_kernel:
                # STACKED launch: the kernel slices ref.at[li] internally. A
                # dynamic_index_in_dim layer slice here would force XLA to
                # materialize a copy of every layer's pages every step (a
                # pallas operand must be a real buffer) — measured as
                # full-cache r/w traffic per decode step (docstring of
                # ops/paged_attention_q8.py)
                attn = paged_attention_stacked(
                    q,
                    c["k"],
                    c["v"],
                    li,
                    attn_lengths,
                    page_table,
                    pages_per_compute_block=ppcb,
                    schedule=schedule,
                    k_scales=c.get("k_scale"),
                    v_scales=c.get("v_scale"),
                )
            else:
                sl = {
                    name: jax.lax.dynamic_index_in_dim(c[name], li, 0, keepdims=False)
                    for name in c
                }
                scales = (
                    dict(k_scales=sl["k_scale"], v_scales=sl["v_scale"])
                    if kv_quant
                    else {}
                )
                attn = paged_kv.paged_attention_xla(
                    q, sl["k"], sl["v"], lengths, page_table, **scales
                )
            attn = attn.reshape(S, H * hd).astype(x.dtype)
        with jax.named_scope("attn_proj"):
            x = x + _proj(cfg, layer, "wo", attn)
        with jax.named_scope("mlp"):
            h = _rms_norm(x, layer["post_attn_norm"], cfg.rms_norm_eps)
            x = x + _ffn(cfg, h, layer)
        return (x, c), None

    (x, out_cache), _ = jax.lax.scan(
        body,
        (x, dict(cache)),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return hidden, {**out_cache, **counts}


