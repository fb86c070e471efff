"""HF checkpoint interop: safetensors <-> stacked-layer JAX params.

Plays the role of the reference's HF load/save paths
(areal/engine/fsdp_engine.py:289-341 memory-efficient load,
:1164-1204 safetensors export; areal/models/mcore/hf_{load,save}.py bridges)
— re-designed for JAX: tensors are read lazily per-name from the safetensors
index, stacked across layers on host, and device_put with the target sharding
so each chip only materializes its shard.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from safetensors import safe_open
from safetensors.numpy import save_file

from areal_tpu.models import config_from_hf_path, family_of
from areal_tpu.models.qwen import ModelConfig


def _open_shards(path: str) -> dict[str, str]:
    """HF tensor name -> safetensors file path (handles sharded checkpoints)."""
    index_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        return {k: os.path.join(path, v) for k, v in index["weight_map"].items()}
    single = os.path.join(path, "model.safetensors")
    with safe_open(single, framework="numpy") as f:
        return {k: single for k in f.keys()}


def load_params_from_hf(
    path: str,
    cfg: ModelConfig | None = None,
    dtype: Any = None,
    put: Callable[[str, np.ndarray], jax.Array] | None = None,
) -> tuple[dict, ModelConfig]:
    """Load an HF checkpoint directory into the param pytree of its model
    family (``models.family_of``): the family's ``hf_name_map`` says which
    checkpoint tensor each leaf is. A stacked leaf is named
    ``<stack>/<i>/<leaf>`` there (one tensor per layer), or
    ``<stack>/<i>/<leaf>/<e>`` (one per layer and expert).

    ``put(param_path, host_array) -> device_array`` lets the engine place each
    stacked tensor with its target sharding (sharded device_put); default is a
    plain jnp.asarray.
    """
    cfg = cfg or config_from_hf_path(path)
    dtype = dtype or cfg.jax_dtype
    shards = _open_shards(path)
    name_map = family_of(cfg).hf_name_map(cfg)
    handles: dict[str, Any] = {}

    def read(hf_name: str) -> np.ndarray:
        file = shards[hf_name]
        if file not in handles:
            handles[file] = safe_open(file, framework="numpy")
        t = handles[file].get_tensor(hf_name)
        if t.dtype == np.dtype("uint16"):  # numpy lacks bf16; reinterpret
            t = t.view(np.uint16)
        return t

    def to_np(hf_name: str, transpose: bool) -> np.ndarray:
        t = read(hf_name)
        if t.dtype == np.uint16:
            t = jnp.asarray(t).view(jnp.bfloat16)
            t = np.asarray(t.astype(jnp.float32))
        if transpose:
            t = np.ascontiguousarray(t.T)
        return t

    put = put or (lambda p, a: jnp.asarray(a, dtype=dtype))

    # (stack, leaf) -> {layer index: {expert index or None}}
    stacked: dict[tuple[str, str], dict[int, set]] = {}
    for our_path in name_map:
        parts = our_path.split("/")
        if len(parts) >= 3:
            per = stacked.setdefault((parts[0], parts[2]), {})
            per.setdefault(int(parts[1]), set()).add(parts[3] if len(parts) == 4 else None)
    params: dict[str, Any] = {
        "embed": put("embed", to_np(*name_map["embed"])),
        "final_norm": put("final_norm", to_np(*name_map["final_norm"])),
    }
    for (stack, name), per in stacked.items():
        per_layer = []
        for i in range(len(per)):
            if None in per[i]:
                per_layer.append(to_np(*name_map[f"{stack}/{i}/{name}"]))
            elif "s0" in per[i]:  # blocks side by side along the leaf's wide axis (several shared experts in one leaf)
                blocks = [to_np(*name_map[f"{stack}/{i}/{name}/s{b}"]) for b in range(len(per[i]))]
                per_layer.append(np.concatenate(blocks, axis=_wide_axis(name)))
            else:  # one checkpoint tensor per (layer, expert): stacked [L, E, ...]
                per_layer.append(
                    np.stack([to_np(*name_map[f"{stack}/{i}/{name}/{e}"]) for e in range(len(per[i]))])
                )
        leaf_path = "/".join((stack, name))  # the path ``put`` places the stacked leaf by
        params.setdefault(stack, {})[name] = put(leaf_path, np.stack(per_layer))
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in shards:
            params["lm_head"] = put("lm_head", to_np(*name_map["lm_head"]))
        else:  # some exports tie silently
            params["lm_head"] = put("lm_head", to_np(*name_map["embed"]))
    if cfg.vision is not None and "visual.patch_embed.proj.weight" in shards:
        params["vision"] = _load_vision_params(cfg.vision, shards, to_np, put)
    return params, cfg


def _wide_axis(leaf: str) -> int:
    """The axis along which a leaf's side-by-side blocks lie, in our layout
    [in, out]: a gate or up projection's columns, a down projection's rows."""
    return 0 if leaf.endswith("down") else 1


def _load_vision_params(vcfg, shards, to_np, put) -> dict:
    """Load a Qwen2-VL ``visual.*`` tower (the reference gets this from HF's
    from_pretrained, fsdp_engine.py:289-341; here the name map lives in
    models/vision.py next to the module structure it mirrors)."""
    from areal_tpu.models.vision import hf_vision_name_map

    name_map = hf_vision_name_map(vcfg)

    def read(path: str) -> np.ndarray:
        hf_name, transpose = name_map[path]
        if hf_name == "visual.patch_embed.proj.weight":
            # Conv3d kernel [D, C, T, p, p] == a [D, patch_dim] matmul
            t = to_np(hf_name, False)
            t = t.reshape(t.shape[0], -1).T
            return np.ascontiguousarray(t)
        return to_np(hf_name, transpose)

    layers = {}
    layer_names = {p.split("/")[2] for p in name_map if p.startswith("layers/")}
    for name in layer_names:
        stacked = np.stack(
            [read(f"layers/{i}/{name}") for i in range(vcfg.num_layers)]
        )
        layers[name] = put(f"vision/layers/{name}", stacked)
    out = {"layers": layers}
    for path in name_map:
        if not path.startswith("layers/"):
            out[path] = put(f"vision/{path}", read(path))
    return out


def write_hf_config(cfg, path: str) -> None:
    """Inverse of the family's ``from_hf_dict``: write a loadable config.json
    so a saved checkpoint dir is self-contained (launcher/server subprocess
    tests; scratch-trained exports)."""
    import json

    if not isinstance(cfg, ModelConfig):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(cfg.to_hf_dict(), f, indent=2)
        return
    assert cfg.vision is None, (
        "write_hf_config cannot reconstruct a vision_config — export VLM "
        "checkpoints with base_model_path pointing at the source model dir"
    )
    base = "qwen3" if cfg.qk_norm else "qwen2"
    # MoE exports always mark qwen3_moe (qwen2_moe implies shared experts
    # this family doesn't have); the explicit qk_norm key keeps a
    # no-qk-norm MoE export round-trippable through from_hf_dict
    mt = ("qwen3_moe" if cfg.num_experts > 0 else base)
    d = {
        "model_type": mt,
        "qk_norm": cfg.qk_norm,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "attention_bias": cfg.attention_bias,
    }
    if cfg.num_experts > 0:
        d.update(
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            norm_topk_prob=cfg.norm_topk_prob,
        )
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(d, f, indent=2)


def save_params_to_hf(
    params: dict,
    cfg: ModelConfig,
    path: str,
    base_model_path: str | None = None,
) -> None:
    """Export params as an HF-layout safetensors file (+config/tokenizer files
    copied from ``base_model_path``) — the disk weight-update format
    (reference fsdp_engine.py:1139-1204)."""
    os.makedirs(path, exist_ok=True)
    name_map = family_of(cfg).hf_name_map(cfg)
    flat: dict[str, np.ndarray] = {}

    def host(x) -> np.ndarray:
        x = jax.device_get(x)
        if x.dtype == jnp.bfloat16:
            x = np.asarray(x.astype(jnp.float32), dtype=np.float32)
        return np.asarray(x)

    # ONE device_get per stacked leaf, sliced on host — per-(layer, expert)
    # device slices would multiply transfers on the disk weight-update path
    host_cache: dict[str, np.ndarray] = {}

    def leaf(*keys: str) -> np.ndarray:
        if keys not in host_cache:
            x = params
            for k in keys:
                x = x[k]
            host_cache[keys] = host(x)
        return host_cache[keys]

    for our_path, (hf_name, transpose) in name_map.items():
        parts = our_path.split("/")
        if len(parts) == 4 and parts[3].startswith("s"):  # <stack>/<l>/<name>/s<b>: block b along the wide axis
            whole = leaf(parts[0], parts[2])[int(parts[1])]
            n_blocks = sum(1 for k in name_map if k.startswith("/".join(parts[:3]) + "/s"))
            t = np.split(whole, n_blocks, axis=_wide_axis(parts[2]))[int(parts[3][1:])]
        elif len(parts) == 4:  # <stack>/<l>/<name>/<e>
            t = leaf(parts[0], parts[2])[int(parts[1]), int(parts[3])]
        elif len(parts) == 3:  # <stack>/<l>/<name>
            t = leaf(parts[0], parts[2])[int(parts[1])]
        else:
            t = leaf(parts[0])
        flat[hf_name] = np.ascontiguousarray(t.T) if transpose else t
    save_file(flat, os.path.join(path, "model.safetensors"))

    # "" (a from-scratch engine's config.path) must behave like None: an
    # export with no config.json is not loadable as an HF artifact
    if not base_model_path and not os.path.exists(
        os.path.join(path, "config.json")
    ):
        write_hf_config(cfg, path)
    src = base_model_path
    if src:
        for fname in (
            "config.json",
            "tokenizer.json",
            "tokenizer_config.json",
            "generation_config.json",
            "vocab.json",
            "merges.txt",
            "special_tokens_map.json",
        ):
            sp = os.path.join(src, fname)
            if os.path.exists(sp):
                with open(sp, "rb") as fi, open(os.path.join(path, fname), "wb") as fo:
                    fo.write(fi.read())
