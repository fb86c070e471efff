"""Hybrid decoder: state-space (Mamba-2) layers beside GQA attention layers.

The ``granitemoehybrid`` family with no experts: every layer is a mixer (one
of two kinds, in the order of the published ``layer_types``) and a dense
SwiGLU MLP, each behind an RMSNorm and a residual multiplier; the attention
layers carry no rotary embedding and take their softmax scale from the
configuration. Serving only (prefill, paged decode); training through the
chunked scan is ROADMAP Reach A.4.

The module has the entry points the decode engine uses of ``models/qwen.py``
(``models.family_of`` picks one of the two from the model configuration), and
shares with it the RMSNorm, the projection (``_proj``), the embedding lookup,
the logits matmul and the scope names.

Params are stacked PER KIND: ``params["mamba"][name]`` is ``[n_mamba, ...]``,
``params["attention"][name]`` is ``[n_attention, ...]``; a run of consecutive
layers of one kind is one ``lax.scan`` over its indices.

The Mamba-2 mixer comes in two forms of one recurrence (per head, state
``S`` in R^{P x N}): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D x_t``. ``ssm_decode_step`` is the recurrence itself, one
token for every slot; ``ssm_chunked_scan`` is the chunked algorithm over a
whole prompt (quadratic inside a chunk of ``mamba_chunk_size`` tokens, the
state carried between chunks). tests/test_hybrid_model.py holds them to each
other. Both compute in float32 whatever the model's dtype: the state is what
thousands of decode steps accumulate into.

What a slot's recurrent state is, and who may write it, is in
``inference/paged_kv.py`` (STATE_LEAVES).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from areal_tpu.models import qwen
from areal_tpu.models.qwen import _embed_lookup, _proj, _rms_norm

MODEL_TYPES = ("granitemoehybrid",)
KINDS = ("mamba", "attention")
# scopes this family adds to qwen.SCOPES (docs/observability.md)
SCOPES = ("ssm_proj", "ssm_conv", "ssm_state", "state_write")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int | None = None  # default hidden_size // num_heads
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None  # softmax scale; None: 1/sqrt(hd)
    logits_scaling: float = 1.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    dtype: str = "bfloat16"
    # the recurrent state's own types: the SSM state accumulates over the
    # whole generation; the conv window holds activations as they are
    ssm_state_dtype: str = "float32"
    conv_state_dtype: str | None = None  # default: dtype
    # K and V pages hold each head zero-padded to a multiple of this many
    # lanes, so that the Pallas paged kernels (128-lane pages) serve a head
    # of 64 exactly; 1 = as published
    kv_lane_pad: int = 128
    # what the serving stack asks of any model configuration
    vision: Any = None
    image_token_id: int = -1

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

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
    def sm_scale(self) -> float:
        if self.attention_multiplier is None:
            return self.head_dim_**-0.5
        return float(self.attention_multiplier)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    # -- what the serving cache holds for this family (paged_kv.py) --------
    @property
    def num_kv_layers(self) -> int:
        return self.count("attention")

    @property
    def kv_head_dim(self) -> int:
        pad = max(1, self.kv_lane_pad)
        return -(-self.head_dim_ // pad) * pad

    @property
    def has_recurrent_state(self) -> bool:
        return self.count("mamba") > 0

    def state_shapes(self, slots: int) -> dict[str, tuple[tuple[int, ...], Any]]:
        """{leaf: (shape, dtype)} of the slot-indexed recurrent state. The
        conv window is stored token-major and flat, ``(d_conv - 1) *
        conv_dim`` wide: with the 3 tokens as the minor dimension the TPU
        would pad every channel's 3 values to a 128-lane row."""
        n = self.count("mamba")
        if not n:
            return {}
        return {
            "ssm": (
                (n, slots, self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
                jnp.dtype(self.ssm_state_dtype),
            ),
            "conv": (
                (n, slots, (self.mamba_d_conv - 1) * self.conv_dim),
                jnp.dtype(self.conv_state_dtype or self.dtype),
            ),
        }

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "HybridConfig":
        """From a published ``granitemoehybrid`` ``config.json``. Raises on
        what this module does not implement rather than serving something
        else under the model's name."""
        mt = d.get("model_type")
        if mt not in MODEL_TYPES:
            raise ValueError(f"model_type {mt!r} is not of the hybrid family {MODEL_TYPES}")
        if d.get("num_local_experts", 0):
            raise ValueError("granitemoehybrid with routed experts is not implemented (dense shared MLP only)")
        if d.get("position_embedding_type", "nope") != "nope":
            raise ValueError("granitemoehybrid with rotary attention layers is not implemented (NoPE only)")
        if d.get("attention_bias") or d.get("mamba_proj_bias"):
            raise ValueError("projection biases are not implemented for the hybrid family")
        if not d.get("mamba_conv_bias", True):
            raise ValueError("a conv without bias is not implemented for the hybrid family")
        if d.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {d['hidden_act']!r} is not implemented")
        kinds = tuple(d["layer_types"])
        if set(kinds) - set(KINDS) or len(kinds) != d["num_hidden_layers"]:
            raise ValueError(f"layer_types {sorted(set(kinds))} x {len(kinds)} for {d['num_hidden_layers']} layers")
        n_heads = d["mamba_n_heads"]
        if n_heads * d["mamba_d_head"] != d.get("mamba_expand", 2) * d["hidden_size"]:
            raise ValueError("mamba_n_heads * mamba_d_head must equal mamba_expand * hidden_size")
        extra = {
            k: d[k]
            for k in ("dtype", "ssm_state_dtype", "conv_state_dtype", "kv_lane_pad", "head_dim")
            if k in d
        }
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("shared_intermediate_size", d["intermediate_size"]),
            layer_types=kinds,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=d.get("tie_word_embeddings", True),
            embedding_multiplier=d.get("embedding_multiplier", 1.0),
            residual_multiplier=d.get("residual_multiplier", 1.0),
            attention_multiplier=d.get("attention_multiplier"),
            logits_scaling=d.get("logits_scaling", 1.0),
            mamba_n_heads=n_heads,
            mamba_d_head=d["mamba_d_head"],
            mamba_d_state=d["mamba_d_state"],
            mamba_n_groups=d.get("mamba_n_groups", 1),
            mamba_d_conv=d.get("mamba_d_conv", 4),
            mamba_chunk_size=d.get("mamba_chunk_size", 256),
            **extra,
        )

    @classmethod
    def from_hf_path(cls, path: str) -> "HybridConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    def to_hf_dict(self) -> dict[str, Any]:
        """Inverse of ``from_hf_dict`` (a saved checkpoint's config.json)."""
        return {
            "model_type": MODEL_TYPES[0],
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "shared_intermediate_size": self.intermediate_size,
            "num_hidden_layers": self.num_layers,
            "layer_types": list(self.layer_types),
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "rms_norm_eps": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "embedding_multiplier": self.embedding_multiplier,
            "residual_multiplier": self.residual_multiplier,
            "attention_multiplier": self.sm_scale,
            "logits_scaling": self.logits_scaling,
            "mamba_n_heads": self.mamba_n_heads,
            "mamba_d_head": self.mamba_d_head,
            "mamba_d_state": self.mamba_d_state,
            "mamba_n_groups": self.mamba_n_groups,
            "mamba_d_conv": self.mamba_d_conv,
            "mamba_chunk_size": self.mamba_chunk_size,
            "mamba_expand": self.d_inner // self.hidden_size,
            "mamba_conv_bias": True,
            "mamba_proj_bias": False,
            "attention_bias": False,
            "position_embedding_type": "nope",
            "num_local_experts": 0,
            "hidden_act": "silu",
        }


def serving_config(cfg: HybridConfig, dtype: str) -> HybridConfig:
    """``cfg`` as a decode engine serves it."""
    return dataclasses.replace(cfg, dtype=dtype)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: HybridConfig) -> dict[str, dict[str, tuple[int, ...]]]:
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, C = cfg.mamba_n_heads, cfg.conv_dim
    shared = {
        "input_norm": (D,),
        "post_norm": (D,),
        "w_gate_up": (D, 2 * F),  # [gate | up], the checkpoint's fused input_linear
        "w_down": (F, D),
    }
    return {
        "mamba": {
            **shared,
            "in_proj": (D, 2 * cfg.d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state + H),
            # the checkpoint's depthwise [C, 1, K] weight, reversed: tap k of
            # channel c is conv_w[k, 0, c]
            "conv_w": (cfg.mamba_d_conv, 1, C),
            "conv_b": (C,),
            "dt_bias": (H,),
            "A_log": (H,),
            "D": (H,),
            "ssm_norm": (cfg.d_inner,),
            "out_proj": (cfg.d_inner, D),
        },
        "attention": {
            **shared,
            "wq": (D, cfg.q_dim),
            "wk": (D, cfg.kv_dim),
            "wv": (D, cfg.kv_dim),
            "wo": (cfg.q_dim, D),
        },
    }


def init_params(rng: jax.Array, cfg: HybridConfig, dtype=None) -> dict:
    """Random init, stacked per kind. ``A``, ``dt`` and ``D`` as the
    published Mamba-2 initialisation draws them (A uniform in 1-16, dt
    log-uniform in 0.001-0.1 through the inverse softplus, D = 1)."""
    dtype = dtype or cfg.jax_dtype
    keys = iter(jax.random.split(rng, 64))

    def dense(shape):
        return (0.02 * jax.random.truncated_normal(next(keys), -2, 2, shape, jnp.float32)).astype(dtype)

    params: dict[str, Any] = {
        "embed": dense((cfg.vocab_size, cfg.hidden_size)),
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense((cfg.vocab_size, cfg.hidden_size))
    for kind, shapes in _layer_shapes(cfg).items():
        n = cfg.count(kind)
        if not n:
            continue
        stack = {}
        for name, shape in shapes.items():
            full = (n, *shape)
            if name.endswith("norm") or name == "D":
                stack[name] = jnp.ones(full, dtype)
            elif name == "conv_b":
                stack[name] = jnp.zeros(full, dtype)
            elif name == "A_log":
                stack[name] = jnp.log(jax.random.uniform(next(keys), full, jnp.float32, 1.0, 16.0)).astype(dtype)
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(next(keys), full, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                stack[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
            else:
                stack[name] = dense(full)
        params[kind] = stack
    return params


def param_partition_specs(cfg: HybridConfig, fsdp_axis: str | None = "fsdp") -> dict:
    """Every leaf replicated: this family serves on one chip per replica. A
    mixer and a recurrent state sharded over the ``model`` axis (heads) is
    ROADMAP Reach A.7."""
    del fsdp_axis
    specs: dict[str, Any] = {"embed": P(), "final_norm": P()}
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P()
    for kind, shapes in _layer_shapes(cfg).items():
        if cfg.count(kind):
            specs[kind] = {name: P() for name in shapes}
    return specs


_HF_LAYER_MAP = {
    "input_norm": ("input_layernorm.weight", False),
    "post_norm": ("post_attention_layernorm.weight", False),
    "w_gate_up": ("shared_mlp.input_linear.weight", True),
    "w_down": ("shared_mlp.output_linear.weight", True),
    "in_proj": ("mamba.in_proj.weight", True),
    "conv_w": ("mamba.conv1d.weight", True),
    "conv_b": ("mamba.conv1d.bias", False),
    "dt_bias": ("mamba.dt_bias", False),
    "A_log": ("mamba.A_log", False),
    "D": ("mamba.D", False),
    "ssm_norm": ("mamba.norm.weight", False),
    "out_proj": ("mamba.out_proj.weight", True),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
}


def hf_name_map(cfg: HybridConfig) -> dict[str, tuple[str, bool]]:
    """Our param path -> (``granitemoehybrid`` checkpoint name, transpose).
    A stacked leaf maps as ``<kind>/<index within the kind>/<name>``; the
    checkpoint numbers layers in the order of ``layer_types``."""
    out: dict[str, tuple[str, bool]] = {
        "embed": ("model.embed_tokens.weight", False),
        "final_norm": ("model.norm.weight", False),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ("lm_head.weight", False)
    seen = dict.fromkeys(KINDS, 0)
    shapes = _layer_shapes(cfg)
    for i, kind in enumerate(cfg.layer_types):
        for name in shapes[kind]:
            suffix, transpose = _HF_LAYER_MAP[name]
            out[f"{kind}/{seen[kind]}/{name}"] = (f"model.layers.{i}.{suffix}", transpose)
        seen[kind] += 1
    return out


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------


def _split_xbc(cfg: HybridConfig, xbc: jax.Array):
    """[..., conv_dim] -> x [..., H, P], B and C [..., G, N], in float32."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    x, b, c = jnp.split(xbc.astype(jnp.float32), [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    lead = xbc.shape[:-1]
    x = x.reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    b = b.reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    c = c.reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state)
    return x, b, c


def _per_head(cfg: HybridConfig, bc: jax.Array, axis: int) -> jax.Array:
    """Group-wise B or C [..., G, N] -> per head [..., H, N] along ``axis``."""
    return jnp.repeat(bc, cfg.mamba_n_heads // cfg.mamba_n_groups, axis=axis)


def _dt_a(layer: dict, dt_raw: jax.Array):
    """Raw dt [..., H] -> (dt after softplus, A per head), float32. Mamba-2's
    ``time_step_limit`` is (0, inf) in this family: nothing is clamped."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(layer["A_log"].astype(jnp.float32))


def ssm_decode_step(cfg: HybridConfig, layer: dict, ssm, xbc, dt_raw, active):
    """The recurrence, one token for each of S slots.

    ssm [S, H, P, N] (its own dtype, computed in float32), xbc [S, conv_dim]
    after the conv, dt_raw [S, H]. Returns (new state, y [S, d_inner] f32).
    A slot that is not ``active`` keeps its state bit for bit."""
    x, b, c = _split_xbc(cfg, xbc)
    b, c = _per_head(cfg, b, 1), _per_head(cfg, c, 1)  # [S, H, N]
    dt, a = _dt_a(layer, dt_raw)
    s32 = ssm.astype(jnp.float32)
    new = s32 * jnp.exp(dt * a)[..., None, None] + (dt[..., None] * x)[..., None] * b[:, :, None, :]
    y = jnp.sum(new * c[:, :, None, :], axis=-1) + layer["D"].astype(jnp.float32)[None, :, None] * x
    new = jnp.where(active[:, None, None, None], new.astype(ssm.dtype), ssm)
    return new, y.reshape(y.shape[0], cfg.d_inner)


def ssm_chunked_scan(cfg: HybridConfig, layer: dict, xbc, dt_raw, n_state, state_dtype=jnp.float32):
    """The chunked algorithm for the same recurrence over whole prompts.

    xbc [A, L, conv_dim] after the conv, dt_raw [A, L, H], n_state [A]: only
    the first ``n_state`` tokens of a row enter its state (``dt`` is 0 from
    there on, so the state neither decays nor takes input; ``y`` at those
    positions is then not the model's and must not be used). Starts from the
    zero state. Returns (state after n_state tokens [A, H, P, N], y
    [A, L, d_inner] float32)."""
    A, L, _ = xbc.shape
    Q = min(cfg.mamba_chunk_size, L)
    pad = (-L) % Q
    H, Pd, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    x, b, c = _split_xbc(cfg, xbc)
    dt, a = _dt_a(layer, dt_raw)
    dt = jnp.where(jnp.arange(L)[None, :, None] < n_state[:, None, None], dt, 0.0)
    if pad:
        x, b, c, dt = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, b, c, dt))
    nc = (L + pad) // Q
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    hi = jax.lax.Precision.HIGHEST
    d_skip = layer["D"].astype(jnp.float32)

    def chunk(i, carry):
        s_in, y_all = carry
        x_c, dt_c, b_c, c_c = (jax.lax.dynamic_slice_in_dim(t, i * Q, Q, axis=1) for t in (x, dt, b, c))
        # [A,Q,H,P] [A,Q,H] [A,Q,G,N] [A,Q,G,N]
        b_h, c_h = _per_head(cfg, b_c, 2), _per_head(cfg, c_c, 2)  # [A,Q,H,N]
        a_cum = jnp.cumsum(dt_c * a, axis=1)  # [A,Q,H], <= 0 and falling
        seg = a_cum[:, :, None, :] - a_cum[:, None, :, :]  # [A, t, s, H]
        decay = jnp.exp(jnp.where(tril[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("athn,ashn->atsh", c_h, b_h, precision=hi)
        w = cb * decay * dt_c[:, None, :, :]
        y = jnp.einsum("atsh,ashp->athp", w, x_c, precision=hi)
        y = y + jnp.einsum("athn,ahpn->athp", c_h, s_in, precision=hi) * jnp.exp(a_cum)[..., None]
        y = y + d_skip[None, None, :, None] * x_c
        to_end = jnp.exp(a_cum[:, -1:, :] - a_cum) * dt_c  # [A,Q,H]
        s_out = s_in * jnp.exp(a_cum[:, -1, :])[..., None, None] + jnp.einsum(
            "ashp,ashn->ahpn", to_end[..., None] * x_c, b_h, precision=hi
        )
        return s_out, jax.lax.dynamic_update_slice_in_dim(y_all, y, i * Q, axis=1)

    carry = (jnp.zeros((A, H, Pd, N), jnp.float32), jnp.zeros((A, L + pad, H, Pd), jnp.float32))
    s_fin, y = jax.lax.fori_loop(0, nc, chunk, carry)
    y = y.reshape(A, L + pad, cfg.d_inner)[:, :L]
    return s_fin.astype(state_dtype), y


def _conv_taps(layer: dict):
    return layer["conv_w"][:, 0, :].astype(jnp.float32), layer["conv_b"].astype(jnp.float32)


def _gated_out(cfg: HybridConfig, layer: dict, y, z, dtype):
    """rmsnorm(y * silu(z)) per group of channels, then the out projection."""
    g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    lead = g.shape[:-1]
    g = g.reshape(*lead, cfg.mamba_n_groups, -1)
    w = layer["ssm_norm"].reshape(cfg.mamba_n_groups, -1)
    return _rms_norm(g, w, cfg.rms_norm_eps).reshape(*lead, cfg.d_inner)


def _mamba_in(cfg: HybridConfig, layer: dict, h):
    zxbcdt = _proj(cfg, layer, "in_proj", h)
    return jnp.split(zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)


def mamba_decode(cfg: HybridConfig, layer: dict, h, state: dict, j, active, live=None):
    """Mixer for one token a slot. h [S, D] (normed); ``state`` holds every
    Mamba layer's slot state, ``ssm`` [n, S, H, P, N] and ``conv``
    [n, S, (K-1) * conv_dim] (the raw conv inputs of the last K-1 tokens,
    oldest first), of which this is layer ``j``. Returns (out [S, D], the
    state with layer j advanced); rows that are not ``active`` keep theirs.

    ``live`` = ``paged_attention_q8.live_order(active)`` runs the recurrence
    in the Pallas kernel, which reads and writes the live slots' SSM state
    only and in place; without it ``ssm_decode_step`` passes over all slots
    under a mask (off a TPU, and the form the tests hold the kernel to)."""
    S = h.shape[0]
    K = cfg.mamba_d_conv
    conv = jax.lax.dynamic_index_in_dim(state["conv"], j, 0, keepdims=False)
    with jax.named_scope("ssm_proj"):
        z, raw, dt_raw = _mamba_in(cfg, layer, h)
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([conv.reshape(S, K - 1, cfg.conv_dim), raw[:, None, :].astype(conv.dtype)], axis=1)
        w, bias = _conv_taps(layer)
        xbc = jax.nn.silu(jnp.sum(window.astype(jnp.float32) * w[None], axis=1) + bias)
        new_conv = jnp.where(active[:, None], window[:, 1:].reshape(S, -1), conv)
    with jax.named_scope("ssm_state"):
        if live is None:
            ssm = jax.lax.dynamic_index_in_dim(state["ssm"], j, 0, keepdims=False)
            ssm, y = ssm_decode_step(cfg, layer, ssm, xbc, dt_raw, active)
        else:
            from areal_tpu.ops.ssm_state_update import ssm_state_update_stacked

            x, b, c = _split_xbc(cfg, xbc)
            dt, a = _dt_a(layer, dt_raw)
            ssm_all, y = ssm_state_update_stacked(state["ssm"], j, x, b, c, dt, a, *live)
            y = (y + layer["D"].astype(jnp.float32)[None, :, None] * x).reshape(S, cfg.d_inner)
        g = _gated_out(cfg, layer, y, z, h.dtype)
    with jax.named_scope("state_write"):
        if live is None:
            ssm_all = jax.lax.dynamic_update_index_in_dim(state["ssm"], ssm, j, 0)
        state = {"ssm": ssm_all, "conv": jax.lax.dynamic_update_index_in_dim(state["conv"], new_conv, j, 0)}
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), state


def mamba_prefill(cfg: HybridConfig, layer: dict, h, n_state, state_dtypes):
    """Mixer over whole prompts. h [A, L, D] (normed), n_state [A]. Returns
    (out [A, L, D], ssm state after n_state tokens, conv window of the last
    K-1 of those tokens; positions before the prompt count as zeros)."""
    A, L, _ = h.shape
    K = cfg.mamba_d_conv
    with jax.named_scope("ssm_proj"):
        z, raw, dt_raw = _mamba_in(cfg, layer, h)
    with jax.named_scope("ssm_conv"):
        w, bias = _conv_taps(layer)
        padded = jnp.pad(raw, ((0, 0), (K - 1, 0), (0, 0)))  # position t at row t + K - 1
        acc = bias
        for k in range(K):
            acc = acc + padded[:, k : k + L].astype(jnp.float32) * w[k]
        xbc = jax.nn.silu(acc)
        # raw inputs of tokens n_state-K+1 .. n_state-1 = padded rows n_state .. n_state+K-2,
        # picked by a one-hot product (exact: one term a sum) and not by a gather
        rows = n_state[:, None] + jnp.arange(K - 1)[None, :]
        pick = (rows[:, :, None] == jnp.arange(L + K - 1)[None, None, :]).astype(raw.dtype)
        conv = jnp.einsum("akt,atc->akc", pick, padded, preferred_element_type=jnp.float32).reshape(A, -1)
    with jax.named_scope("ssm_state"):
        ssm, y = ssm_chunked_scan(cfg, layer, xbc, dt_raw, n_state, state_dtypes[0])
        g = _gated_out(cfg, layer, y, z, h.dtype)
    with jax.named_scope("ssm_proj"):
        return _proj(cfg, layer, "out_proj", g), ssm, conv.astype(state_dtypes[1])


# ---------------------------------------------------------------------------
# the layer stack
# ---------------------------------------------------------------------------


def _mlp(cfg: HybridConfig, layer: dict, x):
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        g, u = jnp.split(_proj(cfg, layer, "w_gate_up", h), 2, axis=-1)
        return x + cfg.residual_multiplier * _proj(cfg, layer, "w_down", jax.nn.silu(g) * u)


def _runs(layer_types) -> list[tuple[str, int, int]]:
    """Runs of consecutive layers of one kind: (kind, first index within the
    kind, count), in model order."""
    out: list[tuple[str, int, int]] = []
    seen = dict.fromkeys(KINDS, 0)
    for kind in layer_types:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], 1))
        seen[kind] += 1
    return out


def _scan_layers(cfg: HybridConfig, params: dict, carry, step):
    """Run ``step(kind, carry, layer, j) -> carry`` over the layers in the
    order of ``layer_types``; ``j`` is the layer's index within its kind
    (traced) and ``layer`` its slice of the kind's stack. One ``lax.scan``
    per run of one kind."""
    for kind, lo, n in _runs(cfg.layer_types):
        stack = params[kind]

        def body(c, j, kind=kind, stack=stack):
            layer = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False), stack)
            return step(kind, c, layer, j), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(lo, lo + n, dtype=jnp.int32))
    return carry


def _embed(params: dict, cfg: HybridConfig, ids):
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], ids, cfg.jax_dtype, batch_sharded=False)
        return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)


def _qkv(cfg: HybridConfig, layer: dict, h):
    lead = h.shape[:-1]
    q = _proj(cfg, layer, "wq", h).reshape(*lead, cfg.num_heads, cfg.head_dim_)
    k = _proj(cfg, layer, "wk", h).reshape(*lead, cfg.num_kv_heads, cfg.head_dim_)
    v = _proj(cfg, layer, "wv", h).reshape(*lead, cfg.num_kv_heads, cfg.head_dim_)
    return q, k, v


def _lane_pad(cfg: HybridConfig, t):
    pad = cfg.kv_head_dim - cfg.head_dim_
    return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, pad),)) if pad else t


def compute_logits(params: dict, cfg: HybridConfig, hidden: jax.Array) -> jax.Array:
    return qwen.compute_logits(params, cfg, hidden) / cfg.logits_scaling


def forward_prefill(
    params: dict,
    cfg: HybridConfig,
    input_ids: jax.Array,  # [A, L]
    seg: jax.Array,  # [A, L] 1=valid 0=pad
    n_state: jax.Array | None = None,  # [A] tokens that enter the state; default all valid
    sink: tuple | None = None,
):
    """Batched prompt pass. Returns (hidden [A, L, D], ks, vs
    [n_attention, A, L, KH, kv_head_dim], state) where ``state`` is the
    recurrent state after each row's first ``n_state`` tokens, stacked per
    Mamba layer ({"ssm": [n, A, H, P, N], "conv": [n, A, ...]}).

    ``sink = (arrays, write)`` replaces the stacked state: ``arrays`` is
    carried through the layers and ``write(arrays, j, ssm, conv)`` stores
    Mamba layer j's state into it (the engine writes straight into its
    cache's slot rows, so no second copy of A states exists)."""
    A, L = input_ids.shape
    if n_state is None:
        n_state = jnp.sum(seg, axis=-1)
    n_state = n_state.astype(jnp.int32)
    shapes = cfg.state_shapes(A)
    dtypes = tuple(shapes[k][1] for k in ("ssm", "conv")) if shapes else (jnp.float32, cfg.jax_dtype)
    if sink is None:
        arrays = {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}

        def write(arr, j, ssm, conv):
            return {"ssm": arr["ssm"].at[j].set(ssm), "conv": arr["conv"].at[j].set(conv)}
    else:
        arrays, write = sink
    n_kv = cfg.num_kv_layers
    kv_shape = (n_kv, A, L, cfg.num_kv_heads, cfg.kv_head_dim)
    mask = qwen._attention_mask(seg)  # [A, 1, L, L]
    rm = cfg.residual_multiplier

    def attend(args):  # one row at a time: [H, L, L] logits, not [A, H, L, L]
        q, k, v, m = args
        G = cfg.num_heads // cfg.num_kv_heads
        qg = q.reshape(L, cfg.num_kv_heads, G, cfg.head_dim_)
        logits = jnp.einsum("tkgd,skd->kgts", qg, k).astype(jnp.float32) * cfg.sm_scale
        logits = jnp.where(m[0][None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(L, cfg.q_dim)

    def step(kind, carry, layer, j):
        x, ks, vs, arr = carry
        if kind == "mamba":
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            out, ssm, conv = mamba_prefill(cfg, layer, h, n_state, dtypes)
            with jax.named_scope("state_write"):
                arr = write(arr, j, ssm, conv)
        else:
            with jax.named_scope("attn_proj"):
                h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
                q, k, v = _qkv(cfg, layer, h)
                ks = ks.at[j].set(_lane_pad(cfg, k))
                vs = vs.at[j].set(_lane_pad(cfg, v))
            with jax.named_scope("attn"):
                attn = jax.lax.map(attend, (q, k, v, mask))
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        x = _mlp(cfg, layer, x + rm * out)
        return x, ks, vs, arr

    x = _embed(params, cfg, input_ids)
    carry = (x, jnp.zeros(kv_shape, cfg.jax_dtype), jnp.zeros(kv_shape, cfg.jax_dtype), arrays)
    x, ks, vs, arrays = _scan_layers(cfg, params, carry, step)
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return hidden, ks, vs, arrays


def prefill_into_cache(
    params: dict,
    cfg: HybridConfig,
    cache: dict,
    ids: jax.Array,  # [A, bucket]
    plens: jax.Array,  # [A]
    flat_pages: jax.Array,  # [A * bucket/psz]
    slots: jax.Array,  # [A] target slot per row; past the last slot for a padding row
    *,
    page_size: int,
    image_embeds: jax.Array | None = None,
) -> dict:
    """What the engine's prefill program does for this family: the K and V
    of every prompt token into the rows' pages (a row written twice is
    harmless), and into each row's slot the recurrent state after the tokens
    BEFORE the prompt's last one. Decode feeds that last token again, at its
    own position, and a state cannot take a token twice: so the last token,
    everything after it and the padding up to the bucket are masked out of
    the state here, not left to be overwritten."""
    from areal_tpu.inference import paged_kv

    assert image_embeds is None, "the hybrid family has no vision tower"
    bucket = ids.shape[1]
    seg = (jnp.arange(bucket, dtype=jnp.int32)[None] < plens[:, None]).astype(jnp.int32)

    n_slots = cache["ssm"].shape[1]

    def write(arr, j, ssm, conv):
        # one dynamic-update-slice a row, a padding row rewriting what its
        # (clamped) slot holds. Not a scatter: on the v5e a prefill of 4 rows
        # of 1024 with `.at[j, slots].set(mode="drop")` here never ended once
        # other prefill programs had run in the process (PERF.md, PR 26)
        arr = dict(arr)
        for i in range(ids.shape[0]):
            at = jnp.minimum(slots[i], n_slots - 1)
            for name, new in (("ssm", ssm), ("conv", conv)):
                start = (j, at) + (0,) * (new.ndim - 1)
                old = jax.lax.dynamic_slice(arr[name], start, (1, 1) + new.shape[1:])
                row = jnp.where(slots[i] < n_slots, new[i][None, None].astype(old.dtype), old)
                arr[name] = jax.lax.dynamic_update_slice(arr[name], row, start)
        return arr

    state = {k: cache[k] for k in paged_kv.STATE_LEAVES}
    _, ks, vs, state = forward_prefill(params, cfg, ids, seg, n_state=plens - 1, sink=(state, write))
    with jax.named_scope("kv_write"):
        cache = paged_kv.scatter_prefill(
            {k: v for k, v in cache.items() if k not in state}, ks, vs, flat_pages, page_size
        )
    return {**cache, **state}


def _refuse(what: str):
    def refuse(*_a, **_k):
        raise NotImplementedError(
            f"{what} needs a recurrent state cut back to a token boundary, which does not "
            "exist for state-space layers (ROADMAP Reach A.7: state snapshots at page boundaries)"
        )

    return refuse


forward_prefill_paged = _refuse("suffix prefill over a cached prefix")
forward_verify_paged = _refuse("speculative verification")


def quantize_params_int8(params: dict) -> dict:
    raise NotImplementedError("int8 weight quantization is not implemented for the hybrid family's mixer")


def forward_decode_paged(
    params: dict,
    cfg: HybridConfig,
    ids: jax.Array,  # [S] current tokens
    positions: jax.Array,  # [S] positions of these tokens
    cache: dict,  # k/v pages of the attention layers + the slot state
    page_table: jax.Array,  # [S, wp]
    *,
    page_size: int,
    active: jax.Array,  # [S] bool: slots whose token is really consumed
    use_kernel: bool = True,
) -> tuple[jax.Array, dict]:
    """One incremental step for all S slots. The attention layers write the
    token's K and V into its page row and read the slot's pages as
    ``qwen.forward_decode_paged`` does (the Pallas kernel over lane-padded
    heads, or the gather path); the Mamba layers advance the recurrent state
    of the ``active`` slots only: an ended, parked or held slot's state is
    what it was, bit for bit. ``use_kernel`` also puts the recurrence on its
    Pallas kernel (ops/ssm_state_update.py), which does not even read the
    state of a slot that is not live."""
    from areal_tpu.inference import paged_kv

    S = ids.shape[0]
    H = cfg.num_heads
    lengths = (positions + 1).astype(jnp.int32)
    slot = jnp.arange(S)
    write_page = page_table[slot, positions // page_size]
    write_off = positions % page_size
    kv_quant = "k_scale" in cache
    if use_kernel:
        from areal_tpu.ops.paged_attention_q8 import decode_schedule, live_order, paged_attention_stacked

        attn_lengths = jnp.where(page_table[:, 0] == 0, 0, lengths)  # see qwen.forward_decode_paged
        ppcb = paged_kv.choose_ppcb(page_table.shape[1])
        schedule = decode_schedule(attn_lengths, page_table.shape[1], page_size, ppcb)
        live = live_order(active)  # the state kernel's work list, made once a step
        with jax.named_scope("kv_write"):
            kv_live = live_order(page_table[:, 0] != 0)  # the KV writer's: qwen.forward_decode_paged
    else:
        live = kv_live = None
    rm = cfg.residual_multiplier

    def step(kind, carry, layer, j):
        x, c = carry
        c = dict(c)
        if kind == "mamba":
            h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            out, state = mamba_decode(cfg, layer, h, {k: c[k] for k in paged_kv.STATE_LEAVES}, j, active, live)
            c.update(state)
        else:
            with jax.named_scope("attn_proj"):
                h = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
                q, k, v = (_lane_pad(cfg, t) for t in _qkv(cfg, layer, h))
            with jax.named_scope("kv_write"):
                c = paged_kv.write_decode_rows(c, j, k, v, write_page, write_off, kv_live)
            with jax.named_scope("attn"):
                if use_kernel:
                    attn = paged_attention_stacked(
                        q, c["k"], c["v"], j, attn_lengths, page_table,
                        pages_per_compute_block=ppcb, schedule=schedule,
                        k_scales=c.get("k_scale"), v_scales=c.get("v_scale"),
                        sm_scale=cfg.sm_scale,
                    )
                else:
                    sl = {
                        name: jax.lax.dynamic_index_in_dim(c[name], j, 0, keepdims=False)
                        for name in c
                        if name not in paged_kv.STATE_LEAVES
                    }
                    scales = dict(k_scales=sl["k_scale"], v_scales=sl["v_scale"]) if kv_quant else {}
                    attn = paged_kv.paged_attention_xla(
                        q, sl["k"], sl["v"], lengths, page_table, sm_scale=cfg.sm_scale, **scales
                    )
                attn = attn[..., : cfg.head_dim_].reshape(S, H * cfg.head_dim_).astype(x.dtype)
            with jax.named_scope("attn_proj"):
                out = _proj(cfg, layer, "wo", attn)
        x = _mlp(cfg, layer, x + rm * out)
        return x, c

    x = _embed(params, cfg, ids)
    x, out_cache = _scan_layers(cfg, params, (x, dict(cache)), step)
    with jax.named_scope("lm_head"):
        hidden = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return hidden, out_cache
