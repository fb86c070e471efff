"""Plain reference of the ``deepseek_v3`` decoder as kanana-2-30b-a3b
publishes it: latent attention (no low-rank query path) in every layer, the
first ``first_k_dense_replace`` feed-forward blocks dense, the rest sparse
experts behind a sigmoid router with a selection bias, beside an always-active
shared block.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no absorption, no batching, no sort, nothing imported from
the program. Attention is computed in its FIRST form for every token: each
head's key and value are made from the token's own latent. It routes FOR
ITSELF over every expert the router scores, then applies the experts it is
given (the configuration's share) under a gate that is 0 where the token did
not choose them. One layer is cast to float32 at a time, one head and one
expert at a time inside it, so that 4k tokens x 48 layers at the published
widths fit on the chip once the engine is gone.

The model (``u`` a sublayer's normed input; no bias anywhere):
  h0 = embed[ids];  h = h + attn_l(rmsnorm(h));  h = h + ffn_l(rmsnorm(h))
  logits = rmsnorm(h, norm) @ lm_head^T            (untied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)
latent attention, H heads: q = W_q u, a head [q_nope (qk_nope_head_dim) |
  q_rope (qk_rope_head_dim)]; [c~ | k_r~] = W_kva u; c = rmsnorm(c~,
  kv_a_layernorm) over kv_lora_rank; k_r = rope(k_r~), ONE rotary key for all
  heads; q_rope = rope(q_rope); [k_nope_h | v_h] = W_kvb,h c; k_h = [k_nope_h |
  k_r]; causal softmax of q_h . k_h / sqrt(nope + rope); o_h = sum p v_h;
  y = W_o [o_1 .. o_H].
  rope (``rope_interleave``): the values are (even, odd) PAIRS; pair i at
  position t turns by t * rope_theta^(-2i / qk_rope_head_dim). (The published
  code moves the pairs to halves first and turns those: the same rotation in
  another order of lanes, and a dot product does not see the order.)
dense FFN: W_down (silu(W_gate u) * W_up u)
expert FFN (``noaux_tc``, n_group 1): s = sigmoid(W_g u) over ALL the
  router's experts, float32; chosen = top-k of s + e_score_correction_bias;
  gate_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor;
  out = sum over the chosen e HELD HERE of gate_e W_down,e (silu(W_gate,e u) *
  W_up,e u) + SwiGLU_shared(u) (gate 1, every token).

The share (the configuration's ``n_routed_experts`` held of the ``assumed``
``router_experts``, ids from ``expert_first``): what the absent experts would
have added is left out, here as in the program, and the partial sum goes on
to the next layer. ``share_of`` hands a test another rank's share.

Departures from the published model, each on purpose:
  * weights, norms and ``e_score_correction_bias`` are random
    (``kanana2_weights.py``);
  * the vocabulary is the share's slice: ids, logits and logprobs over it;
  * the sequence is padded to a fixed length so one program serves every
    sample (everything is causal: the padding cannot reach a real position);
  * where two biased scores tie exactly, the lower expert index wins
    (``jax.lax.top_k``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the pieces every decoder's reference shares (benchlib, not the program): the
# RMSNorm, the padding to one length, the log-softmax over vocabulary blocks
from benchlib.hybrid_reference import _pad, _rms, _vocab_logprobs

F32 = jnp.float32
NORM_TOPK_EPS = 1e-20


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and,
    for the share, its ``assumed``."""
    a = cfg.get("assumed", {})
    held = int(cfg["n_routed_experts"])
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        "E": held,  # experts held here
        "E_all": int(a.get("router_experts", held)),  # experts the router scores
        "e0": int(a.get("expert_first", 0)),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def share_of(cfg: dict, rank: int, ranks: int) -> dict:
    """``cfg`` (an uncut configuration: every expert held) as rank ``rank``
    of ``ranks`` holds its expert layers: the router's width unchanged, an
    equal block of the experts."""
    e_all = int(cfg["n_routed_experts"])
    per = e_all // ranks
    return {**cfg, "n_routed_experts": per, "assumed": {**cfg.get("assumed", {}), "router_experts": e_all, "expert_first": rank * per}}


def _turn_pairs(x, pos, theta: float):
    """The rotary embedding on (even, odd) pairs along the last axis of x
    [T, ..., d] at positions pos [T]."""
    d = x.shape[-1]
    ang = pos.astype(F32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)  # [T, d/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "rank", "nope", "rope", "vd", "eps", "theta"))
def _attention(x, lp, *, heads, rank, nope, rope, vd, eps, theta):
    T = x.shape[0]
    u = _rms(x, lp["input_norm"], eps)
    q = (u @ lp["wq"].astype(F32)).reshape(T, heads, nope + rope)
    kva = u @ lp["w_kva"].astype(F32)
    c = _rms(kva[:, :rank], lp["kv_norm"], eps)
    pos = jnp.arange(T)
    k_r = _turn_pairs(kva[:, rank:], pos, theta)  # [T, rope]: one key for all heads
    q_rope = _turn_pairs(q[..., nope:], pos, theta)
    w_kvb = lp["w_kvb"].astype(F32).reshape(rank, heads, nope + vd)
    causal = pos[:, None] >= pos[None, :]

    def head(args):  # one head at a time: scores are [T, T]
        qn, qr, w = args  # [T, nope], [T, rope], [rank, nope + vd]
        kv = c @ w
        s = (qn @ kv[:, :nope].T + qr @ k_r.T) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ kv[:, nope:]

    o = jax.lax.map(head, (jnp.moveaxis(q[..., :nope], 1, 0), jnp.moveaxis(q_rope, 1, 0), jnp.moveaxis(w_kvb, 1, 0)))
    return x + jnp.moveaxis(o, 0, 1).reshape(T, heads * vd) @ lp["wo"].astype(F32)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    return x + _swiglu(_rms(x, lp["post_norm"], eps), lp["w_gate"], lp["w_up"], lp["w_down"])


def route(u, w_router, bias, *, top_k: int, norm_topk: bool, scale: float):
    """u [T, D] float32 -> (gate of every expert the router scores for every
    token [T, E_all], 0 where not chosen; the chosen experts [T, top_k]; the
    margin of the choice [T]: the last chosen expert's biased score less the
    best unchosen one's)."""
    s = jax.nn.sigmoid(u @ w_router.astype(F32))
    best, order = jax.lax.top_k(s + bias.astype(F32), top_k + 1)
    chosen, margin = order[:, :top_k], best[:, top_k - 1] - best[:, top_k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        picked = picked / (picked.sum(-1, keepdims=True) + NORM_TOPK_EPS)
    picked = picked * scale
    onehot = chosen[:, :, None] == jnp.arange(s.shape[-1])[None, None, :]
    return jnp.sum(jnp.where(onehot, picked[:, :, None], 0.0), axis=1), chosen, margin


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk", "scale", "e0", "shared"))
def _expert_ffn(x, lp, *, eps, top_k, norm_topk, scale, e0, shared=True):
    """x + the held experts' part of the routed sum + the shared block
    (``shared`` False leaves it out: a test that adds shares up counts it
    once)."""
    u = _rms(x, lp["post_norm"], eps)
    gates, chosen, margin = route(u, lp["w_router"], lp["router_bias"], top_k=top_k, norm_topk=norm_topk, scale=scale)
    held = gates[:, e0 : e0 + lp["we_gate"].shape[0]]  # the gates of the experts whose weights are here

    def one(acc, ew):  # every held expert on every token; its gate is 0 where not chosen
        w1, w3, w2, g = ew
        return acc + g[:, None] * _swiglu(u, w1, w3, w2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["we_gate"], lp["we_up"], lp["we_down"], held.T))
    if shared:
        out = out + _swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return x + out, (chosen, margin)


def layer_params(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s slice of the weight tree (stacked per kind of layer:
    ``mla`` the leading dense ones, ``mla_moe`` the expert layers)."""
    n_dense = dims(cfg)["dense"]
    stack, j = ("mla", i) if i < n_dense else ("mla_moe", i - n_dense)
    return {k: v[j] for k, v in params[stack].items()}


def hidden_states(params: dict, cfg: dict, padded, routing: list | None = None, layers: int | None = None) -> jax.Array:
    """The last layer's output [T, D] (before the final norm) for tokens [T];
    ``layers`` stops after that many. With ``routing`` every expert layer
    appends (the experts it picked [T, top_k] int32, the margin of each
    token's choice [T]) as numpy."""
    d = dims(cfg)
    x = params["embed"][padded].astype(F32)
    for i in range(d["layers"] if layers is None else layers):
        lp = layer_params(params, cfg, i)
        x = _attention(
            x, lp, heads=d["heads"], rank=d["rank"], nope=d["nope"], rope=d["rope"], vd=d["vd"], eps=d["eps"], theta=d["theta"]
        )
        if i < d["dense"]:
            x = _dense_ffn(x, lp, eps=d["eps"])
        else:
            x, picked = _expert_ffn(x, lp, eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"], e0=d["e0"])
            if routing is not None:
                routing.append(tuple(np.asarray(a) for a in picked))
    return x


def routing_of(params: dict, cfg: dict, ids, pad_to: int) -> tuple[np.ndarray, np.ndarray]:
    """What the reference's own router does with every token of ``ids``:
    (the experts it picks [expert layers, len(ids), top_k], the margin of
    each pick [expert layers, len(ids)])."""
    ids, padded = _pad(ids, pad_to)
    routing: list = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, cfg, jnp.asarray(padded), routing)
    return tuple(np.stack(a)[:, : len(ids)] for a in zip(*routing))


def logits(params: dict, cfg: dict, ids) -> np.ndarray:
    """The full forward's logits [len(ids), V] float32 (small sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(np.asarray(ids, np.int32)))
        return np.asarray(_rms(x, params["final_norm"], dims(cfg)["eps"]) @ params["lm_head"].astype(F32).T)


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    ids, padded = _pad(ids, pad_to)
    n = len(ids)
    targets = np.zeros(pad_to, np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded))
        lp_all = _vocab_logprobs(
            x, params["final_norm"], params["lm_head"], jnp.asarray(targets), eps=dims(cfg)["eps"], scaling=1.0, block=16384
        )
    return np.asarray(lp_all, np.float32)[: n - 1]
