"""Plain reference of the ``xing4_0`` decoder (Xing4.0-29B-A4B): the DeepSeek-V3
block (latent attention through a low-rank query under a YaRN-scaled rotary
key; two leading dense feed-forward blocks, then sigmoid-routed experts beside
one shared expert) on a residual path of ``hc_mult`` = n STREAMS: manifold-
constrained hyper-connections (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606).

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no absorption, no batching, nothing imported from the
program. The streams are an array ``X [n, T, D]`` (stream-major: a float32
axis of 4 next to the minor one would be padded to 8 on the chip); attention
is computed in its FIRST form for every token (each head's key and value made
from the token's own latent), the full causal softmax a block of
``QUERY_BLOCK`` queries against every key, one head at a time, so that 20k
tokens fit on the chip once the engine is gone (scores are [block, T], never
[T, T]). One layer is cast to float32 at a time.

The model (no bias in any projection; ``rmsnorm(x, w) = w x / sqrt(mean(x^2) +
rms_norm_eps)``):
  X_0[j] = embed[id] for every j < n                      (A: the embedding copied)
  every sublayer F (attention, then the feed-forward block: two a layer, each
  with coefficients Phi, a, b of its own, A):
    x  = vec(X) in R^{nD};  x' = x / sqrt(mean(x^2) + rms_norm_eps)   (A: no weight)
    m  = x' Phi,  Phi in R^{nD x (2n + n^2)}
    H_pre  = sigmoid(a_pre m[0:n] + b_pre)                in R^n
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)           in R^n
    Z = clip(a_res mat(m[2n:]) + B_res, mhc_h_res_clamp_min, .._max)  in R^{n x n}
    M = exp(Z); hc_sinkhorn_iters times: M <- M / (rowsum(M) + hc_eps), then
    M <- M / (colsum(M) + hc_eps)   (A: rows first, hc_eps in both)  = H_res
    u = sum_j H_pre[j] X[j];  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(rmsnorm(u))
  h = sum_j X_L[j]  (A: the streams summed);  logits = rmsnorm(h, norm) @ lm_head^T  (untied)
latent attention, H heads: q_r = rmsnorm(W_qa v, q_a_layernorm) over
  q_lora_rank; q = W_qb q_r, a head [q_nope | q_rope]; [c~ | k_r~] = W_kva v;
  c = rmsnorm(c~, kv_a_layernorm); k_r = rope(k_r~), ONE rotary key for all
  heads; [k_nope_h | v_h] = W_kvb,h c; softmax over s <= t of q_h . [k_nope_h |
  k_r] x (nope + rope)^-1/2 x mscale^2; y = W_o [o_1 .. o_H].
  rope (``rope_interleave``, A): (even, odd) PAIRS; pair i at position t turns
  by t x inv_freq_i, YaRN's table (``yarn_table``): f_i = theta^(-2i / rope);
  lo = floor(d(beta_fast)), hi = ceil(d(beta_slow)), d(b) = rope ln(original /
  (2 pi b)) / (2 ln theta); r_i = clip((i - lo) / (hi - lo), 0, 1); inv_freq_i
  = f_i (1 - r_i) + (f_i / factor) r_i; cos and sin unscaled (mscale /
  mscale_all_dim = 1); mscale = 0.1 mscale_all_dim ln(factor) + 1 (A: the
  DeepSeek-V3 family's published form).
dense FFN: W_down (silu(W_gate v) * W_up v)
expert FFN (``noaux_tc``, n_group 1): s = sigmoid(W_r v) over ALL the router's
  experts; chosen = top-k of s + e_score_correction_bias; gate_e = s_e / (sum
  of the chosen s + 1e-20) x routed_scaling_factor; out = sum over the chosen e
  HELD HERE of gate_e SwiGLU_e(v) + SwiGLU_shared(v).

Departures from the published description, each on purpose:
  * every line marked A is an ASSUMPTION the configuration file lists with its
    reason and the other reading (``assumed`` / ``assumed_notes``): the
    published ``config.json`` names sizes, not forms;
  * the share: the configuration's ``n_routed_experts`` held of the ``assumed``
    ``router_experts``, ids from ``expert_first``; what the absent experts
    would have added is left out, here as in the program (``share_of`` hands a
    test another rank's share); the vocabulary is the share's slice;
  * no multi-token-prediction layer (``num_nextn_predict_layers`` reduced to 0);
  * weights, norms and stream coefficients are seeded (``xing4_weights.py``);
    the sequence is padded to whole blocks (everything is causal: the padding
    cannot reach a real position);
  * where two biased router scores tie exactly, the lower expert wins, as
    ``jax.lax.top_k`` orders them;
  * ``hc_coeff_dtype`` (A: float32) may be given as ``bfloat16``: the
    coefficients are then computed in that type, which is what a control of
    the output check reads (PERF.md section 4), never what a cell serves.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.hybrid_reference import _pad, _rms, _vocab_logprobs
from benchlib.kanana2_reference import _swiglu, route, share_of  # noqa: F401  (share_of: a test's other ranks, as every share's reference hands it)

F32 = jnp.float32
QUERY_BLOCK = 1024  # queries a block; a sequence is padded to whole blocks


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and,
    for the share and the coefficients' type, its ``assumed``."""
    a = cfg.get("assumed", {})
    held = int(cfg["n_routed_experts"])
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        "E": held,
        "E_all": int(a.get("router_experts", held)),
        "e0": int(a.get("expert_first", 0)),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]),
        "rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "n": int(cfg["hc_mult"]),
        "rounds": int(cfg["hc_sinkhorn_iters"]),
        "hc_eps": float(cfg["hc_eps"]),
        "clamp": (float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])),
        "coeff_dtype": str(a.get("hc_coeff_dtype", "float32")),
    }


def yarn_table(cfg: dict) -> tuple[np.ndarray, float, int, int]:
    """(inv_freq of each rotary pair [rope / 2] float32, mscale^2 the softmax
    scale is multiplied by, lo, hi) from ``rope_theta`` and the constants of
    ``rope_scaling``; without ``rope_scaling`` the plain table and 1."""
    rope, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    f = theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    sc = cfg.get("rope_scaling")
    if not sc:
        return f.astype(np.float32), 1.0, 0, 0
    factor, original = float(sc["factor"]), float(sc["original_max_position_embeddings"])

    def pair_of(turns: float) -> float:
        return rope * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(pair_of(float(sc["beta_fast"]))), 0), min(math.ceil(pair_of(float(sc["beta_slow"]))), rope - 1)
    r = np.clip((np.arange(rope // 2, dtype=np.float64) - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    mscale = 0.1 * float(sc["mscale_all_dim"]) * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (f * (1.0 - r) + (f / factor) * r).astype(np.float32), mscale * mscale, lo, hi


def _turn_pairs(x, pos, inv_freq):
    """The rotary embedding on (even, odd) pairs along the last axis of x
    [T, ..., d] at positions pos [T]: pair i turns by pos x inv_freq[i]."""
    d = x.shape[-1]
    ang = pos.astype(F32)[:, None] * inv_freq  # [T, d/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def sinkhorn(M, rounds: int, eps: float):
    """``rounds`` times: rows of M [..., n, n] over (their sum + eps), then columns."""
    for _ in range(rounds):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    return M


@functools.partial(jax.jit, static_argnames=("n", "rounds", "hc_eps", "clamp", "eps", "dtype"))
def coefficients(X, phi, alpha, bias, *, n, rounds, hc_eps, clamp, eps, dtype="float32"):
    """One sublayer's (H_pre [T, n], H_post [T, n], H_res [T, n, n]) from the
    streams X [n, T, D] float32; ``dtype`` is what they are computed in."""
    dt = jnp.dtype(dtype)
    x = jnp.moveaxis(X, 0, 1).reshape(X.shape[1], -1).astype(dt)  # vec(X) a token: stream 0's D values first
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + jnp.asarray(eps, dt))
    m = x @ phi.astype(dt)
    alpha, bias = alpha.astype(dt), bias.astype(dt)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n : 2 * n] + bias[n : 2 * n])
    z = jnp.clip(alpha[2] * m[:, 2 * n :] + bias[2 * n :], clamp[0], clamp[1]).reshape(-1, n, n)
    res = sinkhorn(jnp.exp(z), rounds, jnp.asarray(hc_eps, dt))
    return pre.astype(F32), post.astype(F32), res.astype(F32)


def _coeff_of(X, lp, tag: str, d: dict):
    return coefficients(
        X, lp[f"hc_{tag}_phi"], lp[f"hc_{tag}_alpha"], lp[f"hc_{tag}_bias"],
        n=d["n"], rounds=d["rounds"], hc_eps=d["hc_eps"], clamp=d["clamp"], eps=d["eps"], dtype=d["coeff_dtype"],
    )


@jax.jit
def _pre_mix(X, pre):
    return jnp.einsum("tj,jtd->td", pre, X)


@jax.jit
def _post_mix(X, post, res, out):
    return jnp.einsum("tij,jtd->itd", res, X) + post.T[:, :, None] * out[None, :, :]


_KEYS = ("heads", "q_rank", "rank", "nope", "rope", "vd", "eps")


@functools.partial(jax.jit, static_argnames=_KEYS)
def _keys(u, lp, inv_freq, **d):
    """What every token of the sublayer's input u [T, D] offers the queries:
    (its normed input v, the normed low-rank query q_r, the normed latent c
    [T, rank] that every head's key and value are made from, the rotary key
    [T, rope])."""
    pos = jnp.arange(u.shape[0])
    v = _rms(u, lp["input_norm"], d["eps"])
    q_r = _rms(v @ lp["w_qa"].astype(F32), lp["q_a_norm"], d["eps"])
    kva = v @ lp["w_kva"].astype(F32)
    c = _rms(kva[:, : d["rank"]], lp["kv_norm"], d["eps"])
    return q_r, c, _turn_pairs(kva[:, d["rank"] :], pos, inv_freq)


@functools.partial(jax.jit, static_argnames=_KEYS + ("B",))
def _attend_block(lo, q_r, c, k_r, lp, inv_freq, sm_gain, *, B, **d):
    """The attention's output [B, H * vd] of the B queries from ``lo`` over
    every key up to each query; a head's keys and values are made from the
    latent when its turn comes."""
    nope = d["nope"]
    pos = lo + jnp.arange(B)
    causal = pos[:, None] >= jnp.arange(c.shape[0])[None, :]
    q = (jax.lax.dynamic_slice_in_dim(q_r, lo, B) @ lp["w_qb"].astype(F32)).reshape(B, d["heads"], nope + d["rope"])
    q_rope = _turn_pairs(q[..., nope:], pos, inv_freq)
    w_kvb = lp["w_kvb"].astype(F32).reshape(d["rank"], d["heads"], nope + d["vd"])
    scale = (nope + d["rope"]) ** -0.5 * sm_gain

    def head(args):  # one head at a time: scores are [B, T]
        qn, qr, w = args
        kv_h = c @ w  # [T, nope + vd]
        s = (qn @ kv_h[:, :nope].T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ kv_h[:, nope:]

    o = jax.lax.map(head, (jnp.moveaxis(q[..., :nope], 1, 0), jnp.moveaxis(q_rope, 1, 0), jnp.moveaxis(w_kvb, 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(B, d["heads"] * d["vd"])


def _attention(u, lp, d: dict, table):
    """The layer's attention of its input u [T, D] (NOT added to anything):
    a block of queries at a time."""
    T = u.shape[0]
    kd = {k: d[k] for k in _KEYS}
    inv_freq, gain = jnp.asarray(table[0]), jnp.float32(table[1])
    q_r, c, k_r = _keys(u, lp, inv_freq, **kd)
    wo = lp["wo"].astype(F32)
    B = min(QUERY_BLOCK, T)
    return jnp.concatenate([_attend_block(jnp.int32(lo), q_r, c, k_r, lp, inv_freq, gain, B=B, **kd) @ wo for lo in range(0, T, B)])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(u, lp, *, eps):
    return _swiglu(_rms(u, lp["post_norm"], eps), lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk", "scale", "e0", "shared"))
def _expert_ffn(u, lp, *, eps, top_k, norm_topk, scale, e0, shared=True):
    """The held experts' part of the routed sum + the shared block of the
    sublayer's input u [T, D] (``shared`` False leaves that out: a test that
    adds shares up counts it once); also (the chosen experts, their margin)."""
    v = _rms(u, lp["post_norm"], eps)
    gates, chosen, margin = route(v, lp["w_router"], lp["router_bias"], top_k=top_k, norm_topk=norm_topk, scale=scale)
    held = gates[:, e0 : e0 + lp["we_gate"].shape[0]]

    def one(acc, ew):  # every held expert on every token; its gate is 0 where not chosen
        w1, w3, w2, g = ew
        return acc + g[:, None] * _swiglu(v, w1, w3, w2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (lp["we_gate"], lp["we_up"], lp["we_down"], held.T))
    if shared:
        out = out + _swiglu(v, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, (chosen, margin)


def layer_params(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s slice of the weight tree (stacked per kind of layer:
    ``mla`` the leading dense ones, ``mla_moe`` the expert layers)."""
    n_dense = dims(cfg)["dense"]
    stack, j = ("mla", i) if i < n_dense else ("mla_moe", i - n_dense)
    return {k: v[j] for k, v in params[stack].items()}


def streams_after(
    params: dict, cfg: dict, padded, routing: list | None = None, layers: int | None = None, shared: bool = True,
    rebias=None, parts: str = "all",
):
    """The streams X [n, T, D] after the last layer for tokens [T] (T whole
    blocks, or under one); ``layers`` stops after that many. With ``routing``
    every expert layer appends (the experts it picked [T, top_k], the margin
    of each token's choice [T]) as numpy. ``shared`` False leaves the shared
    block out. With ``rebias`` every expert layer routes under the bias
    ``rebias(the router's scores [T, E_all])`` returns in its own bias's place.
    ``parts`` ``routed`` (a test that adds shares up) makes the LAST layer
    walked return its feed-forward block's output [T, D] alone, before the
    post-mix."""
    d = dims(cfg)
    table = yarn_table(cfg)
    X = jnp.repeat(params["embed"][padded].astype(F32)[None], d["n"], axis=0)
    n_layers = d["layers"] if layers is None else layers
    for i in range(n_layers):
        lp = layer_params(params, cfg, i)
        pre, post, res = _coeff_of(X, lp, "attn", d)
        X = _post_mix(X, post, res, _attention(_pre_mix(X, pre), lp, d, table))
        pre, post, res = _coeff_of(X, lp, "ffn", d)
        u = _pre_mix(X, pre)
        if i < d["dense"]:
            out = _dense_ffn(u, lp, eps=d["eps"])
        else:
            if rebias is not None:
                scores = jax.nn.sigmoid(_rms(u, lp["post_norm"], d["eps"]) @ lp["w_router"].astype(F32))
                lp = {**lp, "router_bias": rebias(scores)}
            out, picked = _expert_ffn(
                u, lp, eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"], e0=d["e0"], shared=shared
            )
            if routing is not None:
                routing.append(tuple(np.asarray(a) for a in picked))
        if parts == "routed" and i == n_layers - 1:
            return out
        X = _post_mix(X, post, res, out)
    return X


def hidden_states(params: dict, cfg: dict, padded, **kw):
    """The streams' sum [T, D] after the last layer (before the final norm)."""
    return streams_after(params, cfg, padded, **kw).sum(axis=0)


def _blocks(n: int, pad_to: int) -> int:
    """The length a sequence of n tokens is computed at: whole blocks of
    4 x QUERY_BLOCK (a handful of programs for every length a cell sends), at
    most ``pad_to`` rounded up to whole query blocks."""
    step = 4 * QUERY_BLOCK
    cap = -(-pad_to // QUERY_BLOCK) * QUERY_BLOCK
    return n if n <= QUERY_BLOCK and pad_to <= QUERY_BLOCK else min(cap, -(-n // step) * step)


def routing_of(params: dict, cfg: dict, ids, pad_to: int):
    """What the reference's own router does with every token of ``ids``:
    (the experts it picks [expert layers, len(ids), top_k], the margin of
    each pick [expert layers, len(ids)])."""
    ids, padded = _pad(ids, _blocks(len(ids), pad_to))
    routing: list = []
    with jax.default_matmul_precision("highest"):
        streams_after(params, cfg, jnp.asarray(padded), routing)
    return tuple(np.stack(a)[:, : len(ids)] for a in zip(*routing))


def logits(params: dict, cfg: dict, ids, shared: bool = True) -> np.ndarray:
    """The full forward's logits [len(ids), V] float32 (small sizes: tests)."""
    ids, padded = _pad(ids, _blocks(len(ids), len(ids)))
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded), shared=shared)
        return np.asarray(_rms(x, params["final_norm"], dims(cfg)["eps"]) @ params["lm_head"].astype(F32).T)[: len(ids)]


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    ids, padded = _pad(ids, _blocks(len(ids), pad_to))
    n = len(ids)
    targets = np.zeros(len(padded), np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded))
        lp_all = _vocab_logprobs(
            x, params["final_norm"], params["lm_head"], jnp.asarray(targets), eps=dims(cfg)["eps"], scaling=1.0, block=16384
        )
    return np.asarray(lp_all, np.float32)[: n - 1]
