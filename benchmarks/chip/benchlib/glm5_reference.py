"""Plain reference of the ``glm_moe_dsa`` decoder as GLM-5 publishes it: latent
attention with a low-rank query in every layer, DeepSeek-V3.2's learned index
("lightning indexer") in every layer, which picks the ``index_topk`` cached
tokens a query attends to, the first ``first_k_dense_replace`` feed-forward
blocks dense, the rest sparse experts behind a sigmoid router with a
selection bias, beside one always-active shared expert.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no absorption, no batching, no bit search, nothing
imported from the program. Attention is computed in its FIRST form for every
token (each head's key and value made from the token's own latent), the
selection as a MASK over ``s <= t``. One layer is cast to float32 at a time;
inside it a block of ``QUERY_BLOCK`` queries meets every key, one head at a
time, so that 20k tokens fit on the chip once the engine is gone (scores are
[block, T], never [T, T]).

The model (``u`` a sublayer's normed input; no bias in any projection):
  h0 = embed[ids];  h = h + attn_l(rmsnorm(h));  h = h + ffn_l(rmsnorm(h))
  logits = rmsnorm(h, norm) @ lm_head^T            (untied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)
latent attention, H heads: q_r = rmsnorm(W_qa u, q_a_layernorm) over
  q_lora_rank; q = W_qb q_r, a head [q_nope (qk_nope_head_dim) | q_rope
  (qk_rope_head_dim)]; [c~ | k_r~] = W_kva u; c = rmsnorm(c~, kv_a_layernorm)
  over kv_lora_rank; k_r = rope(k_r~), ONE rotary key for all heads; q_rope =
  rope(q_rope); [k_nope_h | v_h] = W_kvb,h c; k_h = [k_nope_h | k_r];
  softmax over s IN S_t of q_h . k_h / sqrt(nope + rope); o_h = sum p v_h;
  y = W_o [o_1 .. o_H].
  rope (``rope_interleave``): the values are (even, odd) PAIRS; pair i at
  position t turns by t * rope_theta^(-2i / qk_rope_head_dim).
the index: q^I = W^I_qb q_r, ``index_n_heads`` heads of ``index_head_dim``
  (the SAME normed low-rank query); k^I = layernorm(W^I_k u) with weight and
  bias, ONE key a token; the FIRST qk_rope_head_dim values of every q^I_j and
  of k^I turn as above (``indexer_rope_interleave``: pairs); w = W^I_w u;
  I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) for s <= t;
  S_t = the min(index_topk, t + 1) positions s <= t of largest I[t, s].
dense FFN: W_down (silu(W_gate u) * W_up u)
expert FFN (``noaux_tc``, n_group 1): s = sigmoid(W_g u) over ALL the
  router's experts; chosen = top-k of s + e_score_correction_bias; gate_e =
  s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor; out = sum over
  the chosen e HELD HERE of gate_e SwiGLU_e(u) + SwiGLU_shared(u).

The share (the configuration's ``n_routed_experts`` held of the ``assumed``
``router_experts``, ids from ``expert_first``): what the absent experts would
have added is left out, here as in the program. ``share_of`` hands a test
another rank's share.

Departures from the published code (DeepSeek-V3.2's ``inference/model.py``,
whose index this family carries), each on purpose:
  * no Hadamard rotation of q^I and k^I: it is orthogonal, applied to both,
    and the dot products do not see it (the published code applies it before
    it quantises both to fp8);
  * no fp8: the index key is whatever type the weights are handed in (the
    program serves it in bfloat16), and scores are float32;
  * the constant scales ``index_n_heads^-1/2`` (on w) and
    ``index_head_dim^-1/2`` (on the scores) are left out: positive
    constants, which move no selection;
  * the key's norm is a LayerNorm with weight and bias, eps 1e-6, as the
    published code has it: the configuration has no key for it (``assumed``);
  * no multi-token-prediction layer (``num_nextn_predict_layers`` reduced to 0);
  * weights and norms are random, ``e_score_correction_bias`` random or what
    its training rule leaves on seeded tokens (``glm5_weights.py``); the
    vocabulary is the share's slice; the sequence
    is padded to whole blocks (everything is causal: the padding cannot reach
    a real position);
  * where two index scores, or two biased router scores, tie exactly, the
    lower position (expert) wins, as ``jax.lax.top_k`` orders them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.hybrid_reference import _pad, _rms, _vocab_logprobs
from benchlib.kanana2_reference import _dense_ffn, _expert_ffn, _turn_pairs

F32 = jnp.float32
QUERY_BLOCK = 1024  # queries a block; a sequence is padded to whole blocks


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and,
    for the share and the index key's norm, its ``assumed``."""
    a = cfg.get("assumed", {})
    held = int(cfg["n_routed_experts"])
    rope = cfg.get("rope_parameters") or {}
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
        "i_heads": int(cfg["index_n_heads"]),
        "i_dim": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "i_eps": float(a.get("index_norm_eps", 1e-6)),
        "theta": float(cfg.get("rope_theta") or rope["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def share_of(cfg: dict, rank: int, ranks: int) -> dict:
    """``cfg`` (an uncut configuration: every expert held) as rank ``rank``
    of ``ranks`` holds its expert layers."""
    e_all = int(cfg["n_routed_experts"])
    per = e_all // ranks
    return {**cfg, "n_routed_experts": per, "assumed": {**cfg.get("assumed", {}), "router_experts": e_all, "expert_first": rank * per}}


def _turn_first(x, pos, rope: int, theta: float):
    """The index's rotary embedding: the first ``rope`` values of x [T, ..., d] turn as pairs."""
    return jnp.concatenate([_turn_pairs(x[..., :rope], pos, theta), x[..., rope:]], axis=-1)


_KEYS = ("heads", "q_rank", "rank", "nope", "rope", "vd", "i_heads", "i_dim", "topk", "i_eps", "eps", "theta")


@functools.partial(jax.jit, static_argnames=_KEYS)
def _keys(x, lp, **d):
    """What every token of x [T, D] offers the queries: (the normed input u,
    the normed low-rank query q_r, the normed latent c [T, rank] that every
    head's key and value are made from, the rotary key [T, rope], the index
    key [T, i_dim])."""
    T = x.shape[0]
    pos = jnp.arange(T)
    u = _rms(x, lp["input_norm"], d["eps"])
    q_r = _rms(u @ lp["w_qa"].astype(F32), lp["q_a_norm"], d["eps"])
    kva = u @ lp["w_kva"].astype(F32)
    c = _rms(kva[:, : d["rank"]], lp["kv_norm"], d["eps"])
    k_r = _turn_pairs(kva[:, d["rank"] :], pos, d["theta"])
    k_i = u @ lp["wi_k"].astype(F32)
    k_i = (k_i - k_i.mean(-1, keepdims=True)) * jax.lax.rsqrt(k_i.var(-1, keepdims=True) + d["i_eps"])
    k_i = k_i * lp["wi_k_norm"].astype(F32) + lp["wi_k_norm_bias"].astype(F32)
    return u, q_r, c, k_r, _turn_first(k_i, pos, d["rope"], d["theta"])


def select(scores, pos, topk: int):
    """S_t as a mask: scores [B, T] of the queries at ``pos`` [B] -> bool
    [B, T], the min(topk, t + 1) positions s <= t of largest score (equal
    scores: the lower position first)."""
    T = scores.shape[1]
    causal = pos[:, None] >= jnp.arange(T)[None, :]
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(topk, T))[0][:, -1:]  # -inf where fewer than topk are causal: everything is in
    above, equal = masked > kth, (masked == kth) & causal
    left = topk - above.sum(-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= left))) & causal


@functools.partial(jax.jit, static_argnames=_KEYS)
def _index_block(lo, u, q_r, k_i, lp, **d):
    """(I[t, s] [B, T], S_t as a mask [B, T]) for the B = QUERY_BLOCK queries from ``lo``."""
    B = min(QUERY_BLOCK, u.shape[0])
    pos = lo + jnp.arange(B)
    ub, qb = jax.lax.dynamic_slice_in_dim(u, lo, B), jax.lax.dynamic_slice_in_dim(q_r, lo, B)
    q_i = _turn_first((qb @ lp["wi_qb"].astype(F32)).reshape(B, d["i_heads"], d["i_dim"]), pos, d["rope"], d["theta"])
    w = ub @ lp["wi_w"].astype(F32)  # [B, heads]

    def head(acc, qw):  # one index head at a time: [B, T]
        q_j, w_j = qw
        return acc + w_j[:, None] * jax.nn.relu(q_j @ k_i.T), None

    scores, _ = jax.lax.scan(head, jnp.zeros((B, k_i.shape[0]), F32), (jnp.moveaxis(q_i, 1, 0), w.T))
    return scores, select(scores, pos, d["topk"])


@functools.partial(jax.jit, static_argnames=_KEYS)
def _attend_block(lo, q_r, c, k_r, chosen, lp, **d):
    """The attention's output [B, H * vd] of the B queries from ``lo`` over
    the keys ``chosen`` [B, T] marks; a head's keys and values are made from
    the latent when its turn comes (all heads' at once are 2.3 GB at 20k
    tokens)."""
    B = chosen.shape[0]
    nope = d["nope"]
    pos = lo + jnp.arange(B)
    q = (jax.lax.dynamic_slice_in_dim(q_r, lo, B) @ lp["w_qb"].astype(F32)).reshape(B, d["heads"], nope + d["rope"])
    q_rope = _turn_pairs(q[..., nope:], pos, d["theta"])

    w_kvb = lp["w_kvb"].astype(F32).reshape(d["rank"], d["heads"], nope + d["vd"])

    def head(args):  # one head at a time: scores are [B, T]
        qn, qr, w = args
        kv_h = c @ w  # [T, nope + vd]
        s = (qn @ kv_h[:, :nope].T + qr @ k_r.T) * (nope + d["rope"]) ** -0.5
        return jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1) @ kv_h[:, nope:]

    o = jax.lax.map(head, (jnp.moveaxis(q[..., :nope], 1, 0), jnp.moveaxis(q_rope, 1, 0), jnp.moveaxis(w_kvb, 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(B, d["heads"] * d["vd"])


def _attention(x, lp, d: dict, probe: dict | None = None):
    """x + the layer's attention, a block of queries at a time. ``probe``
    (a dict) takes the layer's index keys and, for the positions it lists
    under ``at``, I[t, :] and S_t."""
    T = x.shape[0]
    kd = {k: d[k] for k in _KEYS}
    u, q_r, c, k_r, k_i = _keys(x, lp, **kd)
    wo = lp["wo"].astype(F32)
    outs = []
    want = np.asarray(probe["at"], np.int64) if probe is not None else None
    if probe is not None:
        probe.update(keys=np.asarray(k_i), scores={}, chosen={})
    for lo in range(0, T, QUERY_BLOCK):
        scores, chosen = _index_block(jnp.int32(lo), u, q_r, k_i, lp, **kd)
        if probe is not None:
            for t in want[(want >= lo) & (want < lo + chosen.shape[0])]:
                probe["scores"][int(t)] = np.asarray(scores[t - lo])
                probe["chosen"][int(t)] = np.asarray(chosen[t - lo])
            if want.max() < lo + chosen.shape[0]:
                return None  # the probe's last position is behind us
        outs.append(_attend_block(jnp.int32(lo), q_r, c, k_r, chosen, lp, **kd) @ wo)
    return x + jnp.concatenate(outs)


def layer_params(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s slice of the weight tree (stacked per kind of layer:
    ``mla`` the leading dense ones, ``mla_moe`` the expert layers)."""
    n_dense = dims(cfg)["dense"]
    stack, j = ("mla", i) if i < n_dense else ("mla_moe", i - n_dense)
    return {k: v[j] for k, v in params[stack].items()}


def hidden_states(
    params: dict, cfg: dict, padded, routing: list | None = None, layers: int | None = None, shared: bool = True, rebias=None
):
    """The last layer's output [T, D] (before the final norm) for tokens [T]
    (T whole blocks, or under one); ``layers`` stops after that many. With
    ``routing`` every expert layer appends (the experts it picked [T, top_k],
    the margin of each token's choice [T]) as numpy. ``shared`` False leaves
    the shared block out (a test that adds shares up counts it once). With
    ``rebias`` every expert layer routes under the bias ``rebias(the router's
    scores [T, E_all])`` returns in its own bias's place
    (``glm5_weights.balanced_router_bias``)."""
    d = dims(cfg)
    x = params["embed"][padded].astype(F32)
    for i in range(d["layers"] if layers is None else layers):
        lp = layer_params(params, cfg, i)
        x = _attention(x, lp, d)
        if i < d["dense"]:
            x = _dense_ffn(x, lp, eps=d["eps"])
        else:
            if rebias is not None:
                scores = jax.nn.sigmoid(_rms(x, lp["post_norm"], d["eps"]) @ lp["w_router"].astype(F32))
                lp = {**lp, "router_bias": rebias(scores)}
            x, picked = _expert_ffn(
                x, lp, eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"], e0=d["e0"], shared=shared
            )
            if routing is not None:
                routing.append(tuple(np.asarray(a) for a in picked))
    return x


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
        hidden_states(params, cfg, jnp.asarray(padded), routing)
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


def first_layer_selection(params: dict, cfg: dict, ids, at, pad_to: int) -> dict:
    """The FIRST layer's index over the tokens ``ids``: {"keys": the index
    keys [len(ids), index_head_dim], "scores": {t: I[t, :t + 1]}, "chosen":
    {t: S_t as a bool mask [t + 1]}} for the positions t in ``at``."""
    ids, padded = _pad(ids, _blocks(len(ids), pad_to))
    probe: dict = {"at": sorted(int(t) for t in at)}
    with jax.default_matmul_precision("highest"):
        _attention(params["embed"][jnp.asarray(padded)].astype(F32), layer_params(params, cfg, 0), dims(cfg), probe)
    n = len(ids)
    return {
        "keys": probe["keys"][:n],
        "scores": {t: v[: t + 1] for t, v in probe["scores"].items()},
        "chosen": {t: v[: t + 1] for t, v in probe["chosen"].items()},
    }
