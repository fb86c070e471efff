"""Plain reference of the ``cohere2_moe`` decoder (command-a-plus-05-2026): a
PARALLEL block under ONE LayerNorm without bias, window layers with a rotary
embedding beside full layers without any position, and in every layer sparse
experts behind a sigmoid router beside FOUR shared experts that are averaged.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no rings, no batching, nothing imported from the program.
A window layer is the full causal softmax under the plain mask ``0 <= t - s <
sliding_window``; a block of ``QUERY_BLOCK`` queries meets every key, one KV
head at a time, so that 20k tokens fit on the chip once the engine is gone
(scores are [group, block, T], never [T, T]). One layer's matrices are cast to
float32 where they are used, an expert at a time, the rows of an expert block
in blocks, and the vocabulary is read in blocks.

The model (no bias in any projection or norm):
  x_0 = embed[ids]
  u = layernorm(x; w) = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * w
  x' = x + Attn_l(u) + MoE_l(u)                       (``use_parallel_block``)
  logits = logit_scale * layernorm(x_last; norm) @ embed^T              (tied)
both attention kinds: q = W_q u (H heads of head_dim), k, v = W_k u, W_v u (KH
  heads), no q/k norm, softmax of q k^T / sqrt(head_dim), query head i reading
  KV head i // (H / KH);  y = W_o o.
window layer (``layer_types[l]`` ``sliding_attention``): q and k rotated at the
  token's position over ALL head_dim channels in pairs (2i, 2i+1)
  (``rope_gptj``, ``rotary_pct`` 1): (a, b) -> (a cos - b sin, b cos + a sin),
  angle = position * rope_theta^(-2i / head_dim); query t attends keys t -
  sliding_window + 1 .. t (the window counts the query's own token).
full layer (``full_attention``): causal over every token, NO positional
  embedding.
MoE, every layer: s = sigmoid(W_r u) over ALL the router's experts; chosen =
  the ``num_experts_per_tok`` largest of s (no selection bias, no groups);
  gate_e = s_e / sum(chosen s) (``norm_topk_prob``); out = sum over the chosen
  e HELD HERE of gate_e SwiGLU_e(u) + (1 / n_shared) sum_j SwiGLU_shared_j(u),
  SwiGLU(u) = W_d (silu(W_g u) * W_u u); every expert, routed or shared, of
  width ``moe_intermediate_size`` (the catalog's reading of
  ``intermediate_size``). The shared experts lie side by side in one leaf
  (expert j: columns j * width .. of ``ws_gate`` / ``ws_up``, rows of
  ``ws_down``) and are computed ONE AT A TIME here.

The share (the configuration's ``num_experts`` held of the ``assumed``
``router_experts``, ids from ``expert_first``): what the absent experts would
have added is left out, here as in the program. ``share_of`` hands a test
another rank's share.

Departures from the published model, each on purpose:
  * ``shared_expert_combination_strategy: "average"`` is read as the MEAN of
    the shared experts, added to the routed sum (the configuration file's
    ``assumed_notes`` names the other readings);
  * weights are random (``cohere2_moe_weights.py``), norms too; the vocabulary
    is the share's slice (embedding AND the tied head);
  * NO vision tower: the source's ``config`` is the language model's and holds
    no key of it; rollouts here are text only;
  * ``prefix_dense_*`` are read by no layer (``first_k_dense_replace`` 0);
  * the sequence is padded to whole blocks so that a handful of programs serve
    every sample (everything is causal: the padding cannot reach a real
    position);
  * where two router scores tie exactly the lower expert wins, as
    ``jax.lax.top_k`` orders them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.hybrid_reference import _pad

F32 = jnp.float32
QUERY_BLOCK = 512  # queries a block of the attention layers; a sequence is padded to whole blocks
ROW_BLOCK = 2048  # rows a block of the expert layer
KINDS = {"sliding_attention": "swa", "full_attention": "attention"}


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and,
    for the share, its ``assumed``."""
    a = cfg.get("assumed", {})
    held = int(cfg["num_experts"])
    return {
        "D": int(cfg["hidden_size"]),
        "Fe": int(cfg.get("moe_intermediate_size") or cfg["intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]),
        "E": held,
        "E_all": int(a.get("router_experts", held)),
        "e0": int(a.get("expert_first", 0)),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "kinds": [KINDS[t] for t in cfg["layer_types"]],
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "window": int(cfg["sliding_window"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["layer_norm_eps"]),
        "logit_scale": float(cfg["logit_scale"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def share_of(cfg: dict, rank: int, ranks: int) -> dict:
    """``cfg`` (an uncut configuration: every expert held) as rank ``rank``
    of ``ranks`` holds its expert layers."""
    e_all = int(cfg["num_experts"])
    per = e_all // ranks
    return {**cfg, "num_experts": per, "assumed": {**cfg.get("assumed", {}), "router_experts": e_all, "expert_first": rank * per}}


def layernorm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope_pairs(x, theta: float):
    """x [T, heads, hd] rotated at positions 0..T-1, channels (2i, 2i+1) a pair."""
    T, _, hd = x.shape
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "window", "theta"))
def attention(u, lp, *, heads, kv_heads, hd, window, theta):
    """Attn(u) [T, D]: ``window`` 0 is a full layer (causal, no position), else
    a window layer (rotary q and k, keys t - window + 1 .. t). ``theta`` None
    leaves the rotary embedding out (a control of the cell's check)."""
    T = u.shape[0]
    grp = heads // kv_heads
    q = (u @ lp["wq"].astype(F32)).reshape(T, heads, hd)
    k = (u @ lp["wk"].astype(F32)).reshape(T, kv_heads, hd)
    v = (u @ lp["wv"].astype(F32)).reshape(T, kv_heads, hd)
    if window and theta is not None:
        q, k = rope_pairs(q, theta), rope_pairs(k, theta)
    q = q.reshape(T, kv_heads, grp, hd)
    blk = min(QUERY_BLOCK, T)
    pos = jnp.arange(T)

    def block(lo):  # a block of queries against every key, one KV head at a time: scores are [grp, blk, T]
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, axis=0)
        behind = (lo + jnp.arange(blk))[:, None] - pos[None, :]
        seen = (behind >= 0) & ((behind < window) if window else True)

        def one(j):
            s = jnp.einsum("tgd,sd->gts", qb[:, j], k[:, j]) * hd**-0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sd->tgd", p, v[:, j])

        return jnp.moveaxis(jax.lax.map(one, jnp.arange(kv_heads)), 0, 1).reshape(blk, heads * hd)

    o = jax.lax.map(block, jnp.arange(0, T, blk)).reshape(T, heads * hd)
    return o @ lp["wo"].astype(F32)


def _swiglu(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg.astype(F32)) * (u @ wu.astype(F32))) @ wd.astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk", "e0", "n_shared", "shared"))
def moe(u, lp, *, top_k, norm_topk, e0, n_shared, shared=True):
    """MoE(u) [T, D] and the router's scores [T, E_all]: the held experts'
    part of the routed sum plus the MEAN of the shared experts."""
    T, D = u.shape
    held, Fe = lp["we_gate"].shape[0], lp["we_gate"].shape[2]
    blk = T if T <= ROW_BLOCK or T % ROW_BLOCK else ROW_BLOCK

    def rows(ub):
        s = jax.nn.sigmoid(ub @ lp["w_router"].astype(F32))
        top, ids = jax.lax.top_k(s, top_k)
        gates = top / jnp.sum(top, axis=-1, keepdims=True) if norm_topk else top
        out = jnp.zeros_like(ub)
        for e in range(held):  # an expert at a time, every row, gate 0 for the rows that did not pick it
            g = jnp.sum(jnp.where(ids == e0 + e, gates, 0.0), axis=-1, keepdims=True)
            out = out + g * _swiglu(ub, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
        if shared:
            mean = jnp.zeros_like(ub)
            for j in range(n_shared):  # ONE shared expert at a time: columns j * Fe .. of the side-by-side leaf
                sl = slice(j * Fe, (j + 1) * Fe)
                mean = mean + _swiglu(ub, lp["ws_gate"][:, sl], lp["ws_up"][:, sl], lp["ws_down"][sl, :])
            out = out + mean / n_shared
        return out, s

    out, s = jax.lax.map(rows, u.reshape(T // blk, blk, D))
    return out.reshape(T, D), s.reshape(T, -1)


def layer_params(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s slice of the weight tree (stacked per kind of layer:
    ``swa_moe`` and ``attention_moe``, each in model order)."""
    kinds = dims(cfg)["kinds"]
    j = kinds[:i].count(kinds[i])
    return {k: v[j] for k, v in params[f"{kinds[i]}_moe"].items()}


def hidden_states(params: dict, cfg: dict, padded, layers: int | None = None, shared: bool = True, rope: bool = True, window: int | None = None):
    """The last layer's output [T, D] (before the final norm) for tokens [T]
    (T whole query blocks, or under one); ``layers`` stops after that many.
    ``shared`` False leaves the shared experts out (a test that adds shares
    up counts them once). ``rope`` False and ``window`` are the check's second
    controls: the window layers without their rotary embedding, or under
    another window."""
    d = dims(cfg)
    x = params["embed"][padded].astype(F32)
    for i in range(d["layers"] if layers is None else layers):
        lp = layer_params(params, cfg, i)
        u = layernorm(x, lp["input_norm"], d["eps"])
        win = (d["window"] if window is None else window) if d["kinds"][i] == "swa" else 0
        a = attention(u, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], window=win, theta=d["theta"] if rope else None)
        m, _ = moe(u, lp, top_k=d["K"], norm_topk=d["norm_topk"], e0=d["e0"], n_shared=d["shared"], shared=shared)
        x = x + a + m
    return x


def _blocks(n: int, pad_to: int, step: int = 4 * QUERY_BLOCK) -> int:
    """The length a sequence of n tokens is computed at: whole blocks of
    ``step`` tokens (a handful of programs for every length a cell sends), at
    most ``pad_to`` rounded up to whole query blocks; a sequence under one
    query block as it is."""
    cap = -(-pad_to // QUERY_BLOCK) * QUERY_BLOCK
    return n if n <= QUERY_BLOCK and pad_to <= QUERY_BLOCK else min(cap, -(-n // step) * step)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "block"))
def _vocab_logprobs(x, final_norm, head, targets, *, eps, scale, block):
    """log softmax(scale * layernorm(x) @ head.T)[targets], the vocabulary in blocks."""
    h = layernorm(x, final_norm, eps)
    V = head.shape[0]
    lse = jnp.full((h.shape[0],), -jnp.inf, F32)
    picked = jnp.zeros((h.shape[0],), F32)
    for lo in range(0, V, block):
        logits = (h @ head[lo : lo + block].astype(F32).T) * scale
        n = logits.shape[1]
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(logits, axis=-1))
        idx = jnp.clip(targets - lo, 0, n - 1)
        here = (targets >= lo) & (targets < lo + n)
        picked = jnp.where(here, jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0], picked)
    return picked - lse


def logits(params: dict, cfg: dict, ids, shared: bool = True, **controls) -> np.ndarray:
    """The full forward's logits [len(ids), V] float32 (small sizes: tests)."""
    d = dims(cfg)
    ids, padded = _pad(ids, _blocks(len(ids), len(ids)))
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded), shared=shared, **controls)
        return np.asarray(d["logit_scale"] * layernorm(x, params["final_norm"], d["eps"]) @ params["embed"].astype(F32).T)[: len(ids)]


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int, **controls) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    d = dims(cfg)
    ids, padded = _pad(ids, _blocks(len(ids), pad_to))
    n = len(ids)
    targets = np.zeros(len(padded), np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded), **controls)
        lp_all = _vocab_logprobs(x, params["final_norm"], params["embed"], jnp.asarray(targets), eps=d["eps"], scale=d["logit_scale"], block=8192)
    return np.asarray(lp_all, np.float32)[: n - 1]
