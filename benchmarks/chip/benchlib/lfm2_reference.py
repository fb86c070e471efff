"""Plain reference of the ``lfm2_moe`` decoder (LFM2-8B-A1B): gated
short-convolution layers beside GQA attention layers in the order of the
published ``layer_types``; the first ``num_dense_layers`` feed-forward blocks
dense, the rest sparse experts behind a biased sigmoid router.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no sort, nothing imported from the program.
It routes FOR ITSELF: the experts of a token are the top-k of its own float32
scores, and every expert is then applied to every token under a gate that is
0 where the token did not choose it (a loop over experts). It reads the
configuration file's published keys and the seeded weight tree of
``lfm2_weights.py``. One layer (one expert) is cast to float32 at a time and
the vocabulary is read in blocks, so that 4k tokens at the published widths
fit on the chip once the engine is gone.

The model:
  h0 = embed[ids]
  h  = h + mixer_l(rmsnorm(h, operator_norm_l));  h = h + ffn_l(rmsnorm(h, ffn_norm_l))
  logits = rmsnorm(h, embedding_norm) @ embed^T            (tied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + norm_eps)
short-conv mixer: [B | C | x] = W_in u;  g_t = B_t * x_t;
  c_t = sum_{j<K} w_j g_{t-K+1+j} per channel (causal, zeros before the
  sequence, no bias, no activation);  y_t = W_out (C_t * c_t)
attention mixer: q, k, v, out without bias; RMSNorm over each head of q and
  of k, then the rotary embedding (rotate-half over the whole head,
  rope_theta); causal softmax of q k^T / sqrt(head size)
dense FFN (layers < num_dense_layers): W2 (silu(W1 u) * W3 u)
expert FFN: s = sigmoid(W_g u); experts = top-k of s + expert_bias; gate_e =
  s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor (the UNBIASED
  scores); out = sum_e gate_e W2_e (silu(W1_e u) * W3_e u). No shared
  expert, no capacity, no token dropped.

Departures from the published model, each on purpose:
  * weights and ``expert_bias`` are random (``lfm2_weights.py``), norms too;
  * the score function (sigmoid), the biased selection and the 1e-6 are the
    family's published implementation, not keys of its ``config.json``;
  * the embedding is tied (the configuration's ``assumed``);
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
NORM_TOPK_EPS = 1e-6


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys."""
    kinds = ["attention" if t == "full_attention" else t for t in cfg["layer_types"]]
    n_dense = int(cfg["num_dense_layers"])
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["num_experts"]),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "kinds": kinds,
        "ffns": ["dense" if i < n_dense else "moe" for i in range(len(kinds))],
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or cfg["assumed"]["head_dim"]),
        "taps": int(cfg["conv_L_cache"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["norm_eps"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def stack_of(kind: str, ffn: str) -> str:
    """The weight tree's stack of the layers with this mixer and this FFN."""
    return kind if ffn == "dense" else f"{kind}_{ffn}"


@functools.partial(jax.jit, static_argnames=("taps", "eps"))
def _conv_mixer(x, lp, *, taps, eps):
    T = x.shape[0]
    b, c, xx = jnp.split(_rms(x, lp["input_norm"], eps) @ lp["in_proj"].astype(F32), 3, axis=-1)
    w = lp["conv_w"].astype(F32)[:, 0, :]  # tap j of channel c: conv_w[j, 0, c]
    padded = jnp.pad(b * xx, ((taps - 1, 0), (0, 0)))
    conv = sum(w[j] * padded[j : j + T] for j in range(taps))
    return x + (c * conv) @ lp["out_proj"].astype(F32)


def _rotate(t, cos, sin):
    half = t.shape[-1] // 2
    turned = jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)
    return t * cos + turned * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "theta"))
def _attention_mixer(x, lp, *, heads, kv_heads, hd, eps, theta):
    T = x.shape[0]
    h = _rms(x, lp["input_norm"], eps)
    q = _rms((h @ lp["wq"].astype(F32)).reshape(T, heads, hd), lp["q_norm"], eps)
    k = _rms((h @ lp["wk"].astype(F32)).reshape(T, kv_heads, hd), lp["k_norm"], eps)
    v = (h @ lp["wv"].astype(F32)).reshape(T, kv_heads, hd)
    pos = jnp.arange(T)
    freq = theta ** (-jnp.arange(0, hd // 2, dtype=F32) / (hd // 2))
    ang = pos[:, None].astype(F32) * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    causal = pos[:, None] >= pos[None, :]
    g = heads // kv_heads
    outs = []
    for j in range(kv_heads):  # one KV head at a time: scores are [g, T, T]
        s = jnp.einsum("tgd,sd->gts", q[:, j * g : (j + 1) * g, :], k[:, j, :]) * hd**-0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", p, v[:, j, :]))
    a = jnp.concatenate(outs, axis=1).reshape(T, heads * hd)
    return x + a @ lp["wo"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    u = _rms(x, lp["post_norm"], eps)
    return x + (jax.nn.silu(u @ lp["w_gate"].astype(F32)) * (u @ lp["w_up"].astype(F32))) @ lp["w_down"].astype(F32)


def route(u, w_router, bias, *, top_k: int, norm_topk: bool, scale: float):
    """u [T, D] float32 -> (gate of every expert for every token [T, E], 0
    where not chosen; the chosen experts [T, top_k]; the margin of the choice
    [T]: the last chosen expert's biased score less the best unchosen one's)."""
    s = jax.nn.sigmoid(u @ w_router.astype(F32))
    best, order = jax.lax.top_k(s + bias.astype(F32), top_k + 1)
    chosen, margin = order[:, :top_k], best[:, top_k - 1] - best[:, top_k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        picked = picked / (picked.sum(-1, keepdims=True) + NORM_TOPK_EPS)
    picked = picked * scale
    onehot = chosen[:, :, None] == jnp.arange(s.shape[-1])[None, None, :]
    return jnp.sum(jnp.where(onehot, picked[:, :, None], 0.0), axis=1), chosen, margin


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk", "scale"))
def _expert_ffn(x, lp, *, eps, top_k, norm_topk, scale):
    u = _rms(x, lp["post_norm"], eps)
    gates, chosen, margin = route(u, lp["w_router"], lp["router_bias"], top_k=top_k, norm_topk=norm_topk, scale=scale)

    def one(acc, ew):  # every expert on every token; its gate is 0 where not chosen
        w1, w3, w2, g = ew
        y = (jax.nn.silu(u @ w1.astype(F32)) * (u @ w3.astype(F32))) @ w2.astype(F32)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["we_gate"], lp["we_up"], lp["we_down"], gates.T))
    return x + out, (chosen, margin)


def hidden_states(params: dict, cfg: dict, padded, routing: list | None = None) -> jax.Array:
    """The last layer's output [T, D] (before the final norm) for tokens [T].
    With ``routing`` every expert layer appends (the experts it picked
    [T, top_k] int32, the margin of each token's choice [T]) as numpy."""
    d = dims(cfg)
    x = params["embed"][padded].astype(F32)
    seen: dict[str, int] = {}
    for kind, ffn in zip(d["kinds"], d["ffns"]):
        stack = stack_of(kind, ffn)
        i = seen.get(stack, 0)
        seen[stack] = i + 1
        lp = {k: v[i] for k, v in params[stack].items()}
        if kind == "conv":
            x = _conv_mixer(x, lp, taps=d["taps"], eps=d["eps"])
        else:
            x = _attention_mixer(
                x, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], eps=d["eps"], theta=d["theta"]
            )
        if ffn == "dense":
            x = _dense_ffn(x, lp, eps=d["eps"])
        else:
            x, picked = _expert_ffn(x, lp, eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"])
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


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    ids, padded = _pad(ids, pad_to)
    n = len(ids)
    targets = np.zeros(pad_to, np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded))
        head = params.get("lm_head", params["embed"])  # tied unless the weight tree brings a head
        lp_all = _vocab_logprobs(x, params["final_norm"], head, jnp.asarray(targets), eps=dims(cfg)["eps"], scaling=1.0, block=16384)
    return np.asarray(lp_all, np.float32)[: n - 1]
