"""Plain reference of the ``solar_open2`` decoder (Solar-Open2-250B): gated
softmax attention without a positional embedding at the layers ``gqa_layers``
names, a delta rule with a decay of its own every key channel (``kda``)
everywhere else, and in every layer sparse experts behind a sigmoid router
with a selection bias beside one always-active shared expert.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no chunks, nothing imported from the program.
The delta rule is run TOKEN BY TOKEN, one ``lax.scan`` step a token, each
step the four lines of the recurrence; the program's chunked scan and its
decode kernel are held to it. Attention is the full causal softmax, a block
of ``QUERY_BLOCK`` queries against every key, one KV head at a time, so that
20k tokens fit on the chip once the engine is gone (scores are [group, block,
T], never [T, T]); one layer's matrices are cast to float32 where they are
used, an expert at a time, and the vocabulary is read in blocks.

The model (``u`` a sublayer's normed input; no bias in any projection):
  h0 = embed[ids];  h = x + mixer_l(rmsnorm(x));  x' = h + moe_l(rmsnorm(h))
  logits = rmsnorm(x_last, norm) @ lm_head^T                  (untied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)
attention layer (l in ``gqa_layers``): q = W_q u (H heads of head_dim), k, v =
  W_k u, W_v u (KH heads), NO rotary embedding (``use_rope`` false), no q/k
  norm, causal softmax of q k^T / sqrt(head_dim), query head i reading KV
  head i // (H / KH);  o <- o * sigmoid(W_g u), one gate a head and channel
  (``use_gqa_gate``);  y = W_o o.
kda layer (``linear_attn_config``; Kimi Linear's ``KimiDeltaAttention``):
  q~, k~, v~ = silu(conv(W_{q,k,v} u)), each through its own depthwise causal
  conv of ``short_conv_kernel_size`` taps (zeros before the sequence, no
  bias); per head q, k in R^K, v in R^V (K = V = ``head_dim``);
  q <- q / sqrt(|q|^2 + 1e-6) / sqrt(K),  k <- k / sqrt(|k|^2 + 1e-6);
  a[h, :] = -exp(A_log[h]) softplus(W_fb W_fa u + dt_bias)[h, :]  in R^K
  (``kda_use_full_proj`` false: the decay through a rank of ``head_dim``);
  beta[h] = 2 sigmoid(W_b u)[h]  (the 2 is ``kda_allow_neg_eigval``);
  S' = diag(exp(a)) S_{t-1};  w = beta (v - S'^T k);  S_t = S' + k w^T;
  o = S_t^T q,  S in R^{K x V} a head, zero before the sequence;
  y = W_o [ rmsnorm_V(o; o_norm) * sigmoid(W_gb W_ga u) ].
expert FFN, every layer: s = sigmoid(W_r u) over ALL the router's experts;
  chosen = top-k of s + e_score_correction_bias; gate_e = s_e / (sum of the
  chosen s + 1e-20) * routed_scaling_factor; out = sum over the chosen e HELD
  HERE of gate_e SwiGLU_e(u) + SwiGLU_shared(u).

The share (the configuration's ``n_routed_experts`` held of the ``assumed``
``router_experts``, ids from ``expert_first``): what the absent experts would
have added is left out, here as in the program. ``share_of`` hands a test
another rank's share.

Departures from the published model, each on purpose:
  * the layer forms above are what the configuration file lists under
    ``assumed``: its ``config.json`` names sizes and switches, not forms;
  * weights are random (``solar_open2_weights.py``), norms too;
    ``e_score_correction_bias`` is what its training rule leaves on seeded
    tokens; the vocabulary is the share's slice;
  * ``intermediate_size`` is read by no layer (``first_k_dense_replace`` 0);
  * the sequence is padded to whole blocks so that a handful of programs
    serve every sample (everything is causal: the padding cannot reach a real
    position; in ``first_layer_state`` the padding is kept out of the state
    by beta = 0 and a = 0 there: the state stands still);
  * where two biased router scores tie exactly the lower expert wins, as
    ``jax.lax.top_k`` orders them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the pieces every decoder's reference shares (benchlib, not the program): the
# RMSNorm, the padding to one length, the log-softmax over vocabulary blocks,
# the DeepSeek-V3 lineage's routed block over a share of the experts
from benchlib.hybrid_reference import _pad, _rms, _vocab_logprobs
from benchlib.kanana2_reference import _expert_ffn
from benchlib.olmo_hybrid_reference import _conv_silu

F32 = jnp.float32
QUERY_BLOCK = 512  # queries a block of the attention layers; a sequence is padded to whole blocks


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and,
    for the share, its ``assumed``."""
    a = cfg.get("assumed", {})
    lin = cfg["linear_attn_config"]
    held = int(cfg["n_routed_experts"])
    n = int(cfg["num_hidden_layers"])
    gqa = {int(i) for i in cfg["gqa_layers"]}
    return {
        "D": int(cfg["hidden_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        "E": held,
        "E_all": int(a.get("router_experts", held)),
        "e0": int(a.get("expert_first", 0)),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "layers": n,
        "kinds": ["attention" if i in gqa else "kda" for i in range(n)],
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "lh": int(lin["num_heads"]),
        "lk": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "neg": bool(cfg["kda_allow_neg_eigval"]),
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


def delta_rule(q, k, v, a, beta):
    """The recurrence token by token. q, k and a [T, H, K], v [T, H, V], beta
    [T, H]. Returns (the state after the last token [H, K, V], o [T, H, V])."""
    H, K, V = q.shape[1], q.shape[2], v.shape[2]

    def token(s, x):
        q_t, k_t, v_t, a_t, b_t = x
        s = s * jnp.exp(a_t)[:, :, None]
        w = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * w[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    return jax.lax.scan(token, jnp.zeros((H, K, V), F32), (q, k, v, a, beta))


@functools.partial(jax.jit, static_argnames=("H", "K", "taps", "neg", "eps"))
def _kda_layer(x, lp, n, *, H, K, taps, neg, eps):
    """(x + the mixer's output [T, D], the state after the first ``n`` tokens [H, K, K])."""
    T = x.shape[0]
    u = _rms(x, lp["input_norm"], eps)
    q = _conv_silu(u @ lp["q_proj"].astype(F32), lp["q_conv_w"], taps).reshape(T, H, K)
    k = _conv_silu(u @ lp["k_proj"].astype(F32), lp["k_conv_w"], taps).reshape(T, H, K)
    v = _conv_silu(u @ lp["v_proj"].astype(F32), lp["v_conv_w"], taps).reshape(T, H, K)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * K**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(u @ lp["b_proj"].astype(F32)) * (2.0 if neg else 1.0)
    f = (u @ lp["f_a"].astype(F32)) @ lp["f_b"].astype(F32) + lp["dt_bias"].astype(F32)
    a = -jnp.exp(lp["A_log"].astype(F32))[None, :, None] * jax.nn.softplus(f).reshape(T, H, K)
    # the padding past token n must not enter the state that is handed back
    real = (jnp.arange(T) < n).astype(F32)
    s_n, o = delta_rule(q, k, v, a * real[:, None, None], beta * real[:, None])
    z = ((u @ lp["g_a"].astype(F32)) @ lp["g_b"].astype(F32)).reshape(T, H, K)
    y = (_rms(o, lp["o_norm"], eps) * jax.nn.sigmoid(z)).reshape(T, H * K)
    return x + y @ lp["o_proj"].astype(F32), s_n


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps"))
def _attention_layer(x, lp, *, heads, kv_heads, hd, eps):
    T = x.shape[0]
    u = _rms(x, lp["input_norm"], eps)
    grp = heads // kv_heads
    q = (u @ lp["wq"].astype(F32)).reshape(T, kv_heads, grp, hd)
    k = (u @ lp["wk"].astype(F32)).reshape(T, kv_heads, hd)
    v = (u @ lp["wv"].astype(F32)).reshape(T, kv_heads, hd)
    blk = min(QUERY_BLOCK, T)
    pos = jnp.arange(T)

    def block(lo):  # a block of queries against every key, one KV head at a time: scores are [grp, blk, T]
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, axis=0)
        causal = (lo + jnp.arange(blk))[:, None] >= pos[None, :]

        def one(j):
            s = jnp.einsum("tgd,sd->gts", qb[:, j], k[:, j]) * hd**-0.5
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sd->tgd", p, v[:, j])

        return jnp.moveaxis(jax.lax.map(one, jnp.arange(kv_heads)), 0, 1).reshape(blk, heads * hd)

    o = jax.lax.map(block, jnp.arange(0, T, blk)).reshape(T, heads * hd)
    o = o * jax.nn.sigmoid(u @ lp["wg"].astype(F32))
    return x + o @ lp["wo"].astype(F32)


def layer_params(params: dict, cfg: dict, i: int) -> dict:
    """Layer ``i``'s slice of the weight tree (stacked per kind of layer:
    ``attention_moe`` and ``kda_moe``, each in model order)."""
    kinds = dims(cfg)["kinds"]
    j = kinds[:i].count(kinds[i])
    return {k: v[j] for k, v in params[f"{kinds[i]}_moe"].items()}


def hidden_states(
    params: dict, cfg: dict, padded, n=None, first_state: list | None = None, layers: int | None = None,
    shared: bool = True, rebias=None,
):
    """The last layer's output [T, D] (before the final norm) for tokens [T]
    (T whole query blocks, or under one), of which the first ``n`` are real
    (default: all); ``layers`` stops after that many. With ``first_state`` it
    stops after the first kda layer's MIXER and leaves there that layer's
    state after those ``n`` tokens. ``shared`` False leaves the shared expert
    out (a test that adds shares up counts it once). With ``rebias`` every
    expert layer routes under the bias ``rebias(the router's scores [T,
    E_all])`` returns in its own bias's place
    (``solar_open2_weights.balanced_router_bias``)."""
    d = dims(cfg)
    n = jnp.int32(len(padded) if n is None else n)
    x = params["embed"][padded].astype(F32)
    for i in range(d["layers"] if layers is None else layers):
        lp = layer_params(params, cfg, i)
        if d["kinds"][i] == "kda":
            x, s_n = _kda_layer(x, lp, n, H=d["lh"], K=d["lk"], taps=d["taps"], neg=d["neg"], eps=d["eps"])
            if first_state is not None:
                first_state.append(np.asarray(s_n))
                break
        else:
            x = _attention_layer(x, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], eps=d["eps"])
        if rebias is not None:
            scores = jax.nn.sigmoid(_rms(x, lp["post_norm"], d["eps"]) @ lp["w_router"].astype(F32))
            lp = {**lp, "router_bias": rebias(scores)}
        x, _ = _expert_ffn(x, lp, eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"], e0=d["e0"], shared=shared)
    return x


def _blocks(n: int, pad_to: int, step: int = 4 * QUERY_BLOCK) -> int:
    """The length a sequence of n tokens is computed at: whole blocks of
    ``step`` tokens (a handful of programs for every length a cell sends), at
    most ``pad_to`` rounded up to whole query blocks; a sequence under one
    query block as it is."""
    cap = -(-pad_to // QUERY_BLOCK) * QUERY_BLOCK
    return n if n <= QUERY_BLOCK and pad_to <= QUERY_BLOCK else min(cap, -(-n // step) * step)


def first_layer_state(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """The FIRST kda layer's state (the model's layer 1 under the published
    pattern: it reads what the attention layer 0 and its experts made of the
    tokens) after exactly the tokens ``ids``: float32 [heads, key size, value
    size]."""
    ids, padded = _pad(ids, _blocks(len(ids), pad_to, 2 * QUERY_BLOCK))
    state: list = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, cfg, jnp.asarray(padded), n=len(ids), first_state=state)
    return state[0]


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
            x, params["final_norm"], params["lm_head"], jnp.asarray(targets), eps=dims(cfg)["eps"], scaling=1.0, block=8192
        )
    return np.asarray(lp_all, np.float32)[: n - 1]
