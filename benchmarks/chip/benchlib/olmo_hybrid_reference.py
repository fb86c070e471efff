"""Plain reference of the ``olmo_hybrid`` decoder (Olmo-Hybrid-7B):
gated-delta-rule linear-attention layers beside full-attention layers in the
order of the published ``layer_types``, in the Olmo family's block (an
RMSNorm on each sublayer's OUTPUT, none before it).

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no chunks, nothing imported from the program.
The delta rule is run TOKEN BY TOKEN, one ``lax.scan`` step a token, each
step the four lines of the recurrence; the program's chunked scan and its
decode kernel are held to it. It reads the configuration file's published
keys and the seeded weight tree of ``olmo_hybrid_weights.py``. One layer is
cast to float32 at a time, attention goes one head at a time and the
vocabulary is read in blocks, so that 4k tokens at the published widths fit
on the chip beside the weights once the engine is gone.

The model (every matrix without bias):
  h0 = embed[ids]
  h  = x + rmsnorm(mixer_l(x), post_attention_norm_l)
  x' = h + rmsnorm(W_down (silu(W_gate h) * W_up h), post_feedforward_norm_l)
  logits = rmsnorm(x_last, final_norm) @ lm_head^T           (untied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)
linear-attention mixer (a gated delta rule; the ``fla`` layer's form):
  q~ = W_q x, k~ = W_k x, v~ = W_v x; each through its own depthwise causal
  conv of ``linear_conv_kernel_dim`` taps (zeros before the sequence, no
  bias) and SiLU; per head q, k in R^K, v in R^V;
  q <- q / sqrt(|q|^2 + 1e-6) / sqrt(K), k <- k / sqrt(|k|^2 + 1e-6);
  beta = 2 sigmoid(W_b x) (the 2 is ``linear_allow_neg_eigval``),
  g = -exp(A_log) softplus(W_a x + dt_bias), alpha = exp(g), a head;
  S' = alpha S_{t-1};  u = beta (v - S'^T k);  S_t = S' + k u^T;  o = S_t^T q
  with S in R^{K x V} a head, zero before the sequence;
  y = W_o [ rmsnorm_V(o; o_norm) * silu(W_g x) ], the norm over a head's V.
full-attention mixer: q = rmsnorm(W_q x), k = rmsnorm(W_k x) over the WHOLE
  projection, heads of ``head_dim``, causal softmax of q k^T / sqrt(head_dim),
  NO rotary embedding (the published ``rope_theta`` is null).

Departures from the published model, each on purpose:
  * weights are random (``olmo_hybrid_weights.py``), norms too;
  * the layer forms above are what the configuration file lists under
    ``assumed`` (its ``config.json`` names sizes, not forms);
  * the sequence is padded to a fixed length so one program serves every
    sample (everything is causal: the padding cannot reach a real position;
    in ``first_layer_state`` the padding is kept out of the state by
    beta = 0 and g = 0 there: the state stands still).
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
KINDS = {"linear_attention": "gdn", "full_attention": "attention"}


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys."""
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "kinds": [KINDS[t] for t in cfg["layer_types"]],
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or cfg["assumed"]["head_dim"]),
        "gh": int(cfg["linear_num_value_heads"]),
        "gk": int(cfg["linear_key_head_dim"]),
        "gv": int(cfg["linear_value_head_dim"]),
        "taps": int(cfg["linear_conv_kernel_dim"]),
        "neg": bool(cfg["linear_allow_neg_eigval"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def _mlp(h, lp, eps):
    y = (jax.nn.silu(h @ lp["w_gate"].astype(F32)) * (h @ lp["w_up"].astype(F32))) @ lp["w_down"].astype(F32)
    return h + _rms(y, lp["post_norm"], eps)


def _conv_silu(u, w, taps):
    """Depthwise causal conv of u [T, C] with taps w [taps, 1, C] (tap j of
    channel c: w[j, 0, c]; zeros before the sequence), then SiLU."""
    T = u.shape[0]
    w = w.astype(F32)[:, 0, :]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * padded[j : j + T] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token. q and k [T, H, K], v [T, H, V], g and
    beta [T, H]. Returns (the state after the last token [H, K, V], o
    [T, H, V])."""
    H, K, V = q.shape[1], q.shape[2], v.shape[2]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    return jax.lax.scan(token, jnp.zeros((H, K, V), F32), (q, k, v, g, beta))


@functools.partial(jax.jit, static_argnames=("H", "K", "V", "taps", "neg", "eps"))
def _gdn_layer(x, lp, n, *, H, K, V, taps, neg, eps):
    """(the layer's output [T, D], the state after the first ``n`` tokens [H, K, V])."""
    T = x.shape[0]
    q = _conv_silu(x @ lp["q_proj"].astype(F32), lp["q_conv_w"], taps).reshape(T, H, K)
    k = _conv_silu(x @ lp["k_proj"].astype(F32), lp["k_conv_w"], taps).reshape(T, H, K)
    v = _conv_silu(x @ lp["v_proj"].astype(F32), lp["v_conv_w"], taps).reshape(T, H, V)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * K**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ lp["b_proj"].astype(F32)) * (2.0 if neg else 1.0)
    g = -jnp.exp(lp["A_log"].astype(F32)) * jax.nn.softplus(x @ lp["a_proj"].astype(F32) + lp["dt_bias"].astype(F32))
    # the padding past token n must not enter the state that is handed back
    real = (jnp.arange(T)[:, None] < n).astype(F32)
    s_n, o = delta_rule(q, k, v, g * real, beta * real)
    z = (x @ lp["g_proj"].astype(F32)).reshape(T, H, V)
    y = (_rms(o, lp["o_norm"], eps) * jax.nn.silu(z)).reshape(T, H * V)
    h = x + _rms(y @ lp["o_proj"].astype(F32), lp["input_norm"], eps)
    return _mlp(h, lp, eps), s_n


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps"))
def _attention_layer(x, lp, *, heads, kv_heads, hd, eps):
    T = x.shape[0]
    q = _rms(x @ lp["wq"].astype(F32), lp["q_norm"], eps).reshape(T, heads, hd)
    k = _rms(x @ lp["wk"].astype(F32), lp["k_norm"], eps).reshape(T, kv_heads, hd)
    v = (x @ lp["wv"].astype(F32)).reshape(T, kv_heads, hd)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    grp = heads // kv_heads

    def one(j):  # one KV head at a time: scores are [grp, T, T]
        qj = jax.lax.dynamic_slice_in_dim(q, j * grp, grp, axis=1)
        s = jnp.einsum("tgd,sd->gts", qj, k[:, j, :]) * hd**-0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, v[:, j, :])

    a = jnp.moveaxis(jax.lax.map(one, jnp.arange(kv_heads)), 0, 1).reshape(T, heads * hd)
    h = x + _rms(a @ lp["wo"].astype(F32), lp["input_norm"], eps)
    return _mlp(h, lp, eps)


def hidden_states(params: dict, cfg: dict, padded, n=None, first_state: list | None = None) -> jax.Array:
    """The last layer's output [T, D] (before the final norm) for tokens [T],
    of which the first ``n`` are real (default: all). With ``first_state`` it
    stops after the first linear-attention layer and leaves there that
    layer's state after those ``n`` tokens."""
    d = dims(cfg)
    n = jnp.int32(len(padded) if n is None else n)
    x = params["embed"][padded].astype(F32)
    seen = {"gdn": 0, "attention": 0}
    for kind in d["kinds"]:
        lp = {k: v[seen[kind]] for k, v in params[kind].items()}
        seen[kind] += 1
        if kind == "gdn":
            x, s_n = _gdn_layer(x, lp, n, H=d["gh"], K=d["gk"], V=d["gv"], taps=d["taps"], neg=d["neg"], eps=d["eps"])
            if first_state is not None:
                first_state.append(np.asarray(s_n))
                break
        else:
            x = _attention_layer(x, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], eps=d["eps"])
    return x


def first_layer_state(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """The first linear-attention layer's state after exactly the tokens
    ``ids``: float32 [heads, key size, value size]."""
    ids, padded = _pad(ids, pad_to)
    state: list = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, cfg, jnp.asarray(padded), n=len(ids), first_state=state)
    return state[0]


def token_logits(params: dict, cfg: dict, ids) -> np.ndarray:
    """Logits [len(ids), vocabulary] of a short sequence (tests)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(np.asarray(ids, np.int32)))
        h = _rms(x, params["final_norm"], dims(cfg)["eps"])
        return np.asarray(h @ params["lm_head"].astype(F32).T)


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
