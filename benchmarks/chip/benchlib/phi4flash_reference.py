"""Plain reference of the ``phi4flash`` decoder (Phi-4-mini-flash-reasoning;
SambaY, arXiv:2507.06607, over YOCO arXiv:2405.05254, Mamba arXiv:2312.00752
and the Differential Transformer arXiv:2410.05258): a decoder-hybrid-decoder.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no rings, no pages, no last-token shortcut, nothing
imported from the program. EVERY layer runs over EVERY token; the selective
scan goes token by token, one ``lax.scan`` step a token; attention is blocked
over the queries (a block of queries against all keys under a mask), so that
20k tokens fit on the chip beside the weights once the engine is gone; one
layer is cast to float32 at a time and the vocabulary is read in blocks.

The model (indices 0-based, n = ``num_hidden_layers``, half = n / 2):
  h0 = embed[ids]                                  (no positional embedding)
  x <- x + Mix_i(LN_a(x));  x <- x + W_2 (silu(g) * u), [g | u] = W_1 LN_b(x)
  logits = LN_f(x_last) @ embed^T                   (tied, no bias)
  LN(x) = w * (x - mean) / sqrt(var + layer_norm_eps) + b
layer kinds: i even and i <= half: ``s6``; i odd and i < half + 1: window
attention; i = half + 1: full attention, THE shared K and V; past it i even:
``gmu``, i odd: ``cross``.
``s6`` (Mamba-1): [u | z] = W_in h; c_t = silu(conv(u)_t + b) (causal,
  depthwise, ``mamba_d_conv`` taps); [r | B | C] = W_x c_t; d = softplus(W_dt
  r + b_dt); S_t = exp(d_t (x) A) * S_{t-1} + (d_t * c_t) B_t^T with A =
  -exp(A_log); y_t = S_t C_t + D * c_t; Mix = W_out (y_t * silu(z_t)). The
  LAST such layer (i = half) hands m_t = y_t (before the gate) down.
``gmu``: Mix = W_o (m_t * silu(W_g h)), m_t of the same token.
attention, differential: query heads (2p, 2p+1) are (q1, q2) of differential
  head p; K heads (2r, 2r+1) are (k1, k2) and V heads (v1, v2) of pair r, V_r =
  [v1 | v2]; head p reads pair p // 2;
  o_p = (1 - l0) RMSNorm( softmax(q1 k1^T s + M) V_r - lam softmax(q2 k2^T s + M) V_r ),
  s = head_dim^-1/2, lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0, l0 = 0.8 - 0.6
  exp(-0.3 i), the RMSNorm over the 2 head_dim values with a learned weight,
  eps ``layer_norm_eps``; Mix = W_o concat_p(o_p) + b_o. M is causal; in a
  window layer a query also sees no key more than ``sliding_window`` - 1
  tokens behind it. ``cross`` layers compute q from their own h and take k, v
  from layer half + 1.

Departures from the published model, each on purpose:
  * weights are random (``phi4flash_weights.py``), norms too;
  * the layer forms above are what the configuration file lists under
    ``assumed`` (the published ``config.json`` names widths, not forms);
  * the sequence is padded to a fixed length so one program serves every
    sample (everything is causal: the padding cannot reach a real position;
    in ``first_layer_state`` the padding is kept out of the state by d = 0
    there: the state stands still).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.hybrid_reference import _pad

F32 = jnp.float32
_QUERY_BLOCK = 1024  # queries that meet all keys at once: [pair, 4, block, T] scores, one pair at a time
_ROW_BLOCK = 4096  # rows of an MLP at once: [block, 2 F] float32


def dims(cfg: dict) -> dict:
    """The family's sizes: the published keys, and what the file assumes."""
    a = cfg.get("assumed", {})

    def get(key, default=None):
        return cfg.get(key, a.get(key, default))

    n = int(cfg["num_hidden_layers"])
    half = n // 2
    kinds = [
        ("s6" if i % 2 == 0 else "attention" if i == half + 1 else "swa") if i <= half + 1 else ("gmu" if i % 2 == 0 else "cross")
        for i in range(n)
    ]
    D = int(cfg["hidden_size"])
    return {
        "D": D,
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "kinds": kinds,
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(get("head_dim") or D // int(cfg["num_attention_heads"])),
        "window": int(cfg["sliding_window"]),
        "inner": int(get("mamba_expand", 2)) * D,
        "N": int(get("mamba_d_state", 16)),
        "taps": int(get("mamba_d_conv", 4)),
        "rank": int(get("mamba_dt_rank") or -(-D // 16)),
        "bias": bool(get("attn_bias", True)),
        "eps": float(cfg["layer_norm_eps"]),
    }


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _mlp(x, lp, eps):
    """x + W_2 (silu(g) * u), a block of rows at a time."""
    w1, w2 = lp["w_gate_up"].astype(F32), lp["w_down"].astype(F32)

    def rows(xb):
        g, u = jnp.split(_ln(xb, lp["post_norm"], lp["post_norm_bias"], eps) @ w1, 2, axis=-1)
        return xb + (jax.nn.silu(g) * u) @ w2

    T = x.shape[0]
    if T <= _ROW_BLOCK or T % _ROW_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(-1, _ROW_BLOCK, x.shape[1])).reshape(x.shape)


def selective_scan(c, d, b, cc, a):
    """The recurrence token by token. c and d [T, channels], b and cc [T, N],
    a = -exp(A_log) [channels, N]. Returns (the state after the last token
    [channels, N], y [T, channels])."""

    def token(s, x):
        c_t, d_t, b_t, cc_t = x
        s = jnp.exp(d_t[:, None] * a) * s + (d_t * c_t)[:, None] * b_t[None, :]
        return s, s @ cc_t

    return jax.lax.scan(token, jnp.zeros(a.shape, F32), (c, d, b, cc))


@functools.partial(jax.jit, static_argnames=("N", "taps", "rank", "eps"))
def _s6_layer(x, lp, n, *, N, taps, rank, eps):
    """(the layer's output [T, D], its scan output y [T, channels] before the
    gate, the state after the first ``n`` tokens [channels, N])."""
    T = x.shape[0]
    h = _ln(x, lp["input_norm"], lp["input_norm_bias"], eps)
    u, z = jnp.split(h @ lp["in_proj"].astype(F32), 2, axis=-1)
    w = lp["conv_w"].astype(F32)[:, 0, :]  # tap j of channel c: conv_w[j, 0, c]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    c = jax.nn.silu(lp["conv_b"].astype(F32) + sum(w[j] * padded[j : j + T] for j in range(taps)))
    r, b, cc = jnp.split(c @ lp["x_proj"].astype(F32), [rank, rank + N], axis=-1)
    d = jax.nn.softplus(r @ lp["dt_proj"].astype(F32) + lp["dt_bias"].astype(F32))
    a = -jnp.exp(lp["A_log"].astype(F32)).T  # the weights hold [N, channels]
    # the padding past token n must not enter the state that is handed back
    d = d * (jnp.arange(T)[:, None] < n).astype(F32)
    s_n, y = selective_scan(c, d, b, cc, a)
    y = y + lp["D"].astype(F32) * c
    out = x + (y * jax.nn.silu(z)) @ lp["out_proj"].astype(F32)
    return _mlp(out, lp, eps), y, s_n


@functools.partial(jax.jit, static_argnames=("eps",))
def _gmu_layer(x, lp, m, *, eps):
    h = _ln(x, lp["input_norm"], lp["input_norm_bias"], eps)
    out = x + (m * jax.nn.silu(h @ lp["gmu_in"].astype(F32))) @ lp["gmu_out"].astype(F32)
    return _mlp(out, lp, eps)


def _proj(h, lp, name, bias):
    y = h @ lp[name].astype(F32)
    return y + lp[f"{name}_b"].astype(F32) if bias else y


@functools.partial(jax.jit, static_argnames=("kv_heads", "hd", "bias", "eps"))
def _keys_values(x, lp, *, kv_heads, hd, bias, eps):
    """k and v [T, pairs, 2, hd] of an attending layer that has its own."""
    h = _ln(x, lp["input_norm"], lp["input_norm_bias"], eps)
    T = x.shape[0]
    return tuple(_proj(h, lp, n, bias).reshape(T, kv_heads // 2, 2, hd) for n in ("wk", "wv"))


@functools.partial(jax.jit, static_argnames=("heads", "hd", "window", "bias", "eps"))
def _attending_layer(x, lp, k, v, depth, *, heads, hd, window, bias, eps):
    """A window, full or cross layer: queries of its own over ``k`` and ``v``
    [T, pairs, 2, hd] (its own, or the shared layer's). ``window`` 0: causal
    alone."""
    T = x.shape[0]
    pairs = k.shape[1]
    h = _ln(x, lp["input_norm"], lp["input_norm_bias"], eps)
    q = _proj(h, lp, "wq", bias).reshape(T, pairs, 2, 2, hd)  # [T, pair r, differential head 2r + a, (q1, q2), hd]
    vv = v.reshape(T, pairs, 2 * hd)  # V_r = [v1 | v2]
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth.astype(F32))
    lam = jnp.exp(jnp.sum(lp["lq1"].astype(F32) * lp["lk1"].astype(F32))) - jnp.exp(jnp.sum(lp["lq2"].astype(F32) * lp["lk2"].astype(F32))) + l0
    s_pos = jnp.arange(T)
    block = min(_QUERY_BLOCK, T)
    assert T % block == 0

    def pair(r):  # one K/V pair at a time
        k_r, v_r, q_r = k[:, r], vv[:, r], q[:, r]

        def queries(i):
            t_pos = i * block + jnp.arange(block)
            q_b = jax.lax.dynamic_slice_in_dim(q_r, i * block, block, axis=0)  # [block, a, s, hd]
            ok = s_pos[None, :] <= t_pos[:, None]
            if window:
                ok = ok & (t_pos[:, None] - s_pos[None, :] < window)
            scores = jnp.einsum("tasd,usd->astu", q_b, k_r) * hd**-0.5
            p = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), axis=-1)
            o = jnp.einsum("astu,ue->tase", p, v_r)  # [block, a, (softmax 1, softmax 2), 2 hd]
            o = o[:, :, 0] - lam * o[:, :, 1]
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * lp["sub_norm"].astype(F32)
            return (1.0 - l0) * o  # [block, a, 2 hd]

        return jax.lax.map(queries, jnp.arange(T // block)).reshape(T, 2, 2 * hd)

    o = jnp.moveaxis(jax.lax.map(pair, jnp.arange(pairs)), 0, 1).reshape(T, heads * hd)  # differential heads in order
    out = x + _proj(o, lp, "wo", bias)
    return _mlp(out, lp, eps)


def hidden_states(params: dict, cfg: dict, padded, n=None, first_state: list | None = None) -> jax.Array:
    """The last layer's output [T, D] (before the final norm) for tokens [T],
    of which the first ``n`` are real (default: all). With ``first_state`` it
    stops after the first selective-scan layer and leaves there that layer's
    state after those ``n`` tokens."""
    d = dims(cfg)
    n = jnp.int32(len(padded) if n is None else n)
    x = params["embed"][padded].astype(F32)
    seen: dict[str, int] = {}
    m = shared = None
    for i, kind in enumerate(d["kinds"]):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        lp = {k: v[at] for k, v in params[kind].items()}
        att = dict(heads=d["heads"], hd=d["hd"], bias=d["bias"], eps=d["eps"])
        if kind == "s6":
            x, m, s_n = _s6_layer(x, lp, n, N=d["N"], taps=d["taps"], rank=d["rank"], eps=d["eps"])  # the last one's m stays
            if first_state is not None:
                first_state.append(np.asarray(s_n))
                break
        elif kind == "gmu":
            x = _gmu_layer(x, lp, m, eps=d["eps"])
        elif kind == "cross":
            x = _attending_layer(x, lp, *shared, jnp.int32(i), window=0, **att)
        else:
            k, v = _keys_values(x, lp, kv_heads=d["kv_heads"], hd=d["hd"], bias=d["bias"], eps=d["eps"])
            if kind == "attention":
                shared = (k, v)
            x = _attending_layer(x, lp, k, v, jnp.int32(i), window=d["window"] if kind == "swa" else 0, **att)
    return x


def first_layer_state(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """The first selective-scan layer's state after exactly the tokens
    ``ids``: float32 [state size, channels] (a "head" of the state probe is
    one state index: the 5,120 channels that decay at one rate family)."""
    ids, padded = _pad(ids, pad_to)
    state: list = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, cfg, jnp.asarray(padded), n=len(ids), first_state=state)
    return state[0].T


def _final(params, cfg, x):
    return _ln(x, params["final_norm"], params["final_norm_bias"], dims(cfg)["eps"])


def token_logits(params: dict, cfg: dict, ids) -> np.ndarray:
    """Logits [len(ids), vocabulary] of a short sequence (tests)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(np.asarray(ids, np.int32)))
        return np.asarray(_final(params, cfg, x) @ params["embed"].astype(F32).T)


@functools.partial(jax.jit, static_argnames=("block",))
def _vocab_logprobs(h, head, targets, *, block):
    """log softmax(h @ head.T)[targets], the vocabulary in blocks."""
    V = head.shape[0]
    lse = jnp.full((h.shape[0],), -jnp.inf, F32)
    picked = jnp.zeros((h.shape[0],), F32)
    for lo in range(0, V, block):
        logits = h @ head[lo : lo + block].astype(F32).T
        width = logits.shape[1]
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(logits, axis=-1))
        here = (targets >= lo) & (targets < lo + width)
        idx = jnp.clip(targets - lo, 0, width - 1)
        picked = jnp.where(here, jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0], picked)
    return picked - lse


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    ids, padded = _pad(ids, pad_to)
    n = len(ids)
    targets = np.zeros(pad_to, np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded))
        lp_all = _vocab_logprobs(_final(params, cfg, x), params["embed"], jnp.asarray(targets), block=16384)
    return np.asarray(lp_all, np.float32)[: n - 1]
