"""Plain reference of the hybrid decoder ``granitemoehybrid`` without experts
(HF ``GraniteMoeHybridForCausalLM``): Mamba-2 layers beside GQA attention
layers, in the order of the published ``layer_types``.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, nothing imported from the program. The
state-space recurrence is the recurrence itself, one token after another
(``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
per head), not the chunked algorithm the program prefills with. It reads the
configuration file's published keys and the seeded weight tree of
``hybrid_weights.py``. One layer is cast to float32 at a time and the
vocabulary is read in blocks, so that it runs at the published widths on the
chip once the engine is gone.

The model, as the configuration's source publishes it:
  h0 = embed[ids] * embedding_multiplier
  h  = h + residual_multiplier * mixer_l(rmsnorm(h, input_norm_l))
  h  = h + residual_multiplier * W_down (silu(g) * u),  [g | u] = W_gate_up rmsnorm(h, post_norm_l)
  logits = rmsnorm(h, final_norm) @ embed^T / logits_scaling
attention mixer: q, k, v, o without bias, no rotary embedding, causal softmax
of attention_multiplier * q k^T; Mamba-2 mixer: [z | xBC | dt] = W_in x,
xBC_t = silu(b + sum_j w_j xBC_raw_{t-K+1+j}) depthwise, [x | B | C] = xBC,
dt = softplus(dt + dt_bias), A = -exp(A_log), the recurrence above,
y = rmsnorm(y * silu(z), norm_w) per group of channels, out = W_out y.

Departures from the published model, each on purpose:
  * weights are random (``hybrid_weights.py``), norms and the conv bias too;
  * the sequence is padded to a fixed length so one program serves every
    sample (everything is causal: the padding cannot reach a real position).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys."""
    H, P, N, G = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    kinds = list(cfg["layer_types"])
    hd = int(cfg.get("head_dim") or cfg["assumed"]["head_dim"])
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["shared_intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "kinds": kinds,
        "n_mamba": kinds.count("mamba"),
        "n_attention": kinds.count("attention"),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": hd,
        "H": H,
        "P": P,
        "N": N,
        "G": G,
        "K": int(cfg["mamba_d_conv"]),
        "d_inner": H * P,
        "conv_dim": H * P + 2 * G * N,
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _mlp(x, lp, eps, rm):
    gu = _rms(x, lp["post_norm"], eps) @ lp["w_gate_up"].astype(F32)
    g, u = jnp.split(gu, 2, axis=-1)
    return x + rm * ((jax.nn.silu(g) * u) @ lp["w_down"].astype(F32))


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "rm", "scale"))
def _attention_layer(x, lp, *, heads, kv_heads, hd, eps, rm, scale):
    T = x.shape[0]
    h = _rms(x, lp["input_norm"], eps)
    q = (h @ lp["wq"].astype(F32)).reshape(T, heads, hd)
    k = (h @ lp["wk"].astype(F32)).reshape(T, kv_heads, hd)
    v = (h @ lp["wv"].astype(F32)).reshape(T, kv_heads, hd)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    g = heads // kv_heads
    outs = []
    for j in range(kv_heads):  # one KV head at a time: scores are [g, T, T]
        s = jnp.einsum("tgd,sd->gts", q[:, j * g : (j + 1) * g, :], k[:, j, :]) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", p, v[:, j, :]))
    a = jnp.concatenate(outs, axis=1).reshape(T, heads * hd)
    return _mlp(x + rm * (a @ lp["wo"].astype(F32)), lp, eps, rm)


@functools.partial(jax.jit, static_argnames=("H", "P", "N", "G", "K", "eps", "rm"))
def _mamba_layer(x, lp, n, *, H, P, N, G, K, eps, rm):
    """(the layer's output [T, D], the state S after the first ``n`` tokens [H, P, N])."""
    T = x.shape[0]
    d_inner, gn = H * P, G * N
    zxbcdt = _rms(x, lp["input_norm"], eps) @ lp["in_proj"].astype(F32)
    z, raw, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    w = lp["conv_w"].astype(F32)[:, 0, :]  # tap j of channel c: conv_w[j, 0, c]
    padded = jnp.pad(raw, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["conv_b"].astype(F32) + sum(w[j] * padded[j : j + T] for j in range(K)))
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    xs = xs.reshape(T, H, P)
    b = jnp.repeat(b.reshape(T, G, N), H // G, axis=1)  # a group's B and C serve its heads
    c = jnp.repeat(c.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))  # [T, H]
    # the padding past token n must not enter the state that is handed back
    # (dt = 0: the state stands still); no real position sees the padding
    dt = jnp.where(jnp.arange(T)[:, None] < n, dt, 0.0)
    a = -jnp.exp(lp["A_log"].astype(F32))
    d_skip = lp["D"].astype(F32)

    def token(s, t):
        x_t, b_t, c_t, dt_t = t
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d_skip[:, None] * x_t

    s_n, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, b, c, dt))
    y = (y.reshape(T, d_inner) * jax.nn.silu(z)).reshape(T, G, d_inner // G)
    y = _rms(y, lp["ssm_norm"].reshape(G, d_inner // G), eps).reshape(T, d_inner)
    return _mlp(x + rm * (y @ lp["out_proj"].astype(F32)), lp, eps, rm), s_n


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "block"))
def _vocab_logprobs(x, final_norm, head, targets, *, eps, scaling, block):
    """log softmax(h @ head.T / scaling)[targets], the vocabulary in blocks."""
    h = _rms(x, final_norm, eps)
    V = head.shape[0]
    lse = jnp.full((h.shape[0],), -jnp.inf, F32)
    picked = jnp.zeros((h.shape[0],), F32)
    for lo in range(0, V, block):
        logits = (h @ head[lo : lo + block].astype(F32).T) / scaling
        n = logits.shape[1]
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(logits, axis=-1))
        idx = jnp.clip(targets - lo, 0, n - 1)
        here = (targets >= lo) & (targets < lo + n)
        picked = jnp.where(here, jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0], picked)
    return picked - lse


def hidden_states(params: dict, cfg: dict, padded, n=None, first_state: list | None = None) -> jax.Array:
    """The last layer's output [T, D] (before the final norm) for tokens [T],
    of which the first ``n`` are real (default: all). With ``first_state`` it
    stops after the first state-space layer and leaves there that layer's
    state after those ``n`` tokens."""
    d = dims(cfg)
    n = jnp.int32(len(padded) if n is None else n)
    eps, rm = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    x = params["embed"][padded].astype(F32) * float(cfg["embedding_multiplier"])
    seen = {"mamba": 0, "attention": 0}
    for kind in d["kinds"]:
        lp = {k: v[seen[kind]] for k, v in params[kind].items()}
        seen[kind] += 1
        if kind == "mamba":
            x, s_n = _mamba_layer(x, lp, n, H=d["H"], P=d["P"], N=d["N"], G=d["G"], K=d["K"], eps=eps, rm=rm)
            if first_state is not None:
                first_state.append(np.asarray(s_n))
                break
        else:
            x = _attention_layer(
                x, lp, heads=d["heads"], kv_heads=d["kv_heads"], hd=d["hd"], eps=eps, rm=rm,
                scale=float(cfg["attention_multiplier"]),
            )
    return x


def _pad(ids, pad_to: int):
    ids = np.asarray(ids, np.int32)
    if len(ids) > pad_to:
        raise ValueError(f"sequence of {len(ids)} tokens exceeds pad_to={pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[: len(ids)] = ids
    return ids, padded


def first_layer_state(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """The first state-space layer's state after exactly the tokens ``ids``:
    float32 [heads, head size, state size]."""
    ids, padded = _pad(ids, pad_to)
    state: list = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, cfg, jnp.asarray(padded), n=len(ids), first_state=state)
    return state[0]


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    ids, padded = _pad(ids, pad_to)
    n = len(ids)
    targets = np.zeros(pad_to, np.int32)
    targets[: n - 1] = ids[1:]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, cfg, jnp.asarray(padded))
        head = params["embed"] if cfg["tie_word_embeddings"] else params["lm_head"]
        lp_all = _vocab_logprobs(
            x, params["final_norm"], head, jnp.asarray(targets),
            eps=float(cfg["rms_norm_eps"]), scaling=float(cfg["logits_scaling"]), block=16384,
        )
    return np.asarray(lp_all, np.float32)[: n - 1]
