"""Plain reference of the Qwen2 decoder, as published (HF ``Qwen2ForCausalLM``).

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no scan, nothing imported from the program.
It reads the configuration file's published keys and the seeded weight tree
of ``weights.py``. Memory is bounded so that it runs beside a serving engine
at 7B widths: one layer is cast to float32 at a time, attention goes one KV
head at a time, and the vocabulary is read in blocks.

Departures from the published model, each on purpose:
  * weights are random (``weights.py``), norms and biases included;
  * the sequence is padded to a fixed length so one program serves every
    sample (causal attention: the padding cannot reach a real position).

``int8=True`` is the *control* of the output check: the same mathematics
with every matrix multiplication in int8, the step below bfloat16 that would
tempt a later PR (the chip's int8 rate is twice its bf16 rate): weights
rounded per output channel, the activations entering each matmul and the
keys and values per token; in the backward pass the cotangent entering each
matmul per token too. It must come out as not correct.

``grpo_step`` is the reference of one training step: the GRPO/PPO-clip loss
of a batch and its gradient, one sequence at a time and one layer at a time
(a layer's input is kept, its inside recomputed), then AdamW as published
(Loshchilov & Hutter) on the leaves it keeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import peaks

F32 = jnp.float32


def _fake_int8(w, axis):
    """Symmetric per-output-channel int8 rounding, returned in float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _a(x, int8: bool):
    """Keys, values and embedding rows: per-token int8 under the control
    (rounding has no slope, so the gradient passes straight through)."""
    return x + jax.lax.stop_gradient(_fake_int8(x, axis=-1) - x) if int8 else x


@jax.custom_vjp
def _int8_mm(a, w):
    return _fake_int8(a, -1) @ _fake_int8(w, 0)


def _int8_mm_fwd(a, w):
    a8, w8 = _fake_int8(a, -1), _fake_int8(w, 0)
    return a8 @ w8, (a8, w8)


def _int8_mm_bwd(res, g):
    a8, w8 = res
    g8 = _fake_int8(g, -1)
    return g8 @ w8.T, a8.T @ g8


_int8_mm.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def _mm(a, w, int8: bool):
    """a [T, k] @ w [k, n]. Under the control an int8 multiplication, forward
    and backward: a and the cotangent per token, w per output channel."""
    w = w.astype(F32)
    return _int8_mm(a, w) if int8 else a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x [T, heads, hd]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "eps", "theta", "int8"))
def _layer(x, lp, *, n_heads, n_kv, hd, eps, theta, int8):
    """One decoder block on x [T, D]; lp is this layer's weights."""
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, lp["input_norm"], eps)
    q = _mm(h, lp["wq"], int8) + lp["bq"].astype(F32)
    k = _mm(h, lp["wk"], int8) + lp["bk"].astype(F32)
    v = _mm(h, lp["wv"], int8) + lp["bv"].astype(F32)
    q = _rope(q.reshape(T, n_heads, hd), pos, theta)
    k = _a(_rope(k.reshape(T, n_kv, hd), pos, theta), int8)
    v = _a(v.reshape(T, n_kv, hd), int8)
    causal = pos[:, None] >= pos[None, :]
    g = n_heads // n_kv
    outs = []
    for j in range(n_kv):  # one KV head at a time: scores are [g, T, T]
        qj = q[:, j * g : (j + 1) * g, :]
        s = jnp.einsum("tgd,sd->gts", qj, k[:, j, :]) / jnp.sqrt(F32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("gts,sd->tgd", p, v[:, j, :]))
    a = jnp.concatenate(outs, axis=1).reshape(T, n_heads * hd)
    x = x + _mm(a, lp["wo"], int8)
    h = _rms(x, lp["post_attn_norm"], eps)
    m = jax.nn.silu(_mm(h, lp["w_gate"], int8)) * _mm(h, lp["w_up"], int8)
    return x + _mm(m, lp["w_down"], int8)


def _vocab_logprobs(x, final_norm, head, targets, eps, int8, block):
    """log softmax(h @ head.T)[targets], the vocabulary read in blocks."""
    h = _rms(x, final_norm, eps)
    V = head.shape[0]
    lse = jnp.full((h.shape[0],), -jnp.inf, F32)
    picked = jnp.zeros((h.shape[0],), F32)
    for lo in range(0, V, block):
        logits = _mm(h, head[lo : lo + block].T, int8)
        n = logits.shape[1]
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(logits, axis=-1))
        idx = jnp.clip(targets - lo, 0, n - 1)
        here = (targets >= lo) & (targets < lo + n)
        picked = jnp.where(here, jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0], picked)
    return picked - lse


_head_logprobs = jax.jit(_vocab_logprobs, static_argnames=("eps", "int8", "block"))


def _model_kw(cfg: dict, int8: bool) -> dict:
    return dict(
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        hd=peaks.dims(cfg)["hd"],
        eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]),
        int8=bool(int8),
    )


@functools.partial(jax.jit, static_argnames=("int8",))
def _embed_rows(emb, ids, *, int8):
    return _a(emb[ids].astype(F32), int8)  # per-row scale of the table, as for any matrix


def _padded(ids, pad_to: int):
    """(tokens, next tokens) of one sequence, zero-padded to ``pad_to``."""
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad_to={pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = ids
    targets = np.zeros(pad_to, np.int32)
    targets[: n - 1] = ids[1:]
    return jnp.asarray(padded), jnp.asarray(targets)


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """log p(ids[t] | ids[:t]) for t = 1..len(ids)-1, as float32 numpy."""
    padded, targets = _padded(ids, pad_to)
    kw = _model_kw(cfg, False)
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(params["embed"], padded, int8=kw["int8"])
        for i in range(int(cfg["num_hidden_layers"])):
            lp = {k: v[i] for k, v in params["layers"].items()}
            x = _layer(x, lp, **kw)
        head = params["embed"] if cfg["tie_word_embeddings"] else params["lm_head"]
        lp_all = _head_logprobs(
            x, params["final_norm"], head, targets, eps=kw["eps"], int8=kw["int8"], block=16384
        )
    return np.asarray(lp_all, np.float32)[: len(ids) - 1]


# ---- one training step ----------------------------------------------------


def _sumsq(tree):
    return sum(jnp.sum(jnp.square(v)) for v in jax.tree.leaves(tree))


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "eps", "theta", "int8", "keep"))
def _layer_vjp(x, lp, g, *, keep, **kw):
    """Cotangent ``g`` of a block's output pulled back to its input and its
    weights (the block is recomputed from its input). Returns the input's
    cotangent, the gradients of the leaves named in ``keep``, and the sum of
    squares of all of them."""
    _, pull = jax.vjp(lambda x_, lp_: _layer(x_, lp_, **kw), x, lp)
    dx, dlp = pull(g)
    return dx, {k: dlp[k] for k in keep}, _sumsq(dlp)


@functools.partial(jax.jit, static_argnames=("eps", "int8", "block", "clip_eps", "cap"))
def _head_loss_vjp(x, final_norm, head, targets, prox, old, adv, mask, *, eps, int8, block, clip_eps, cap):
    """Sum over one sequence's masked positions of the decoupled PPO-clip
    loss (GRPO: token-level ratio against the proximal policy, clipped to
    1 +- clip_eps; the behaviour weight pi_prox/pi_behave dropped where it
    exceeds ``cap``), and its gradient at the last hidden state, the final
    norm and the output head."""

    def f(x_, fn_, head_):
        lp = _vocab_logprobs(x_, fn_, head_, targets, eps, int8, block)
        ratio = jnp.exp(lp - prox)
        pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps))
        w = jnp.exp(prox - old)
        w = jnp.where(w > cap, 0.0, w)
        return jnp.sum(jnp.where(mask, pg * w, 0.0))

    return jax.value_and_grad(f, argnums=(0, 1, 2))(x, final_norm, head)


def grpo_grads(params: dict, cfg: dict, seqs: list[dict], loss: dict, keep: dict, pad_multiple: int, int8: bool = False) -> dict:
    """Loss of the batch (sum over masked positions / their count) and its
    gradient on the leaves ``keep`` names, in float32.

    Position t of a sequence (t < n-1) scores token t+1 and carries the
    batch's ``prox_logprobs``, ``old_logprobs``, ``advantages`` and
    ``loss_mask`` at t. ``keep`` = {"layers": [...], "matrices": [...],
    "vectors": [...], "rows": R}: the named matrices of the listed layers,
    the named per-layer vectors of every layer, the final norm, and the
    first R rows of the embedding (and of an untied head).

    ``norm_bound`` is an upper bound of the whole gradient's norm: the sum
    over sequences of each sequence's own gradient norm."""
    kw = _model_kw(cfg, int8)
    L = int(cfg["num_hidden_layers"])
    tied = bool(cfg["tie_word_embeddings"])
    R = min(int(keep["rows"]), int(cfg["vocab_size"]))
    big, small = tuple(keep["matrices"]), tuple(keep["vectors"])
    acc: dict = {}

    def add(name, g):
        acc[name] = acc[name] + g if name in acc else g

    total = count = bound = 0.0
    with jax.default_matmul_precision("highest"):
        p32 = lambda t: jax.tree.map(lambda v: v.astype(F32), t)  # noqa: E731
        head = params["embed"] if tied else params["lm_head"]
        for s in seqs:
            ids = np.asarray(s["input_ids"], np.int32)
            n = len(ids)
            T = -(-n // pad_multiple) * pad_multiple
            padded, targets = _padded(ids, T)

            def per_token(key, dtype=np.float32):
                a = np.zeros(T, dtype)
                a[: n - 1] = np.asarray(s[key])[: n - 1]
                return jnp.asarray(a)

            mask = per_token("loss_mask") > 0
            count += float(mask.sum())
            xs = [_embed_rows(params["embed"], padded, int8=kw["int8"])]
            for i in range(L):
                xs.append(_layer(xs[-1], {k: v[i] for k, v in params["layers"].items()}, **kw))
            val, (dx, dfn, dhead) = _head_loss_vjp(
                xs[-1], params["final_norm"].astype(F32), head.astype(F32), targets,
                per_token("prox_logprobs"), per_token("old_logprobs"), per_token("advantages"), mask,
                eps=kw["eps"], int8=kw["int8"], block=16384,
                clip_eps=float(loss["eps_clip"]), cap=float(loss["behave_imp_weight_cap"]),
            )
            total += float(val)
            sq = _sumsq((dfn, dhead))
            add("final_norm", dfn)
            add("embed" if tied else "lm_head", dhead[:R])
            del dhead
            for i in reversed(range(L)):
                lp = p32({k: v[i] for k, v in params["layers"].items()})
                dx, dlp, sq_i = _layer_vjp(xs[i], lp, dx, keep=small + big, **kw)  # one program for every layer
                sq = sq + sq_i
                for k in small + (big if i in keep["layers"] else ()):
                    add(f"{k}.{i}", dlp[k])
                xs.pop()
            # the looked-up rows of the embedding (every real and padded position)
            rows = jnp.zeros((R, dx.shape[1]), F32).at[padded].add(dx, mode="drop")
            add("embed", rows)
            bound += float(jnp.sqrt(sq)) + float(jnp.sum(jnp.linalg.norm(dx, axis=-1)))
    denom = max(count, 1.0)
    grads = {k: np.asarray(v, np.float32) / denom for k, v in acc.items()}
    out = {"final_norm": grads["final_norm"], "embed": grads["embed"]}
    if not tied:
        out["lm_head"] = grads["lm_head"]
    for k in small:
        out[k] = np.stack([grads[f"{k}.{i}"] for i in range(L)])
    for i in keep["layers"]:
        for k in big:
            out[f"{k}.{i}"] = grads[f"{k}.{i}"]
    return {"loss": total / denom, "grads": out, "norm_bound": bound / denom}


def adamw_delta(p0: np.ndarray, g: np.ndarray, opt: dict, steps: int, dtype) -> np.ndarray:
    """p_after - p0 after ``steps`` AdamW steps on the same gradient ``g``,
    where only the last step has a learning rate above 0 (linear warm-up from
    0 over ``steps - 1`` steps, so the parameters and hence ``g`` stay put
    until then). Moments in float32; the parameter and the update it takes
    are rounded to ``dtype``, the type the trainer keeps them in."""
    b1, b2 = float(opt["beta1"]), float(opt["beta2"])
    g = g.astype(np.float64)
    m = v = np.zeros_like(g)
    for _ in range(steps):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1**steps)) / (np.sqrt(v / (1 - b2**steps)) + float(opt["eps"]))
    p0 = jnp.asarray(p0, dtype)
    upd = jnp.asarray(-float(opt["lr"]) * (u + float(opt["weight_decay"]) * np.asarray(p0, np.float64)), dtype)
    return np.asarray((p0 + upd).astype(dtype), np.float32) - np.asarray(p0, np.float32)
