"""Plain reference of the ``sdar_moe`` decoder (SDAR-30B-A3B-Chat) and of its
generation by diffusion over blocks.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no sort, nothing imported from the program.
It routes FOR ITSELF (the experts of a token are the top-k of its own float32
softmax, every expert is applied to every token under a gate that is 0 where
the token did not choose it) and reads the configuration file's published
keys, its ``assumed`` block and the seeded weight tree of ``sdar_weights.py``.

The layer (Qwen3-MoE's, key for key):
  h  = h + attn_l(rmsnorm(h, input_norm_l));  h = h + moe_l(rmsnorm(h, post_attn_norm_l))
  logits = rmsnorm(h, final_norm) @ lm_head^T                     (untied)
  rmsnorm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)
attention: q, k, v, out without bias; RMSNorm over each head of q and of k
  (``assumed.qk_norm``), then the rotary embedding (rotate-half over the whole
  head, ``rope_theta``, no scaling); softmax of q k^T / sqrt(head size) over
  the keys the BLOCK-CAUSAL mask allows
experts: s = softmax(W_g u) over all ``num_experts``; the top
  ``num_experts_per_tok`` of s, their gates s_e / (sum of the chosen s)
  (``norm_topk_prob``); out = sum_e gate_e W2_e (silu(W1_e u) * W3_e u). No
  shared expert, no capacity, no token dropped.

Generation (the family's published script, as this repo's ISSUE 58 wrote it
down: every item is under the configuration file's ``assumed``):
  * positions go in blocks of ``block_length`` B by ABSOLUTE position
    (position j is in block j // B); a query sees every key of the blocks up
    to its own, its own both ways;
  * a block in flight holds the embedding of ``mask_token_id`` at every
    position not committed yet. A denoise pass scores the block's B rows
    against the CLEAN keys and values of the blocks before it and the block's
    own rows, and the log-probability of a candidate is read at ITS OWN
    position's row (no shift);
  * a block's keys and values, as later blocks see them, are those of the
    whole block clean.

So the log-probability of a generated token, given the pass of its block that
committed it, is a function of the ids, the prompt's length and the pass
numbers: ``trace_logprobs``. It evaluates one pass number of ALL blocks in one
forward, in the two-stream form block-diffusion training uses: a clean stream
of the ids under the block-causal mask, and a noisy stream whose block b holds
the mask wherever a position's pass number is not smaller than the one
evaluated, attending the clean stream's keys and values of blocks < b and its
own rows of block b. ``trace_logprobs_by_block`` is the same mathematics the
way generation runs it, a block at a time over a growing list of clean blocks'
keys and values; a CPU test holds the two to each other.

``token_logprobs`` is what a check that has the ids alone can say: every block
taken as generated whole under ``sequential`` at the configuration's
``denoising_steps`` (position j's pass is (j mod B) // (B / steps)). That is
exact for every block a request generated whole, and for the one block that
holds the prompt's end when (prompt length mod B) is 0 or B / 2 at two passes a
block; otherwise up to three tokens of a request are evaluated in a
neighbouring state (the kind ``rollout_family_trace`` measures it).

Departures from the published model, each on purpose:
  * weights are random (``sdar_weights.py``), norms too;
  * the per-head RMSNorm of q and k and everything about generation are the
    family's published implementation, not keys of its ``config.json``;
  * the sequence is padded to a fixed length so one program serves every
    sample; the last block's positions past the sequence hold the mask, as
    they do in a served request whose last block is cut by its budget, and
    whole blocks of padding are reached by nothing (the mask is block-causal);
  * where two router scores tie exactly, the lower expert index wins
    (``jax.lax.top_k``);
  * queries are taken ``QUERY_BLOCK`` at a time and one KV head at a time, so
    that 4,096 positions in two streams fit beside the weights.
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
QUERY_BLOCK = 512
NEVER = 1 << 20  # the pass number of a position that is never committed: the mask at every pass


def dims(cfg: dict) -> dict:
    """The family's sizes from the configuration file's published keys and
    its ``assumed`` block."""
    a = cfg["assumed"]
    return {
        "D": int(cfg["hidden_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["num_experts"]),
        "K": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "qk_norm": bool(a["qk_norm"]),
        "B": int(a["block_length"]),
        "mask_id": int(a["mask_token_id"]),
        "steps": int(a["denoising_steps"]),
    }


def sequential_passes(n: int, B: int, steps: int, start: int = 0) -> np.ndarray:
    """The pass that commits each of ``n`` positions from absolute position
    ``start`` when every block is generated whole under ``sequential``: the
    first B / steps positions of a block in pass 0, the next in pass 1."""
    return ((start + np.arange(n)) % B) // (B // steps)


def _rotate(t, pos, theta):
    half = t.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    turned = jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)
    return t * cos + turned * sin


def _qkv(x, lp, pos, d):
    """x [T, D] at positions ``pos`` -> q [T, heads, hd], k, v [T, kv_heads, hd], rotated."""
    T = x.shape[0]
    h = _rms(x, lp["input_norm"], d["eps"])
    q = (h @ lp["wq"].astype(F32)).reshape(T, d["heads"], d["hd"])
    k = (h @ lp["wk"].astype(F32)).reshape(T, d["kv_heads"], d["hd"])
    v = (h @ lp["wv"].astype(F32)).reshape(T, d["kv_heads"], d["hd"])
    if d["qk_norm"]:
        q, k = _rms(q, lp["q_norm"], d["eps"]), _rms(k, lp["k_norm"], d["eps"])
    return _rotate(q, pos, d["theta"]), _rotate(k, pos, d["theta"]), v


def _attend(q, k, v, allowed, d):
    """softmax(q k^T / sqrt(hd)) v over the keys ``allowed`` [Tq, Tk] lets each
    query see, a KV head and ``QUERY_BLOCK`` queries at a time -> [Tq, heads * hd]."""
    g = d["heads"] // d["kv_heads"]
    outs = []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        ql, ok = q[lo : lo + QUERY_BLOCK], allowed[lo : lo + QUERY_BLOCK]
        heads = []
        for j in range(d["kv_heads"]):
            s = jnp.einsum("tgd,sd->gts", ql[:, j * g : (j + 1) * g, :], k[:, j, :]) * d["hd"] ** -0.5
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            heads.append(jnp.einsum("gts,sd->tgd", p, v[:, j, :]))
        outs.append(jnp.concatenate(heads, axis=1))
    return jnp.concatenate(outs, axis=0).reshape(q.shape[0], d["heads"] * d["hd"])


def route(u, w_router, *, top_k: int, norm_topk: bool):
    """u [T, D] float32 -> the gate of every expert for every token [T, E], 0
    where the token did not choose it."""
    s = jax.nn.softmax(u @ w_router.astype(F32), axis=-1)
    picked, chosen = jax.lax.top_k(s, top_k)
    if norm_topk:
        picked = picked / picked.sum(-1, keepdims=True)
    onehot = chosen[:, :, None] == jnp.arange(s.shape[-1])[None, None, :]
    return jnp.sum(jnp.where(onehot, picked[:, :, None], 0.0), axis=1)


def _experts(x, lp, d):
    u = _rms(x, lp["post_attn_norm"], d["eps"])
    gates = route(u, lp["w_router"], top_k=d["K"], norm_topk=d["norm_topk"])

    def one(acc, ew):  # every expert on every token; its gate is 0 where not chosen
        w1, w3, w2, g = ew
        y = (jax.nn.silu(u @ w1.astype(F32)) * (u @ w3.astype(F32))) @ w2.astype(F32)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["we_gate"], lp["we_up"], lp["we_down"], gates.T))
    return x + out


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("dd",))
def _two_stream_layer(xc, xn, lp, *, dd):
    """One layer over a clean stream ``xc`` and a noisy stream ``xn`` of the
    same T positions: the clean stream attends itself under the block-causal
    mask; a noisy row of block b attends the clean keys of blocks < b and the
    noisy keys of block b."""
    d = dict(dd)
    T = xc.shape[0]
    pos = jnp.arange(T)
    blk = pos // d["B"]
    qc, kc, vc = _qkv(xc, lp, pos, d)
    qn, kn, vn = _qkv(xn, lp, pos, d)
    before, own = blk[:, None] > blk[None, :], blk[:, None] == blk[None, :]
    ac = _attend(qc, kc, vc, before | own, d)
    an = _attend(qn, jnp.concatenate([kc, kn]), jnp.concatenate([vc, vn]), jnp.concatenate([before, own], axis=1), d)
    both = jnp.concatenate([xc + ac @ lp["wo"].astype(F32), xn + an @ lp["wo"].astype(F32)])
    both = _experts(both, lp, d)
    return both[:T], both[T:]


def _noisy_hidden(params: dict, d: dict, clean, noisy) -> jax.Array:
    """The noisy stream's last hidden rows [T, D] (before the final norm)."""
    xc, xn = params["embed"][clean].astype(F32), params["embed"][noisy].astype(F32)
    for i in range(d["L"]):
        xc, xn = _two_stream_layer(xc, xn, {k: v[i] for k, v in params["layers"].items()}, dd=_freeze(d))
    return xn


def _passes_of(d: dict, n: int, prompt_len: int, passes, pad_to: int) -> np.ndarray:
    """The pass number of every position of the padded sequence: -1 for the
    prompt's (clean at every pass), the given ones for the generated, ``NEVER``
    past the sequence."""
    full = np.full(pad_to, NEVER, np.int64)
    full[:prompt_len] = -1
    full[prompt_len:n] = np.asarray(passes, np.int64)
    return full


def trace_logprobs(params: dict, cfg: dict, ids, prompt_len: int, passes, pad_to: int) -> np.ndarray:
    """The log-probability of every generated token ``ids[prompt_len:]`` at
    the pass of its block that committed it (``passes``, one a generated token,
    0-based within its block): its block holding the mask wherever a generated
    position's pass number is not smaller, the prompt's positions clean
    throughout, the blocks before it clean. float32 numpy
    [len(ids) - prompt_len]."""
    d = dims(cfg)
    ids, padded = _pad(ids, pad_to)
    n = len(ids)
    when = _passes_of(d, n, prompt_len, passes, pad_to)
    out = np.zeros(n, np.float32)
    with jax.default_matmul_precision("highest"):
        for p in sorted(set(int(x) for x in when[prompt_len:n])):
            noisy = np.where(when < p, padded, d["mask_id"]).astype(np.int32)
            x = _noisy_hidden(params, d, jnp.asarray(padded), jnp.asarray(noisy))
            lp = _vocab_logprobs(x, params["final_norm"], params["lm_head"], jnp.asarray(padded), eps=d["eps"], scaling=1.0, block=16384)
            here = when[:n] == p
            out[here] = np.asarray(lp, np.float32)[:n][here]
    return out[prompt_len:]


def token_logprobs(params: dict, cfg: dict, ids, pad_to: int) -> np.ndarray:
    """What the ids alone give, in the other references' layout (entry j - 1
    is token j's, for j = 1..len(ids)-1): every block taken as generated whole
    under ``sequential`` at the configuration's ``denoising_steps``."""
    d = dims(cfg)
    return trace_logprobs(params, cfg, ids, 0, sequential_passes(len(ids), d["B"], d["steps"]), pad_to)[1:]


# ---------------------------------------------------------------------------
# the same mathematics the way generation runs it: a block at a time
# ---------------------------------------------------------------------------


def _block_pass(params: dict, d: dict, block_ids, start: int, past: list):
    """One pass over the block at positions ``start``.. with inputs
    ``block_ids`` [B]: (log-probabilities over the vocabulary at every row
    [B, V], the rows' keys and values a layer). ``past``: a layer's (k, v) of
    the clean blocks before it, or None."""
    B = len(block_ids)
    pos = start + jnp.arange(B)
    x = params["embed"][jnp.asarray(block_ids)].astype(F32)
    kv = []
    for i in range(d["L"]):
        lp = {k: v[i] for k, v in params["layers"].items()}
        q, k, v = _qkv(x, lp, pos, d)
        kv.append((k, v))
        if past[i] is not None:
            k, v = jnp.concatenate([past[i][0], k]), jnp.concatenate([past[i][1], v])
        a = _attend(q, k, v, jnp.ones((B, k.shape[0]), bool), d)
        x = _experts(x + a @ lp["wo"].astype(F32), lp, d)
    logits = _rms(x, params["final_norm"], d["eps"]) @ params["lm_head"].astype(F32).T
    return jax.nn.log_softmax(logits, axis=-1), kv


def trace_logprobs_by_block(params: dict, cfg: dict, ids, prompt_len: int, passes) -> np.ndarray:
    """``trace_logprobs`` as generation computes it: block after block, every
    pass of a block over the clean blocks' keys and values kept so far, then the
    clean block's own appended. Small sizes only (every pass is its own
    forward)."""
    d = dims(cfg)
    B = d["B"]
    ids = np.asarray(ids, np.int32)
    n = len(ids)
    total = -(-n // B) * B
    when = _passes_of(d, n, prompt_len, passes, total)
    padded = np.zeros(total, np.int32)
    padded[:n] = ids
    out = np.zeros(n, np.float32)
    past = [None] * d["L"]
    with jax.default_matmul_precision("highest"):
        for start in range(0, total, B):
            sl = slice(start, start + B)
            for p in sorted(set(int(x) for x in when[sl] if 0 <= x < NEVER)):
                logp, _ = _block_pass(params, d, np.where(when[sl] < p, padded[sl], d["mask_id"]), start, past)
                for j in range(B):
                    if when[start + j] == p:
                        out[start + j] = float(logp[j, padded[start + j]])
            _, kv = _block_pass(params, d, padded[sl], start, past)  # the commit pass: the block clean
            past = [kv[i] if past[i] is None else tuple(jnp.concatenate([a, b]) for a, b in zip(past[i], kv[i])) for i in range(d["L"])]
    return out[prompt_len:]
